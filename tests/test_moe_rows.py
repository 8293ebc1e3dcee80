"""The expert layer's row movement over live rows (``ops/moe_rows.py``)
against ``jnp.take``, kernels interpreted on the CPU: the sorted-order form
bit for bit on every live row, the token-order form within a bf16 ulp of
the masked float32 einsum, and the layer above the size threshold against
the same call on the ``jnp.take`` path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import monitor
from apex_tpu.models import mellum as ml
from apex_tpu.ops import grouped_matmul as gmm
from apex_tpu.ops import moe_rows as mr
from apex_tpu.transformer import moe_dropless as md

BF16, F32 = jnp.bfloat16, jnp.float32
T, H, K, BM, TILES = 64, 512, 4, 16, 10
ROWS = TILES * BM
#: tiles_used: none, one, some, all (the worst case: nothing dropped)
USED = (0, 1, 4, TILES)


def _bits(a):
    return np.asarray(a.view(jnp.uint16), np.int32)


@pytest.fixture(scope="module")
def arrays():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    return dict(x=jax.random.normal(ks[0], (T, H), BF16),
                ys=jax.random.normal(ks[1], (ROWS, H), BF16),
                src=jax.random.randint(ks[2], (ROWS,), 0, T),
                scale=jax.random.uniform(ks[3], (ROWS,), F32) + 0.1)


# -- token order -> sorted order ------------------------------------------------

@pytest.mark.parametrize("used", USED)
def test_sorted_rows_are_take_bit_for_bit_on_every_live_row(arrays, used):
    got = mr.sorted_rows(arrays["x"], arrays["src"], jnp.int32(used),
                         block_m=BM, interpret=True)
    want = jnp.take(arrays["x"], arrays["src"], axis=0)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(_bits(got[:used * BM]),
                                  _bits(want[:used * BM]))


@pytest.mark.parametrize("used", USED)
def test_sorted_rows_scale_and_dot_in_the_same_pass(arrays, used):
    """The combine's cotangent: ``scale[r] * x[src[r]]`` as the float32
    product rounded once, and each row's float32 dot with ``dot_with``."""
    got, dot = mr.sorted_rows(arrays["x"], arrays["src"], jnp.int32(used),
                              block_m=BM, scale=arrays["scale"],
                              dot_with=arrays["ys"], interpret=True)
    rows = jnp.take(arrays["x"], arrays["src"], axis=0).astype(F32)
    want = (arrays["scale"][:, None] * rows).astype(BF16)
    n = used * BM
    np.testing.assert_array_equal(_bits(got[:n]), _bits(want[:n]))
    assert dot.shape == (ROWS,) and dot.dtype == F32
    np.testing.assert_allclose(
        np.asarray(dot[:n]),
        np.asarray((rows * arrays["ys"].astype(F32)).sum(-1)[:n]),
        rtol=1e-5, atol=1e-4)


def test_scale_and_dot_come_together(arrays):
    with pytest.raises(ValueError, match="together"):
        mr.sorted_rows(arrays["x"], arrays["src"], jnp.int32(1), block_m=BM,
                       scale=arrays["scale"], interpret=True)


# -- sorted order -> token order ------------------------------------------------

def _choices(used, active=None):
    """``(idx, ok)``: rows of the used tiles, token 0 with no row at all,
    token 1 with all of its ``K``, the others some."""
    n = max(used * BM, 1)
    idx = jax.random.randint(jax.random.PRNGKey(4), (T, K), 0, n)
    ok = jax.random.bernoulli(jax.random.PRNGKey(5), 0.4, (T, K))
    ok = ok.at[0].set(False).at[1].set(True) & (used > 0)
    if active is not None:
        ok = ok & active[:, None]
    return idx, ok


def _einsum(ys, idx, ok, wm):
    rows = jnp.take(ys, idx.reshape(-1), axis=0).reshape(T, K, H)
    rows = jnp.where(ok[:, :, None], rows.astype(F32), 0.0)
    return jnp.einsum("tk,tkh->th", wm, rows, precision="highest")


@pytest.mark.parametrize("weighted", [True, False])
@pytest.mark.parametrize("used", USED)
def test_token_rows_are_the_masked_float32_einsum(arrays, used, weighted):
    idx, ok = _choices(used)
    w = jax.random.uniform(jax.random.PRNGKey(6), (T, K), F32) + 0.1
    wm = jnp.where(ok, w, 0.0) if weighted else ok.astype(F32)
    got = mr.token_rows(arrays["ys"], jnp.where(ok, idx, -1), wm,
                        jnp.int32(used), block_m=BM, interpret=True)
    want = _einsum(arrays["ys"], idx, ok, wm).astype(BF16)
    assert got.shape == (T, H) and got.dtype == BF16
    assert np.abs(_bits(got) - _bits(want)).max() <= 1
    np.testing.assert_array_equal(_bits(got[0]), 0)     # a token with no row
    if not weighted:                # same sums in the same order: the bits
        np.testing.assert_array_equal(_bits(got), _bits(want))


def test_token_rows_under_an_active_mask_and_in_float32(arrays):
    active = jnp.arange(T) % 3 != 0
    idx, ok = _choices(TILES, active)
    wm = jnp.where(ok, 0.5, 0.0)
    got = mr.token_rows(arrays["ys"], jnp.where(ok, idx, -1), wm,
                        jnp.int32(TILES), block_m=BM, out_dtype=F32,
                        interpret=True)
    assert got.dtype == F32
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        _einsum(arrays["ys"], idx, ok, wm)), rtol=1e-6, atol=1e-6)
    assert not np.asarray(got)[~np.asarray(active)].any()


def _opened(x):
    """The layout of the module's doc, in plain XLA: row ``r`` at sublanes
    ``[r * S, r * S + h / 256)``, word ``(s, lane)`` = column ``s * 128 +
    lane`` below column ``h / 2 + s * 128 + lane``."""
    n, h = x.shape
    bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    words = bits[:, :h // 2] | (bits[:, h // 2:] << 16)
    return words.reshape(n, h // 256, 128)


@pytest.mark.parametrize("used", USED)
def test_open_tiles_lays_the_used_tiles_rows_out_as_words(arrays, used):
    got = mr.open_tiles(arrays["ys"], jnp.int32(used), block_m=BM,
                        interpret=True)
    s_rows = mr._row_sublanes(H)
    assert got.shape == (ROWS * s_rows, 128) and got.dtype == jnp.uint32
    got = got.reshape(ROWS, s_rows, 128)[:used * BM, :H // 256]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(
        _opened(arrays["ys"])[:used * BM]))


def test_open_rows_opens_every_row(arrays):
    s_rows = mr._row_sublanes(H)
    got = mr.open_rows(arrays["x"], interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got.reshape(T, s_rows, 128)[:, :H // 256]),
        np.asarray(_opened(arrays["x"])))


def test_a_blocks_live_slots_come_first_in_its_list():
    """``_live_slots``: every live (row, slot) of a block once, packed from
    the front, and the count."""
    idx = jnp.where(jax.random.bernoulli(jax.random.PRNGKey(7), 0.3, (64, 4)),
                    jax.random.randint(jax.random.PRNGKey(8), (64, 4), 0,
                                       ROWS), -1)
    idx = idx.at[:32].set(-1).at[32:36].set(5)      # a dead block; full rows
    live, keys, bits = mr._live_slots(idx, 32)
    assert bits == 7 and keys.shape == (2, 1, 128)
    assert live.tolist() == [0, int((idx[32:] >= 0).sum())]
    slots = np.asarray(idx[32:]).T.reshape(-1)      # choice-major
    want = sorted((int(r) << 7) | s for s, r in enumerate(slots) if r >= 0)
    assert sorted(np.asarray(keys)[1, 0, :len(want)].tolist()) == want
    assert not np.asarray(keys)[0].any()


def test_only_rows_that_fit_travel_as_words():
    assert mr.fits(2304, BF16) and mr.fits(7168, BF16)
    assert not mr.fits(2304, F32) and not mr.fits(2304, jnp.float16)
    assert not mr.fits(64, BF16)
    assert not mr.fits(2304 + 128, BF16)


# -- the layer ------------------------------------------------------------------------

def _share(h=256, dtype=BF16, **kw):
    cfg = ml.MellumConfig(**{**dict(
        vocab_size=96, hidden_size=h, num_heads=4, num_kv_heads=2,
        head_dim=32, moe_intermediate_size=128, n_routed_experts=16,
        num_experts_per_tok=8, layer_types=(ml.FULL,), sliding_window=48,
        rope_theta=10000.0, dtype=dtype, n_local_experts=4,
        first_expert=4), **kw})
    p = ml.init_params(cfg, jax.random.PRNGKey(0))["layer_0"]["moe"]
    return cfg, jax.tree.map(
        lambda a: a.astype(dtype) if a.ndim == 3 else a, p)


def _value_and_grads(cfg, p, x, g):
    def loss(p, x):
        y, st = md.expert_layer(cfg, p, x, interpret=True)
        return jnp.sum(y.astype(F32) * g), st
    return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(p, x)


def test_a_training_size_call_moves_live_rows_and_differentiates_as_take(
        monkeypatch):
    """8,192 tokens x top 8 = 65,536 assignments, the threshold: the rows
    move through the kernels (``rows_moved`` = the used tiles' rows), and the
    loss and its gradients to ``x``, the experts and the router are those of
    the same call on the ``jnp.take`` path."""
    cfg, p = _share()
    t = md.TRAIN_ASSIGNMENTS // cfg.num_experts_per_tok
    x = jax.random.normal(jax.random.PRNGKey(1), (t, 256), BF16)
    g = jax.random.normal(jax.random.PRNGKey(2), (t, 256), F32)
    (loss, st), grads = _value_and_grads(cfg, p, x, g)
    padded = gmm.num_tiles(4, md.BLOCK_M_TRAIN, t * 4) * md.BLOCK_M_TRAIN
    moved, handed = int(st["rows_moved"]), int(st["assignments_local"])
    assert moved % md.BLOCK_M_TRAIN == 0
    assert handed <= moved < handed + 4 * md.BLOCK_M_TRAIN < padded

    monkeypatch.setattr(md, "_moves_live_rows", lambda *a: False)
    (want, st), wants = _value_and_grads(cfg, p, x, g)
    assert int(st["rows_moved"]) == padded
    assert int(st["assignments_local"]) == handed
    assert float(loss) == pytest.approx(float(want), rel=1e-3, abs=1e-3)
    for (path, got), ref in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree.leaves(wants)):
        scale = float(jnp.max(jnp.abs(ref.astype(F32)))) + 1e-12
        err = float(jnp.max(jnp.abs(got.astype(F32) - ref.astype(F32))))
        assert err / scale < 2e-3, jax.tree_util.keystr(path)


def test_a_call_under_the_threshold_takes(monkeypatch):
    """A prompt's or a decode step's call keeps ``jnp.take``: no row
    kernel is traced, and ``rows_moved`` is the whole padded buffer."""
    for name in ("sorted_rows", "token_rows"):
        monkeypatch.setattr(mr, name, lambda *a, **kw: pytest.fail(name))
    cfg, p = _share()
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 256), BF16)
    _, st = md.expert_layer(cfg, p, x, interpret=True)
    bm = md._block_m(512 * 8)
    assert int(st["rows_moved"]) == gmm.num_tiles(4, bm, 512 * 4) * bm
    assert not md._moves_live_rows(8191, 8, 2304, BF16)
    assert md._moves_live_rows(16384, 8, 2304, BF16)
    assert not md._moves_live_rows(16384, 8, 2304, F32)      # rows of 4 bytes
    assert not md._moves_live_rows(16384 + 8, 8, 2304, BF16)  # no whole blocks


def test_a_serve_round_hands_out_the_counters_it_had():
    """``rows_moved`` is a constant of a serve program's shape: the engine's
    ``aux`` leaves it out, so a decode program has the results it had and a
    round copies to the host and counts what it did (PERF.md section 6,
    PR 47 f: the serve programs are the parent's, instruction for
    instruction)."""
    from apex_tpu.serve import latent
    cfg, p = _share()
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 256), BF16)
    _, st = md.expert_layer(cfg, p, x, interpret=True)
    assert "rows_moved" in st
    aux = latent._aux([st, st])
    assert sorted(aux["round"]) == ["assignments_local", "expert_load_max",
                                    "experts_touched"]
    assert aux["rows"]["moe_idx"].shape == (512, 2, 8)


@pytest.fixture
def low_threshold(monkeypatch):
    """The kernels' path at 512 tokens x top 8, for what does not hang on
    the size."""
    monkeypatch.setattr(md, "TRAIN_ASSIGNMENTS", 4096)


def test_rows_past_the_used_tiles_are_never_read(monkeypatch, low_threshold):
    """Every buffer in the tile layout poisoned past its used tiles (the
    dispatched rows, both grouped matmuls' results and the opened rows):
    the layer's output and gradients do not change by a bit."""
    cfg, p = _share()
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 256), BF16)
    g = jax.random.normal(jax.random.PRNGKey(2), (512, 256), F32)
    (want, _), wants = _value_and_grads(cfg, p, x, g)

    def poisoned(fn, used_at, rows_a_tile):
        def run(*a, **kw):
            out = fn(*a, **kw)
            first, rest = (out, ()) if not isinstance(out, tuple) \
                else (out[0], out[1:])
            dead = jnp.arange(first.shape[0]) >= \
                jnp.reshape(a[used_at], ()) * rows_a_tile(kw["block_m"])
            bad = jnp.full((), jnp.nan if jnp.issubdtype(
                first.dtype, jnp.floating) else 0x7FC07FC0, first.dtype)
            first = jnp.where(dead[:, None], bad, first)
            return (first,) + tuple(rest) if rest else first
        return run

    words = mr._row_sublanes(256)
    monkeypatch.setattr(mr, "sorted_rows",
                        poisoned(mr.sorted_rows, 2, lambda bm: bm))
    monkeypatch.setattr(mr, "open_tiles",
                        poisoned(mr.open_tiles, 1, lambda bm: bm * words))
    monkeypatch.setattr(gmm, "grouped_matmul",
                        poisoned(gmm.grouped_matmul, 3, lambda bm: bm))
    (got, st), grads = _value_and_grads(cfg, p, x, g)
    assert int(st["rows_moved"]) < 512 * 4          # tiles were dead
    assert float(got) == float(want)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(wants)):
        np.testing.assert_array_equal(_bits(a) if a.dtype == BF16
                                      else np.asarray(a),
                                      _bits(b) if b.dtype == BF16
                                      else np.asarray(b))


def _on_the_take_path(monkeypatch, cfg, p, x):
    monkeypatch.setattr(md, "_moves_live_rows", lambda *a: False)
    return md.expert_layer(cfg, p, x, interpret=True)[0]


def test_dropless_when_every_token_picks_the_held_experts(monkeypatch,
                                                          low_threshold):
    """The worst case of the buffers through the kernels: every assignment
    is held here, every tile is used, nothing is dropped."""
    cfg, p = _share(n_routed_experts=8, n_local_experts=8, first_expert=0)
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 256), BF16)
    y, st = md.expert_layer(cfg, p, x, interpret=True)
    assert int(st["assignments_local"]) == 512 * 8
    assert int(st["rows_moved"]) >= 512 * 8
    want = _on_the_take_path(monkeypatch, cfg, p, x)
    assert np.abs(_bits(y) - _bits(want)).max() <= 1


def test_a_shared_expert_is_added_before_the_one_cast(monkeypatch,
                                                      low_threshold):
    """With more to add to the sum (a shared expert) the kernel hands the
    sum over in float32 and the layer casts once, as the take path does."""
    cfg, p = _share()
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    p = {**p, "shared": {
        "gate": jax.random.normal(ks[0], (256, 64), BF16) * 0.05,
        "up": jax.random.normal(ks[1], (256, 64), BF16) * 0.05,
        "down": jax.random.normal(ks[2], (64, 256), BF16) * 0.05}}
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 256), BF16)
    y, _ = md.expert_layer(cfg, p, x, interpret=True)
    want = _on_the_take_path(monkeypatch, cfg, p, x)
    assert np.abs(_bits(y) - _bits(want)).max() <= 1


# -- the counter ----------------------------------------------------------------------

def test_the_model_counts_rows_moved_after_the_counters_it_had():
    assert ml.MOE_COUNTS[0] == "assignments_local"
    assert ml.MOE_COUNTS[-1] == "rows_moved"
    aux = {"moe": {"rows_moved": np.asarray([512, 768]),
                   "assignments_local": np.asarray([7, 9]),
                   "expert_load_max": np.asarray([4, 5]),
                   "experts_touched": np.asarray([2, 3])}}
    rec = monitor.Recorder(name="t", traced_hooks=False)
    monitor.attach(rec)
    try:
        ml.record_step(aux)
        # an aux from before the counter (the benchmark's made-up runs)
        ml.record_step({"moe": {k: v for k, v in aux["moe"].items()
                                if k != "rows_moved"}})
    finally:
        monitor.detach()
    names = [(e["name"], e["layer"]) for e in rec.records()
             if e["kind"] == "counter"]
    assert names[:4] == [("moe/assignments_local", 0),
                         ("moe/expert_load_max", 0),
                         ("moe/experts_touched", 0), ("moe/rows_moved", 0)]
    assert names[4] == ("moe/assignments_local", 1)
    assert len(names) == 8 + 6 and names[8] == ("moe/assignments_local", 0)
    assert rec.counters()["moe/rows_moved"] == 512 + 768
