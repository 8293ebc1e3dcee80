"""Flash attention's causal strips and its kernel calls jitted on their own.

A plain causal call (no segments, bias, dropout or padding) walks the block
the diagonal crosses in strips of query rows, each against the keys up to
its own diagonal (``ops/flash_attention.py``, "Strips"); every other call
runs the whole-block program it ran. The forward's and the backward's
kernel calls are ``jax.jit`` functions, so the layers of a model share one
trace of the kernel body and one lowered Mosaic module a program.
Everything here runs the kernels interpreted, on the CPU.
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import monitor

fa = importlib.import_module("apex_tpu.ops.flash_attention")

F32, BF16 = jnp.float32, jnp.bfloat16


def _operands(sq, sk, d, dtype, seed=0, h=1):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(1, h, s, d), dtype)
            for s in (sq, sk, sk, sq)]


# (sq, sk, d, dtype, the forward's strip rows, the fused backward's; None =
# whole blocks). The blocks are the defaults: 1,024, clamped to the lengths.
PARITY = {
    "s512-d64": (512, 512, 64, F32, 256, 256),
    "s1024-d64": (1024, 1024, 64, F32, 512, 256),      # cells 1 and 4
    "s1024-d64-bf16": (1024, 1024, 64, BF16, 512, 256),
    "s1024-d128": (1024, 1024, 128, F32, 512, 256),
    "s512-d192": (512, 512, 192, F32, 256, 128),
    # [1024, 192] accumulators pass the fused backward's VMEM gate: two
    # kernels, whole blocks
    "s1024-d192": (1024, 1024, 192, F32, 256, None),
    # the online carry across key blocks, whole blocks under the diagonal
    "s2048-d64": (2048, 2048, 64, F32, 512, 256),
    "s4096-d64": (4096, 4096, 64, BF16, 512, None),
    "sq-under-sk": (512, 1024, 64, F32, 256, 256),     # causal_offset > 0
    # causal_offset < 0: the first 512 rows see no key and write zeros
    "sq-over-sk": (1024, 512, 64, F32, 512, 256),
    "s640-five-strips": (640, 640, 64, F32, 128, 128),
    "s1000-no-strip-divides": (1000, 1000, 64, F32, None, None),
}


@pytest.mark.parametrize("case", sorted(PARITY))
def test_causal_strips_match_the_references(case):
    """Forward against ``mha_reference``, backward against ``_bwd_math``,
    and the plan each direction takes stated beside the case."""
    sq, sk, d, dtype, r_fwd, r_bwd = PARITY[case]
    q, k, v, do = _operands(sq, sk, d, dtype)
    scale = d ** -0.5
    plans = (fa._strip_plan("fwd", True, True, sq, sk, 1024, 1024, d),
             fa._bwd_strip_plan(True, True, sq, sk, 1024, 1024, d, dtype,
                                dtype))
    assert [p and p.rows for p in plans] == [r_fwd, r_bwd]

    out, lse = fa._flash_fwd_impl(q, k, v, None, None, None,
                                  jnp.zeros((1,), jnp.int32), scale, True,
                                  0.0, 1024, 1024, True)
    ref = fa.mha_reference(q, k, v, causal=True, scale=scale)
    live = slice(max(sq - sk, 0), None)     # the reference gives a row that
    tol = 2e-2 if dtype == BF16 else 2e-5   # sees no key the mean of V
    np.testing.assert_allclose(np.asarray(out[:, :, live], np.float32),
                               np.asarray(ref[:, :, live], np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(out[:, :, :live.start]), 0.0)

    res = (q, k, v, out, lse, None, None, None, None)
    grads = fa._flash_bwd_impl(res, do, scale=scale, causal=True,
                               dropout_rate=0.0, block_q=1024, block_k=1024,
                               interpret=True)
    for got, want in zip(grads, fa._bwd_math(res, do, scale=scale,
                                             causal=True)):
        tol = 1e-1 if dtype == BF16 else 1e-4
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


def _kernel_calls(monkeypatch, **kw):
    """The ``pallas_call``s of a fwd + bwd of ``flash_attention`` at b1 h2
    s1024 d64 bf16 under ``kw``: (kernel name, its static arguments, grid)."""
    seen = []
    real = fa.pl.pallas_call

    def spy(kernel, **call_kw):
        seen.append((kernel.func.__name__, kernel.keywords,
                     call_kw["grid"]))
        return real(kernel, **call_kw)

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    fa._flash_fwd_impl.clear_cache()
    fa._flash_bwd_impl.clear_cache()
    q = jnp.zeros((1, 2, 1024, 64), BF16)
    jax.eval_shape(jax.grad(lambda q: jnp.sum(fa.flash_attention(
        q, q, q, interpret=True, autotune="off", **kw).astype(F32))), q)
    fa._flash_fwd_impl.clear_cache()
    fa._flash_bwd_impl.clear_cache()
    return seen


# what the call lowered to before strips existed: kernels, blocks, grids
BYPASS = {
    "non-causal": (dict(causal=False),
                   [("_fwd_kernel", 1024, (1, 2, 1, 1)),
                    ("_bwd_fused_kernel", 1024, (1, 2, 1, 1))]),
    "segments": (dict(causal=True,
                      segment_ids_q=jnp.zeros((1, 1024), jnp.int32)),
                 [("_fwd_kernel", 1024, (1, 2, 1, 1)),
                  ("_bwd_fused_kernel", 512, (1, 2, 2, 2))]),
    "bias": (dict(causal=True, bias=jnp.zeros((1, 1, 1024, 1024), BF16)),
             [("_fwd_kernel", 1024, (1, 2, 1, 1)),
              ("_bwd_fused_kernel", 512, (1, 2, 2, 2))]),
    "dropout": (dict(causal=True, dropout_rate=0.1, dropout_seed=3),
                [("_fwd_kernel", 1024, (1, 2, 1, 1)),
                 ("_bwd_fused_kernel", 512, (1, 2, 2, 2))]),
}


@pytest.mark.parametrize("case", sorted(BYPASS))
def test_a_call_with_nothing_to_skip_lowers_as_before(monkeypatch, case):
    """Not causal, or with segments, a bias or dropout: no strip plan, the
    blocks and grids of the program before strips (a causal backward that
    cannot walk strips keeps its two 512-blocks at s = 1,024)."""
    kw, want = BYPASS[case]
    calls = _kernel_calls(monkeypatch, **kw)
    assert [(name, static["block_q"], grid)
            for name, static, grid in calls] == want
    assert all(static["strips"] is None for _, static, _ in calls)
    assert all(static["block_k"] == static["block_q"]
               for _, static, _ in calls)


def test_a_plain_causal_call_walks_strips_in_one_program(monkeypatch):
    """The contrast: the same shapes, plain causal: one 1,024-block a
    (batch, head) in BOTH directions, 512 rows a strip forward, 256
    backward."""
    calls = _kernel_calls(monkeypatch, causal=True)
    assert [(name, static["block_q"], static["block_k"], grid,
             static["strips"]) for name, static, grid in calls] == [
        ("_fwd_kernel", 1024, 1024, (1, 2, 1, 1),
         fa._StripPlan(rows=512, rel=0, any_full=False)),
        ("_bwd_fused_kernel", 1024, 1024, (1, 2, 1, 1),
         fa._StripPlan(rows=256, rel=0, any_full=False))]


def test_strips_stop_at_their_own_diagonal():
    """The strips of a plan: rows, the keys they multiply against, and
    which of them still need a mask."""
    def strips(plan, bq, bk, **kw):
        return [(s.rows.start, s.rows.size, s.cols.size, s.shift, s.masked)
                for s in fa._strips_of(plan, bq, bk, **kw)]

    plan = fa._strip_plan("bwd", True, True, 1024, 1024, 1024, 1024, 64)
    assert strips(plan, 1024, 1024) == [
        (0, 256, 256, 0, True), (256, 256, 512, 256, True),
        (512, 256, 768, 512, True), (768, 256, 1024, 768, True)]
    # sq < sk: every row sees the 512 keys before its own; the last strip
    # ends at the block's edge
    plan = fa._strip_plan("fwd", True, True, 512, 1024, 1024, 1024, 64)
    assert strips(plan, 512, 1024) == [
        (0, 256, 768, 512, True), (256, 256, 1024, 768, True)]
    # sq > sk: the strip that sees nothing is skipped, or kept on one lane
    # tile where the program has to write its rows
    plan = fa._strip_plan("fwd", True, True, 1024, 512, 1024, 1024, 64)
    assert strips(plan, 1024, 512) == [(512, 512, 512, 0, True)]
    assert strips(plan, 1024, 512, keep_dead=True) == [
        (0, 512, 128, -512, True), (512, 512, 512, 0, True)]
    # a diagonal that crosses its blocks at two places has no plan
    assert fa._strip_plan("fwd", True, True, 1536, 2048, 512, 1024,
                          64) is None


def _tile_counts(rec):
    out = {}
    for ev in rec.records():
        if ev["name"].startswith("flash/tiles_"):
            key = (ev["direction"], ev["name"].split("_")[1])
            out[key] = out.get(key, 0) + ev["value"]
    return out


def test_calls_count_the_tiles_they_multiply():
    """``flash/tiles_computed`` / ``flash/tiles_square`` by direction, once
    a trace of the kernel's call: 0.75 forward (two strips of 512 rows)
    and 0.625 backward (four of 256) at s = 1,024, from the function that
    derives the strips; 1.0 where the call is not causal."""
    q = jnp.zeros((2, 4, 1024, 64), BF16)

    def traced(**kw):
        fa._flash_fwd_impl.clear_cache()
        fa._flash_bwd_impl.clear_cache()
        rec = monitor.Recorder(name="flash-tiles")
        with monitor.attached(rec):
            jax.eval_shape(jax.grad(lambda q: jnp.sum(fa.flash_attention(
                q, q, q, interpret=True, **kw).astype(F32))), q)
        return _tile_counts(rec)

    n = traced(causal=True)
    square = 2 * 4 * 8 * 8                      # 128 x 128 tiles
    assert n["fwd", "square"] == n["bwd", "square"] == square
    assert n["fwd", "computed"] / square == 0.75
    assert n["bwd", "computed"] / square == 0.625
    for direction, rows in (("fwd", 512), ("bwd", 256)):
        plan = fa._strip_plan(direction, True, True, 1024, 1024, 1024, 1024,
                              64)
        assert plan.rows == rows
        assert n[direction, "computed"] == 8 * sum(
            s.rows.size * s.cols.size
            for s in fa._strips_of(plan, 1024, 1024)) / 128 ** 2

    n = traced(causal=False)
    assert n["fwd", "computed"] == n["bwd", "computed"] == square
    # a causal grid of whole blocks skips its dead ones: one of four
    n = traced(causal=True, segment_ids_q=jnp.zeros((2, 1024), jnp.int32))
    assert n["fwd", "computed"] / square == 1.0
    assert n["bwd", "computed"] / square == 0.75


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_layers_share_one_trace_of_each_kernel(monkeypatch, remat):
    """A four-layer GPT's forward + backward under ``jax.jit`` enters the
    forward kernel's body and the backward kernel's body ONCE each, not
    once a layer, and lowers one function a direction that the layers
    call; the compiled program still names both scopes.

    What it costs when this fails: a Pallas call is traced (its body,
    unrolled over its strips) and lowered once a call site at every
    lowering of the program around it, compile cache hit or not. PR 44's
    strips without the jit raised warm ``setup_s`` 92 -> 113 s in
    ``gpt2l-train-4chip`` (36 layers x three programs) and 28.1 -> 32.9 s
    in ``gpt2m-serve-closed64``, and lost a measured +5.5% for it."""
    from apex_tpu.models.gpt import GPT, GPTConfig

    entered = {"_fwd_kernel": 0, "_bwd_fused_kernel": 0}
    for name in entered:
        def counting(*refs, _name=name, _body=getattr(fa, name), **kw):
            entered[_name] += 1
            return _body(*refs, **kw)
        monkeypatch.setattr(fa, name, counting)
    fa._flash_fwd_impl.clear_cache()
    fa._flash_bwd_impl.clear_cache()

    model = GPT(GPTConfig(vocab_size=256, max_seq_len=256, hidden_size=128,
                          num_layers=4, num_heads=2, remat_blocks=remat,
                          fused_lm_head=False))
    ids = jnp.zeros((2, 256), jnp.int32)
    params = jax.eval_shape(functools.partial(model.init,
                                              jax.random.PRNGKey(0)), ids)
    lowered = jax.jit(jax.grad(
        lambda p, ids: model.loss(p, ids, ids))).lower(params, ids)
    fa._flash_fwd_impl.clear_cache()
    fa._flash_bwd_impl.clear_cache()

    # init and the step are two programs on ONE trace of the forward; a
    # rematerialised block is traced once more where its derivative is
    # taken (``remat_jvp``), whatever the number of layers
    assert entered == {"_fwd_kernel": 2 if remat else 1,
                       "_bwd_fused_kernel": 1}
    text = lowered.as_text()
    for fn, layers in (("_flash_fwd_impl", 4), ("_flash_bwd_impl", 4)):
        assert text.count(f"func.func private @{fn}(") == 1
        assert text.count(f"call @{fn}(") >= layers
    hlo = lowered.compile().as_text()
    assert "apx:flash_attention_fwd" in hlo
    assert "apx:flash_attention_bwd" in hlo


# -- a sliding window and grouped key/value heads: the banded grids -----------------

def _band_reference(q, k, v, window):
    """``mha_reference`` with the band as a bias and K/V repeated a group."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    bias = None
    if window is not None:
        sq, sk = q.shape[2], k.shape[2]
        i = jnp.arange(sq)[:, None] + (sk - sq)
        bias = jnp.where(jnp.arange(sk)[None, :] > i - window, 0.0,
                         fa._NEG_INF)[None, None]
    return fa.mha_reference(q, k, v, causal=True, bias=bias)


def _band_operands(h, hk, sq, sk, d=32, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(1, n, s, d), F32)
            for n, s in ((h, sq), (hk, sk), (hk, sk), (h, sq))]


# (query heads, key/value heads, sq, sk, window, block): the window under,
# at and over a block, whole blocks below the band, grouped heads with and
# without a window, a length no block divides (padding installs segments)
BAND = {
    "window-under-a-block": (2, 2, 256, 256, 48, 64),
    "window-is-a-block": (2, 2, 256, 256, 64, 64),
    "window-over-a-block": (2, 2, 256, 256, 100, 64),
    "window-gqa": (4, 2, 256, 256, 48, 64),
    "window-gqa-one-kv-head": (4, 1, 256, 256, 96, 128),
    "gqa-no-window": (4, 2, 256, 256, None, 64),
    "window-padded-length": (2, 1, 200, 200, 70, 64),
    "window-sq-under-sk": (2, 2, 128, 256, 80, 64),
}


@pytest.mark.parametrize("case", sorted(BAND))
def test_window_and_grouped_heads_match_the_reference(case):
    """Forward and backward of the banded grids against ``mha_reference``
    with the band as a bias; dK and dV of a key/value head equal the
    repeat-K/V formulation's, summed over the group (``jax.grad`` through
    ``jnp.repeat`` sums them)."""
    h, hk, sq, sk, window, blk = BAND[case]
    q, k, v, do = _band_operands(h, hk, sq, sk)

    def mine(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  block_q=blk, block_k=blk, interpret=True)

    np.testing.assert_allclose(
        np.asarray(mine(q, k, v)),
        np.asarray(_band_reference(q, k, v, window)), rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(mine(*a) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_band_reference(*a, window) * do),
                    (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def test_grouped_heads_without_a_causal_mask_walk_every_block():
    q, k, v, do = _band_operands(4, 2, 128, 256)

    def mine(q, k, v):
        return fa.flash_attention(q, k, v, block_q=64, block_k=64,
                                  interpret=True)

    def want(q, k, v):
        return fa.mha_reference(q, *(jnp.repeat(a, 2, axis=1)
                                     for a in (k, v)))

    np.testing.assert_allclose(np.asarray(mine(q, k, v)),
                               np.asarray(want(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    for g, w in zip(
            jax.grad(lambda *a: jnp.sum(mine(*a) * do), (0, 1, 2))(q, k, v),
            jax.grad(lambda *a: jnp.sum(want(*a) * do), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def test_a_window_over_the_sequence_is_plain_causal_bit_for_bit():
    q, k, v, do = _band_operands(2, 2, 256, 256)

    def run(**kw):
        f = lambda *a: fa.flash_attention(     # noqa: E731
            *a, causal=True, block_q=128, block_k=128, interpret=True, **kw)
        return (f(q, k, v),) + jax.grad(
            lambda *a: jnp.sum(f(*a) * do), (0, 1, 2))(q, k, v)

    for a, b in zip(run(window=256), run()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_band_skips_blocks_and_counts_what_it_multiplies(monkeypatch):
    """s = 512 in 64-blocks under a window of 100: a q block sees three k
    blocks at most, so the grids' inner extent is 3 (of 8), the grouped
    dk/dv grid walks its two query heads in one (kv head, k block) program
    sequence, and the counters say ``attention=window``."""
    calls = _band_calls(monkeypatch, window=100)
    assert calls == [("_fwd_kernel", (1, 4, 8, 3)),
                     ("_dkdv_kernel", (1, 2, 8, 2 * 3)),
                     ("_dq_kernel", (1, 4, 8, 3))]
    calls = _band_calls(monkeypatch, window=None)     # grouped, no window
    assert calls == [("_fwd_kernel", (1, 4, 8, 8)),
                     ("_dkdv_kernel", (1, 2, 8, 2 * 8)),
                     ("_dq_kernel", (1, 4, 8, 8))]

    rec = monitor.Recorder(name="t", traced_hooks=False)
    monitor.attach(rec)
    try:
        _band_calls(monkeypatch, window=100)
    finally:
        monitor.detach()
    events = [e for e in rec.records() if e["kind"] == "counter"
              and e["name"].startswith("flash/tiles")]
    assert {e["attention"] for e in events} == {"window"}
    by = {(e["name"], e["direction"]): e["value"] for e in events}
    # live 64-blocks: 1 + 2 + 6 x 3 = 21 of 64, four heads, in 128-tiles
    assert by["flash/tiles_computed", "fwd"] == 4 * 21 / 4
    assert by["flash/tiles_square", "fwd"] == 4 * 64 / 4
    assert by["flash/tiles_computed", "bwd"] == 4 * 21 / 4


def _band_calls(monkeypatch, window):
    seen = []
    real = fa.pl.pallas_call

    def spy(kernel, **kw):
        seen.append((kernel.func.__name__, kw["grid"]))
        return real(kernel, **kw)

    monkeypatch.setattr(fa.pl, "pallas_call", spy)
    fa._flash_fwd_impl.clear_cache()
    fa._flash_bwd_impl.clear_cache()
    q = jnp.zeros((1, 4, 512, 32), F32)
    k = jnp.zeros((1, 2, 512, 32), F32)
    jax.eval_shape(jax.grad(lambda q, k: jnp.sum(fa.flash_attention(
        q, k, k, causal=True, window=window, block_q=64, block_k=64,
        interpret=True))), q, k)
    fa._flash_fwd_impl.clear_cache()
    fa._flash_bwd_impl.clear_cache()
    monkeypatch.setattr(fa.pl, "pallas_call", real)
    return seen


@pytest.mark.parametrize("bad", [
    dict(h=3, hk=2, kw=dict(causal=True)),              # 3 is no multiple of 2
    dict(h=2, hk=2, kw=dict(causal=False, window=8)),   # a window is causal
    dict(h=2, hk=2, kw=dict(causal=True, window=0)),
])
def test_flash_attention_refuses_what_it_cannot_mean(bad):
    q = jnp.zeros((1, bad["h"], 64, 32), F32)
    k = jnp.zeros((1, bad["hk"], 64, 32), F32)
    with pytest.raises(ValueError):
        fa.flash_attention(q, k, k, interpret=True, **bad["kw"])


def test_window_and_full_layers_each_trace_their_kernels_once(monkeypatch):
    """A model of six window layers and two full ones enters the forward
    kernel's body twice a kind (its blocks are rematerialised: once more
    where the derivative is taken, as the GPT case above), not eight times,
    and each of the two backward kernels once a kind: ``window`` is a static
    argument of the jitted kernel calls, the layer count is not."""
    from apex_tpu.models import mellum as ml

    entered = {"_fwd_kernel": 0, "_dkdv_kernel": 0, "_dq_kernel": 0}
    for name in entered:
        def counting(*refs, _name=name, _body=getattr(fa, name), **kw):
            entered[_name] += 1
            return _body(*refs, **kw)
        monkeypatch.setattr(fa, name, counting)
    fa._flash_fwd_impl.clear_cache()
    fa._flash_bwd_impl.clear_cache()
    cfg = ml.MellumConfig(
        vocab_size=128, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=32, moe_intermediate_size=64, n_routed_experts=4,
        num_experts_per_tok=2, sliding_window=48,
        layer_types=(ml.SLIDING,) * 3 + (ml.FULL,) + (ml.SLIDING,) * 3
        + (ml.FULL,), dtype=F32)
    params = jax.eval_shape(functools.partial(ml.init_params, cfg),
                            jax.random.PRNGKey(0))
    ids = jnp.zeros((1, 128), jnp.int32)
    lowered = jax.jit(jax.grad(lambda p: ml.loss(
        cfg, p, ids, ids, impl="reference", interpret=True)[0])).lower(params)
    fa._flash_fwd_impl.clear_cache()
    fa._flash_bwd_impl.clear_cache()
    assert entered == {"_fwd_kernel": 4, "_dkdv_kernel": 2, "_dq_kernel": 2}
    text = lowered.as_text()
    # lowered functions a kind: one forward (a block keeps the kernel's two
    # results by name, ``models/mellum.py:_kept``, ONE policy object for
    # every layer: a policy a layer would lower a forward a layer) and one
    # backward; eight layers call each once
    assert text.count("func.func private @_flash_fwd_impl") == 2
    assert text.count("func.func private @_flash_bwd_impl") == 2
    assert text.count("call @_flash_fwd_impl") == 8
    assert text.count("call @_flash_bwd_impl") == 8
    hlo = lowered.compile().as_text()
    for name in ("apx:flash_attention_window_fwd", "apx:flash_attention_fwd",
                 "apx:flash_attention_window_bwd", "apx:flash_attention_bwd",
                 "apx:attn_window", "apx:attn_full"):
        assert name in hlo, name
