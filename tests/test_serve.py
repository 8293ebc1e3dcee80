"""apex_tpu.serve: paged KV-cache inference with continuous batching.

The acceptance contracts of PR 11, each asserted mechanically:

- the paged decode kernel matches the pure-XLA reference (GQA, fp8,
  inactive slots);
- the serve path reproduces the TRAINING model's greedy decode exactly
  (the same params, the same logits argmax as ``GPT.apply``);
- preempt/resume and evict/re-admit are BIT-exact vs uninterrupted
  decode (logits compared with ``array_equal``, bf16-to-the-bit — the
  recompute-preemption + fixed-batch-shape design);
- fp8-KV parity within tolerance, and its >= ~2x concurrent-sequence
  capacity asserted from the block-pool byte accounting;
- the scheduler state machine: FCFS admission, page-boundary growth,
  evict-on-exhaustion from the back, conservation of pages;
- page size resolves explicit > tuned cache > heuristic through
  apex_tpu.tune.
"""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu import serve
from apex_tpu.models.gpt import GPT, GPTConfig
from apex_tpu.ops import paged_attention as fa_mod
from apex_tpu.ops.paged_attention import (paged_attention_reference,
                                          paged_decode_attention)
from apex_tpu.serve import cache as cache_mod
from apex_tpu.serve.scheduler import (RUNNING, WAITING, PageAllocator,
                                      Scheduler, Sequence)
from apex_tpu.transformer import parallel_state as ps


# ---------------------------------------------------------------------------
# shared tiny model
# ---------------------------------------------------------------------------

CFG = GPTConfig(vocab_size=64, max_seq_len=128, hidden_size=32,
                num_layers=2, num_heads=2, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    ps.destroy_model_parallel()
    return GPT(CFG).init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))["params"]


PROMPTS = [[5, 9, 17, 3, 40, 22, 8], [11, 2, 33, 60, 7, 7, 1]]
N_NEW = 12


def _engine(params, *, fp8=False, num_pages=32, max_batch=2, **kw):
    return serve.ServeEngine(CFG, params, num_pages=num_pages,
                             max_seq_len=64, max_prompt_len=16,
                             page_size=8, max_batch=max_batch,
                             fp8_kv=fp8, record_logits=True, **kw)


def _pool(k, v, **ccfg_kw):
    """K and V pages ``[kv, pages, page_size, d]`` as one layer's pool
    leaf, checked against the shape and dtype ``init_cache`` gives it."""
    kv, n_pages, bs, d = k.shape
    leaf, = cache_mod.init_cache(cache_mod.CacheConfig(
        num_layers=1, kv_heads=kv, head_dim=d, num_pages=n_pages,
        page_size=bs, **ccfg_kw)).pools
    pool = jnp.concatenate([k, v], axis=-1)
    assert (pool.shape, pool.dtype) == (leaf.shape, leaf.dtype)
    return pool


def _run(params, *, fp8=False, preempt_at=None, **kw):
    eng = _engine(params, fp8=fp8, **kw)
    ids = [eng.add_request(p, N_NEW) for p in PROMPTS]
    seqs = list(eng.sched.waiting)           # keep refs past finish()
    steps = 0
    while eng.sched.has_work:
        eng.step()
        steps += 1
        if preempt_at and steps == preempt_at and any(
                s.seq_id == ids[0] for s in eng.sched.running):
            eng.preempt(ids[0])
        assert steps < 500
    out = {s.seq_id: s.tokens[len(s.prompt):] for s in seqs}
    n_preempts = sum(s.n_preemptions for s in seqs)
    return eng, ids, out, n_preempts


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

def test_paged_decode_kernel_matches_reference_gqa():
    rng = np.random.RandomState(0)
    b, kv, g, d = 3, 2, 3, 16          # group 3: a real GQA shape
    bs, n_pages, m = 8, 9, 4
    q = jnp.asarray(rng.randn(b, kv, g, d) * 0.3, jnp.float32)
    kp = jnp.asarray(rng.randn(kv, n_pages, bs, d) * 0.3, jnp.float32)
    vp = jnp.asarray(rng.randn(kv, n_pages, bs, d) * 0.3, jnp.float32)
    bt = jnp.asarray(rng.randint(1, n_pages, (b, m)), jnp.int32)
    sl = jnp.asarray([13, 0, 32], jnp.int32)
    pool = _pool(kp, vp, dtype=jnp.float32)
    ref = paged_attention_reference(q, pool, bt, sl)
    out = paged_decode_attention(q, pool, bt, sl)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-5)
    # inactive slot (seq_len 0) contributes exact zeros
    assert float(jnp.max(jnp.abs(out[1]))) == 0.0


def test_paged_decode_kernel_fp8_dequant():
    from apex_tpu.amp import fp8 as f8
    rng = np.random.RandomState(1)
    kv, n_pages, bs, d = 2, 5, 8, 16
    q = jnp.asarray(rng.randn(2, kv, 1, d) * 0.3, jnp.float32)
    k32 = jnp.asarray(rng.randn(kv, n_pages, bs, d) * 0.3, jnp.float32)
    v32 = jnp.asarray(rng.randn(kv, n_pages, bs, d) * 0.3, jnp.float32)
    ks = jnp.full((kv, n_pages), 2.0, jnp.float32)
    vs = jnp.full((kv, n_pages), 4.0, jnp.float32)
    kp = f8.quantize(k32, 2.0, f8.E4M3)
    vp = f8.quantize(v32, 4.0, f8.E4M3)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    sl = jnp.asarray([11, 16], jnp.int32)
    pool = _pool(kp, vp, fp8=True)
    ref = paged_attention_reference(q, pool, bt, sl, k_scales=ks,
                                    v_scales=vs)
    out = paged_decode_attention(q, pool, bt, sl, k_scales=ks,
                                 v_scales=vs)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-5)
    exact = paged_attention_reference(q, _pool(k32, v32, dtype=jnp.float32),
                                      bt, sl)
    assert float(jnp.max(jnp.abs(ref - exact))) < 0.1


def _pallas_grids(fn, *args):
    """The grid of every ``pallas_call`` in ``fn``'s jaxpr, nested jits
    included."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield tuple(eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def _walk_case(group, fp8, *, kv=2):
    """Five rows in one batch: inactive, one token, exactly a page, a page
    and a token, the whole table. Every row owns its pages (none shared,
    none the null page) and holds them out of order; the pages a row's
    length does not reach are in its table all the same, as stale entries
    are."""
    from apex_tpu.amp import fp8 as f8
    d, bs, m = 16, 8, 4
    rng = np.random.RandomState(4)
    sl = np.asarray([0, 1, bs, bs + 1, m * bs], np.int32)
    b = len(sl)
    n_pages = 1 + b * m
    bt = rng.permutation(np.arange(1, n_pages)).reshape(b, m).astype(np.int32)
    dtype = jnp.float32 if fp8 else jnp.bfloat16
    q = jnp.asarray(rng.randn(b, kv, group, d) * 0.5, dtype)
    k32 = jnp.asarray(rng.randn(kv, n_pages, bs, d) * 0.5, jnp.float32)
    v32 = jnp.asarray(rng.randn(kv, n_pages, bs, d) * 0.5, jnp.float32)
    scales = {}
    if fp8:
        ks = jnp.asarray(rng.uniform(1.0, 4.0, (kv, n_pages)), jnp.float32)
        vs = jnp.asarray(rng.uniform(1.0, 4.0, (kv, n_pages)), jnp.float32)
        pool = _pool(f8.quantize(k32, ks[:, :, None, None], f8.E4M3),
                     f8.quantize(v32, vs[:, :, None, None], f8.E4M3),
                     fp8=True)
        scales = dict(k_scales=ks, v_scales=vs)
    else:
        pool = _pool(k32.astype(dtype), v32.astype(dtype), dtype=dtype)
    dead = np.ones(n_pages, bool)           # the null page among them
    for row, n in zip(bt, -(-sl // bs)):
        dead[row[:n]] = False
    return q, pool, jnp.asarray(bt), jnp.asarray(sl), scales, dead


@pytest.mark.parametrize("pool_dtype", ["bf16", "e4m3"])
@pytest.mark.parametrize("group", [1, 4, 8])
def test_paged_decode_kernel_walks_live_pages(group, pool_dtype):
    """The kernel against the reference at MHA and two GQA groups (one
    form for all: the heads' scores side by side), both pools, and
    sequence lengths on every edge of a page."""
    q, pool, bt, sl, scales, _ = _walk_case(group, pool_dtype == "e4m3")
    ref = paged_attention_reference(q, pool, bt, sl, **scales)
    out = paged_decode_attention(q, pool, bt, sl, **scales)
    assert out.shape == q.shape and out.dtype == q.dtype
    # bf16: the result is rounded to 8 bits of mantissa, and so are the
    # probabilities that enter the value product
    np.testing.assert_allclose(
        np.asarray(ref, np.float32), np.asarray(out, np.float32),
        atol=1e-5 if pool_dtype == "e4m3" else 2e-2)
    assert float(jnp.max(jnp.abs(out[0].astype(jnp.float32)))) == 0.0


def test_paged_decode_kernel_splits_heads_that_do_not_fit(monkeypatch):
    """Where one page of every kv head, twice, is over the kernel's VMEM
    budget, a program takes a block of the heads and the grid grows a
    second dimension; the walk, and the copy a program starts for the
    next one, go on as before."""
    q, pool, bt, sl, _, _ = _walk_case(2, False, kv=4)
    page_bytes = pool[0, 0].size * pool.dtype.itemsize
    monkeypatch.setattr(fa_mod, "_DECODE_BUFFER_BYTES", 2 * 2 * page_bytes)
    assert _pallas_grids(paged_decode_attention, q, pool, bt, sl) == [
        (q.shape[0], 2)]
    np.testing.assert_allclose(
        np.asarray(paged_attention_reference(q, pool, bt, sl), np.float32),
        np.asarray(paged_decode_attention(q, pool, bt, sl), np.float32),
        atol=2e-2)


@pytest.mark.parametrize("group", [1, 8])
def test_paged_decode_kernel_never_reads_a_dead_page(group):
    """NaN in the null page and in every page past a row's live range
    (both still named by the block tables) changes no bit of the
    result: a dead slot is not fetched, let alone multiplied by zero."""
    q, pool, bt, sl, _, dead = _walk_case(group, False)
    clean = paged_decode_attention(q, pool, bt, sl)
    poisoned = jnp.where(jnp.asarray(dead)[None, :, None, None], jnp.nan,
                         pool)
    assert bool(jnp.isnan(poisoned[:, 0]).all())
    out = paged_decode_attention(q, poisoned, bt, sl)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    np.testing.assert_array_equal(np.asarray(clean, np.float32),
                                  np.asarray(out, np.float32))


def test_paged_decode_grid_is_one_program_a_sequence():
    """At the benchmark's serve cell (64 rows, 16 heads of 64, 385 pages
    of 128 tokens, 8 slots a row) the grid holds the batch and nothing of
    the table or the heads: 64 programs a layer."""
    b, kv, d, m = 64, 16, 64, 8
    grid, = _pallas_grids(
        paged_decode_attention,
        jax.ShapeDtypeStruct((b, kv, 1, d), jnp.bfloat16),
        jax.ShapeDtypeStruct((kv, 385, 128, 2 * d), jnp.bfloat16),
        jax.ShapeDtypeStruct((b, m), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32))
    assert grid[0] == b and m not in grid and kv not in grid
    assert int(np.prod(grid)) == b


def test_decode_forward_kernel_impl_matches_reference(params):
    """The model-level decode step through the Pallas kernels (interpret:
    the aliased write, then the paged read) == through the XLA scatter
    and the reference gather."""
    from apex_tpu.serve import model as serve_model
    ccfg = cache_mod.CacheConfig(num_layers=CFG.num_layers, kv_heads=2,
                                 head_dim=16, num_pages=8, page_size=8,
                                 dtype=jnp.float32)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([3, 5], jnp.int32)
    tok = jnp.asarray([7, 9], jnp.int32)
    act = jnp.ones((2,), bool)
    rng = np.random.RandomState(2)
    state = cache_mod.init_cache(ccfg)
    state = state._replace(pools=tuple(
        jnp.asarray(rng.randn(*p.shape) * 0.3, p.dtype)
        for p in state.pools))
    l_ref, s_ref = serve_model.decode_forward(CFG, ccfg, params, state, bt,
                                              pos, tok, act,
                                              paged_impl="reference")
    l_ker, s_ker = serve_model.decode_forward(CFG, ccfg, params, state, bt,
                                              pos, tok, act,
                                              paged_impl="kernel",
                                              interpret=True)
    np.testing.assert_allclose(np.asarray(l_ref), np.asarray(l_ker),
                               atol=2e-5)
    # the first layer's write sees the same inputs on both paths
    np.testing.assert_array_equal(np.asarray(s_ref.pools[0]),
                                  np.asarray(s_ker.pools[0]))
    for a, b in zip(s_ref.pools[1:], s_ker.pools[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# ---------------------------------------------------------------------------
# the pool: what is written is what is stored, whichever write stores it
# ---------------------------------------------------------------------------

def _write_case(fp8, dtype=jnp.bfloat16):
    """A prompt (13 of 24 padded positions live: one full page, one partly
    live, one dead) into layer 1, then a decode step of 4 rows into layer
    0: two live rows, one of them opening a page (slot 0), and two masked
    rows that carry the same K/V (as inactive slots do) to the null page."""
    ccfg = cache_mod.CacheConfig(num_layers=2, kv_heads=2, head_dim=16,
                                 num_pages=8, page_size=8, dtype=dtype,
                                 fp8=fp8)
    rng = np.random.RandomState(3)

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.5, jnp.float32)

    k_seq, v_seq = rand(24, 2, 16), rand(24, 2, 16)
    k_new, v_new = rand(4, 2, 16), rand(4, 2, 16)
    k_new = k_new.at[3].set(k_new[2])
    v_new = v_new.at[3].set(v_new[2])
    table = jnp.asarray([5, 2, 7], jnp.int32)
    page_ids = jnp.asarray([3, 6, 0, 0], jnp.int32)
    slots = jnp.asarray([5, 0, 0, 0], jnp.int32)

    def write(impl):
        state = cache_mod.init_cache(ccfg)
        state = cache_mod.write_prompt(ccfg, state, 1, table, jnp.int32(13),
                                       k_seq, v_seq, impl=impl,
                                       interpret=True)
        return cache_mod.write_token(ccfg, state, 0, page_ids, slots, k_new,
                                     v_new, impl=impl, interpret=True)
    return ccfg, write, (k_seq, v_seq, table), (k_new, v_new, page_ids, slots)


@pytest.mark.parametrize("fp8", [False, True], ids=["bf16", "e4m3"])
def test_pool_read_back_equals_written_kv(fp8):
    """K in lanes 0:d, V in d:2d of the token's row: the merged pool read
    back is the K and V that were written, in the stored type (bf16 rows,
    or e4m3 under the page's slot-0 scale)."""
    from apex_tpu.amp import fp8 as f8
    ccfg, write, (k_seq, v_seq, table), (k_new, v_new, page_ids, slots) = \
        _write_case(fp8)
    state = write("reference")
    d = ccfg.head_dim

    def stored(x, scale):
        if not fp8:
            return np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32))
        return np.asarray(f8.quantize(x, scale[..., None], f8.E4M3)
                          .astype(jnp.float32))

    def scale_of(first):             # the page's first token, [kv, d]
        return cache_mod._page_scales(ccfg, first) if fp8 else None

    for pos in range(13):            # the prompt's live positions, layer 1
        row = np.asarray(state.pools[1][:, int(table[pos // 8]), pos % 8]
                         .astype(jnp.float32))
        first = pos // 8 * 8
        np.testing.assert_array_equal(
            row[:, :d], stored(k_seq[pos], scale_of(k_seq[first])))
        np.testing.assert_array_equal(
            row[:, d:], stored(v_seq[pos], scale_of(v_seq[first])))
    # the decode row that opens page 6 (slot 0 sets its scale), layer 0
    row = np.asarray(state.pools[0][:, 6, 0].astype(jnp.float32))
    np.testing.assert_array_equal(row[:, :d],
                                  stored(k_new[1], scale_of(k_new[1])))
    np.testing.assert_array_equal(row[:, d:],
                                  stored(v_new[1], scale_of(v_new[1])))
    if fp8:
        np.testing.assert_array_equal(np.asarray(state.k_scale[0, :, 6]),
                                      np.asarray(scale_of(k_new[1])))
    # untouched pages of a written layer stay zero
    assert not np.asarray(state.pools[1][:, 1].astype(jnp.float32)).any()


@pytest.mark.parametrize("pool", ["f32", "bf16", "e4m3"])
def test_pallas_write_equals_xla_scatter_bitwise(pool):
    """The aliased Pallas writes (interpret mode) leave the whole state
    — every page of every layer, the null page with its masked rows, the
    fp8 scales — bit for bit as the XLA scatter leaves it."""
    _, write, _, _ = _write_case(
        pool == "e4m3", jnp.float32 if pool == "f32" else jnp.bfloat16)
    ref, ker = write("reference"), write("kernel")
    # the masked writes did land on the null page
    assert np.asarray(ref.pools[0][:, 0, 0].astype(jnp.float32)).any()
    assert np.asarray(ref.pools[1][:, 0, 5:].astype(jnp.float32)).any()
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(ker)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


#: the token write's cases: pool leaf [kv_heads, 6 pages, page_size, width],
#: its dtype, the most rows a program may take and the rows it then takes,
#: and the (page, slot) of every row. A bf16 tile is 16 rows of a page, an
#: e4m3 tile 32.
_TOKEN_WRITES = {
    # 7 rows in groups of 4: the last group is one row short
    "b_not_a_multiple_of_the_group": (
        (2, 32, 128), jnp.bfloat16, (4, 4),
        [(1, 3), (2, 17), (0, 0), (3, 31), (4, 0), (5, 16), (0, 0)]),
    # 3 rows where a program could take 32: one group of 3
    "b_under_the_group": (
        (2, 32, 128), jnp.bfloat16, (32, 3), [(4, 30), (1, 0), (2, 15)]),
    "every_row_masked": (
        (2, 32, 128), jnp.bfloat16, (4, 3), [(0, 0)] * 6),
    # the latent leaf of cells 5 and 6: one head, 640 lanes
    "one_head_of_640_lanes": (
        (1, 32, 640), jnp.bfloat16, (4, 3),
        [(5, 31), (0, 0), (1, 16), (2, 2), (3, 15)]),
    # cell 7's sparse leaf: two heads, pages of 64
    "two_heads_pages_of_64": (
        (2, 64, 256), jnp.bfloat16, (4, 3),
        [(1, 63), (2, 48), (0, 0), (3, 0), (4, 17), (5, 33)]),
    # 8-bit pages: a tile is 32 rows, two of them a page of 64
    "e4m3_tile_of_32": (
        (2, 64, 128), "e4m3", (4, 3),
        [(1, 31), (2, 32), (0, 0), (3, 63), (4, 0)]),
    # two REAL rows of one tile inside one group (slots 3 and 9 of page 2;
    # 20 is the same page's other tile), between rows of other pages, and
    # one slot written twice: the later row stays
    "two_rows_of_one_tile_in_one_group": (
        (2, 32, 128), jnp.bfloat16, (8, 7),
        [(1, 5), (2, 3), (4, 8), (2, 9), (2, 20), (3, 1), (4, 8)]),
    # ... and in two groups: rows 1 and 3 of the case above, 2 rows a group
    "two_rows_of_one_tile_in_two_groups": (
        (2, 32, 128), jnp.bfloat16, (2, 2),
        [(1, 5), (2, 3), (4, 8), (2, 9), (2, 20), (3, 1)]),
}


@pytest.mark.parametrize("case", list(_TOKEN_WRITES))
def test_grouped_token_write_equals_xla_scatter_bitwise(case, monkeypatch):
    """``paged_kv_write_rows`` (interpret mode) moves a group of rows a
    program; whatever the group and however its rows share tiles, every
    page of the leaf is bit for bit what the XLA scatter leaves."""
    from apex_tpu.amp import fp8 as f8
    (kv, page_size, width), dtype, (most, group), where = _TOKEN_WRITES[case]
    dtype = jnp.dtype(f8.E4M3 if dtype == "e4m3" else dtype)
    # the group is a function of shapes and of this constant: a trace
    # cached under another value of it must not answer for this one
    monkeypatch.setattr(fa_mod, "_WRITE_GROUP_ROWS", most)
    fa_mod._write_rows_call.clear_cache()
    rng = np.random.RandomState(len(case))
    pool = jnp.asarray(rng.randn(kv, 6, page_size, width), jnp.float32
                       ).astype(dtype)
    rows = jnp.asarray(rng.randn(len(where), kv, width), jnp.float32
                       ).astype(dtype)
    if case == "every_row_masked":     # inactive slots carry one row
        rows = jnp.broadcast_to(rows[:1], rows.shape)
    page_ids, slots = (jnp.asarray(x, jnp.int32) for x in zip(*where))
    assert group == fa_mod._write_group(
        len(where), kv, 32 // dtype.itemsize, width, dtype.itemsize)
    ref = pool.at[:, page_ids, slots].set(rows.transpose(1, 0, 2))
    try:
        got = fa_mod.paged_kv_write_rows(pool, page_ids, slots, rows,
                                         interpret=True)
    finally:
        fa_mod._write_rows_call.clear_cache()
    assert got.dtype == ref.dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(ref.astype(jnp.float32)))
    # the scatter itself kept the later of two rows of one slot
    for i, (page, slot) in enumerate(where):
        if (page, slot) not in where[i + 1:]:
            np.testing.assert_array_equal(
                np.asarray(got[:, page, slot].astype(jnp.float32)),
                np.asarray(rows[i].astype(jnp.float32)))


# ---------------------------------------------------------------------------
# layout rules
# ---------------------------------------------------------------------------

def test_serve_rules_cache_and_param_specs(params):
    from jax.sharding import PartitionSpec as P
    state = cache_mod.init_cache(cache_mod.CacheConfig(
        num_layers=1, kv_heads=2, head_dim=8, num_pages=4, page_size=8,
        fp8=True))
    spec = serve.match_serve_rules(serve.CACHE_RULES, state, world=2)
    assert spec.pools == (P("tensor", None, None, None),)
    assert spec.k_scale == P(None, "tensor", None)
    pspec = serve.match_serve_rules(serve.GPT_PARAM_RULES, params, world=2)
    assert pspec["block_0"]["attn"]["qkv"]["kernel"] == P(None, "tensor")
    assert pspec["block_0"]["attn"]["proj"]["kernel"] == P("tensor", None)
    assert pspec["block_0"]["mlp"]["fc2"]["kernel"] == P("tensor", None)
    assert pspec["wte"]["embedding"] == P("tensor", None)
    assert pspec["wpe"] == P()
    assert pspec["block_0"]["ln1"]["weight"] == P()
    # world 1: structural override — everything replicates
    p1 = serve.match_serve_rules(serve.GPT_PARAM_RULES, params, world=1)
    specs = jax.tree_util.tree_leaves(
        p1, is_leaf=lambda x: isinstance(x, P))
    assert specs and all(s == P() for s in specs)


def test_serve_rules_errors():
    with pytest.raises(ValueError, match="no serve layout rule"):
        serve.match_serve_rules((("^only_this$", "replicate"),),
                                {"other": np.zeros((4,))}, world=2)
    with pytest.raises(ValueError, match="not divisible"):
        serve.match_serve_rules(((".*", "shard:0"),),
                                {"x": np.zeros((3, 4))}, world=2)
    with pytest.raises(ValueError, match="decision"):
        serve.match_serve_rules(((".*", "bogus"),), {"x": np.zeros((4,))},
                                world=2)


# ---------------------------------------------------------------------------
# cache accounting + page-size resolution
# ---------------------------------------------------------------------------

def test_fp8_capacity_from_pool_accounting():
    """fp8-KV fits >= ~2x the concurrent sequences of bf16 at the SAME
    pool bytes — asserted from the block-pool byte accounting."""
    common = dict(num_layers=12, kv_heads=16, head_dim=64, num_pages=256,
                  page_size=128)
    bf16 = cache_mod.CacheConfig(dtype=jnp.bfloat16, **common)
    fp8 = cache_mod.CacheConfig(fp8=True, **common)
    # per-page bytes: e4m3 + per-page-per-head scales vs bf16
    ratio = fp8.bytes_per_page() / bf16.bytes_per_page()
    assert ratio <= 0.55, ratio
    budget = bf16.pool_bytes()
    seqs_bf16 = bf16.max_concurrent_seqs(budget, seq_len=1024)
    seqs_fp8 = fp8.max_concurrent_seqs(budget, seq_len=1024)
    assert seqs_fp8 >= 2 * seqs_bf16, (seqs_fp8, seqs_bf16)


def test_resolve_page_size_explicit_cached_heuristic(tmp_path):
    from apex_tpu.tune import TuneCache, cache_key
    from apex_tpu.tune import runtime as tune_rt
    kw = dict(kv_heads=2, head_dim=16, context_len=64, dtype=jnp.float32)
    # explicit wins over everything
    assert cache_mod.resolve_page_size(page_size=24, **kw) == 24
    # empty cache (conftest pins a fresh dir): heuristic
    assert cache_mod.resolve_page_size(**kw) == \
        min(cache_mod.DEFAULT_PAGE_SIZE, 64)
    # a tuned entry resolves through the same cache the CLI writes
    cache = TuneCache(str(tmp_path))
    shape = {"b": 1, "kv": 2, "group": 1, "s": 64, "d": 16, "itemsize": 4}
    cache.put(cache_key("decode_attention", shape, "float32",
                        {"fp8": False}), {"block_kv": 16})
    with tune_rt.override_cache_dir(str(tmp_path)):
        assert cache_mod.resolve_page_size(**kw) == 16
    # "off" skips the lookup
    with tune_rt.override_cache_dir(str(tmp_path)):
        assert cache_mod.resolve_page_size(autotune="off", **kw) == \
            min(cache_mod.DEFAULT_PAGE_SIZE, 64)


def test_decode_attention_tune_space_and_cli(tmp_path):
    from apex_tpu.ops.__main__ import main as ops_main
    from apex_tpu.tune import TuneCache
    from apex_tpu.tune.space import config_space
    cands = config_space("decode_attention",
                         {"s": 1024, "d": 64, "group": 1, "itemsize": 2})
    assert {"block_kv": 128} in cands and {"block_kv": 512} in cands
    # page sizes clip to the context like flash blocks clip to seq
    tiny = config_space("decode_attention", {"s": 16, "d": 8})
    assert tiny == [{"block_kv": 16}]
    rc = ops_main(["tune", "--kernel", "decode_attention", "--shapes",
                   "b=1,kv=1,s=16,d=8,dtype=float32", "--cache",
                   str(tmp_path), "--median-of", "1", "--warmup", "0",
                   "--interpret", "--json"])
    assert rc == 0
    entries = TuneCache(str(tmp_path)).entries()
    assert any(k.startswith("decode_attention|") for k in entries), entries


# ---------------------------------------------------------------------------
# scheduler state machine (pure host — no jax)
# ---------------------------------------------------------------------------

def _seq(i, n_prompt=6, max_new=8):
    return Sequence(seq_id=i, prompt=list(range(1, n_prompt + 1)),
                    max_new_tokens=max_new)


def test_scheduler_fcfs_admission_and_capacity():
    sched = Scheduler(num_pages=8, page_size=4, max_batch=4)
    for i in range(3):
        sched.add(_seq(i, n_prompt=6))       # needs ceil(7/4) = 2 pages
    plan = sched.schedule()
    # 7 usable pages: three 2-page admissions fit
    assert [s.seq_id for s in plan.prefill] == [0, 1, 2]
    assert sched.allocator.free_pages == 1
    # a fourth arrival now blocks (head-of-line, no pages)
    sched.add(_seq(3))
    plan = sched.schedule()
    assert plan.prefill == []
    assert sched.waiting[0].seq_id == 3


def test_scheduler_growth_on_page_boundary():
    sched = Scheduler(num_pages=8, page_size=4, max_batch=1)
    sched.add(_seq(0, n_prompt=6))
    plan = sched.schedule()
    (seq,) = plan.prefill
    assert len(seq.pages) == 2               # ceil((6+1)/4): positions 0..6
    seq.tokens.extend([99, 99])              # 8 tokens: position 7 no growth
    assert sched.schedule().decode == [seq]
    assert len(seq.pages) == 2
    seq.tokens.append(99)                    # 9 tokens: position 8 -> page 3
    sched.schedule()
    assert len(seq.pages) == 3


def test_scheduler_evicts_latest_on_exhaustion_and_readmits():
    sched = Scheduler(num_pages=5, page_size=4, max_batch=2)
    a, b = _seq(0, n_prompt=6), _seq(1, n_prompt=6)
    sched.add(a)
    sched.add(b)
    plan = sched.schedule()
    assert [s.seq_id for s in plan.prefill] == [0, 1]
    assert sched.allocator.free_pages == 0
    # A crosses a page boundary; no free pages -> B (latest) is evicted
    a.tokens.extend([9, 9, 9])               # 9 tokens -> 3 pages
    plan = sched.schedule()
    assert [s.seq_id for s in plan.preempted] == [1]
    assert b.state == WAITING and b.pages == [] and b.n_preemptions == 1
    assert b.tokens == list(b.prompt)        # tokens survive eviction
    assert a.state == RUNNING and len(a.pages) == 3
    # A finishing frees pages; B re-admits with its full token count
    sched.finish(a)
    plan = sched.schedule()
    assert [s.seq_id for s in plan.prefill] == [1]


def test_scheduler_self_preempts_when_latest():
    sched = Scheduler(num_pages=5, page_size=4, max_batch=2)
    a, b = _seq(0, n_prompt=4, max_new=20), _seq(1, n_prompt=4, max_new=20)
    sched.add(a)
    sched.add(b)
    plan = sched.schedule()
    assert len(plan.prefill) == 2            # 2 pages each, 4 usable
    # B is the latest arrival; when B itself needs the page, B yields
    b.tokens.extend([9] * 5)                 # 9 tokens -> needs page 3
    a.tokens.append(9)
    plan = sched.schedule()
    assert b in plan.preempted and a in plan.decode


def test_scheduler_pool_too_small_raises():
    sched = Scheduler(num_pages=2, page_size=4, max_batch=1)
    sched.add(_seq(0, n_prompt=8))           # needs 3 pages, 1 usable
    with pytest.raises(RuntimeError, match="never be admitted"):
        sched.schedule()


def test_page_allocator_invariants():
    alloc = PageAllocator(5)
    got = alloc.alloc(4)
    assert sorted(got) == [1, 2, 3, 4] and alloc.free_pages == 0
    assert alloc.alloc(1) is None
    alloc.free(got[:2])
    with pytest.raises(ValueError, match="double free"):
        alloc.free([got[0]])
    with pytest.raises(ValueError, match="invalid page"):
        alloc.free([0])


# ---------------------------------------------------------------------------
# engine contracts
# ---------------------------------------------------------------------------

def test_engine_matches_plain_gpt_greedy(params):
    """The serve path IS the training model: greedy tokens equal
    ``GPT.apply`` over the growing sequence, token for token."""
    _, ids, out, _ = _run(params)
    model = GPT(CFG)
    toks = list(PROMPTS[0])
    for _ in range(N_NEW):
        logits = model.apply({"params": params},
                             jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    assert out[ids[0]] == toks[len(PROMPTS[0]):]


def test_engine_run_returns_outputs(params):
    eng = _engine(params)
    ids = [eng.add_request(p, N_NEW) for p in PROMPTS]
    out = eng.run()
    assert set(out) == set(ids)
    assert all(len(v) == N_NEW for v in out.values())
    # every page returned to the allocator, no slot leaked
    assert eng.sched.allocator.free_pages == eng.ccfg.num_pages - 1
    assert eng.slots == [None, None]
    assert eng.tokens_generated == 2 * N_NEW


def _assert_logits_bitwise_equal(engA, engB, ids):
    for sid in ids:
        la, lb = engA.logits_log[sid], engB.logits_log[sid]
        assert set(la) == set(lb), (sid, sorted(la), sorted(lb))
        for pos in la:
            assert np.array_equal(la[pos], lb[pos]), (sid, pos)


def test_preempt_resume_bit_exact(params):
    """Forced preempt mid-generation: tokens AND every logits row
    (including the replayed ones) are BIT-identical to the
    uninterrupted run."""
    engA, ids, outA, _ = _run(params)
    engB, _, outB, n_pre = _run(params, preempt_at=4)
    assert n_pre >= 1                        # the preempt really landed
    assert outA == outB
    _assert_logits_bitwise_equal(engA, engB, ids)


def test_organic_evict_readmit_bit_exact(params):
    """Scheduler-driven evict-on-exhaustion (tiny pool) completes AND
    stays bit-exact vs a roomy-pool run."""
    engA, ids, outA, _ = _run(params, num_pages=32)
    # 5 usable pages vs a final demand of 3 pages/seq: exhaustion hits
    # when the second sequence needs its third page
    engB, idsB, outB, n_pre = _run(params, num_pages=6)
    assert ids == idsB
    assert n_pre >= 1, "pool was roomy enough that nothing evicted — " \
        "shrink it so the test bites"
    assert outA == outB
    _assert_logits_bitwise_equal(engA, engB, ids)


def test_fp8_kv_parity_teacher_forced(params):
    """fp8 cache vs full-precision cache within tolerance — TEACHER-
    FORCED (both paths process the same token sequence; a free-running
    comparison conflates quantization error with greedy-decode
    divergence, which is chaotic by construction)."""
    from apex_tpu.serve import model as serve_model
    prompt = PROMPTS[0]
    tail = [14, 3, 59, 22, 8, 41, 30, 7]

    def forced(fp8):
        ccfg = cache_mod.CacheConfig(
            num_layers=CFG.num_layers, kv_heads=CFG.num_heads,
            head_dim=CFG.hidden_size // CFG.num_heads, num_pages=8,
            page_size=8, dtype=jnp.float32, fp8=fp8)
        state = cache_mod.init_cache(ccfg)
        bt1 = jnp.asarray([1, 2, 3], jnp.int32)
        ids = jnp.asarray(prompt + [0] * (16 - len(prompt)), jnp.int32)
        rows = []
        logits, state = serve_model.prefill_forward(
            CFG, ccfg, params, state, bt1, jnp.int32(len(prompt)), ids)
        rows.append(np.asarray(logits))
        bts = jnp.asarray([[1, 2, 3]], jnp.int32)
        for j, tok in enumerate(tail):
            pos = len(prompt) + j
            logits, state = serve_model.decode_forward(
                CFG, ccfg, params, state, bts,
                jnp.asarray([pos], jnp.int32),
                jnp.asarray([tok], jnp.int32), jnp.ones((1,), bool))
            rows.append(np.asarray(logits[0]))
        return rows

    exact = forced(False)
    quant = forced(True)
    worst = max(float(np.max(np.abs(a - b))) for a, b in zip(exact, quant))
    mag = max(float(np.max(np.abs(a))) for a in exact)
    assert worst < 0.15 * max(mag, 1.0), (worst, mag)


def test_fp8_kv_bit_exact_resume(params):
    """The fp8 slot-0 scale rule keeps preempt/resume bit-exact too."""
    engF, ids, _, _ = _run(params, fp8=True)
    f1, _, _, n_pre = _run(params, fp8=True, preempt_at=5)
    assert n_pre >= 1
    _assert_logits_bitwise_equal(engF, f1, ids)


def test_engine_tp2_parity(params):
    engA, ids, outA, _ = _run(params)
    ps.destroy_model_parallel()
    try:
        ps.initialize_model_parallel(tensor_model_parallel_size_=2)
        eng2, _, out2, _ = _run(params)
    finally:
        ps.destroy_model_parallel()
    worst = max(float(np.max(np.abs(engA.logits_log[s][p]
                                    - eng2.logits_log[s][p])))
                for s in ids for p in engA.logits_log[s])
    assert worst < 2e-4, worst
    assert outA == out2                      # greedy tokens identical


def test_serve_scopes_in_analytic_profile(params):
    """monitor.attribution: the decode step's cost lands under
    the serve scope vocabulary (serve_decode / block_i / paged_attn /
    lm_head), so per-request attribution falls out of the existing
    analytic walk."""
    from apex_tpu.monitor import attribution as prof
    from apex_tpu.serve import model as serve_model
    ccfg = cache_mod.CacheConfig(num_layers=CFG.num_layers, kv_heads=2,
                                 head_dim=16, num_pages=4, page_size=8)
    state = cache_mod.init_cache(ccfg)
    bt = jnp.zeros((2, 2), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    tok = jnp.zeros((2,), jnp.int32)
    act = jnp.ones((2,), bool)

    def fn(params, state):
        return serve_model.decode_forward(CFG, ccfg, params, state, bt,
                                          pos, tok, act,
                                          paged_impl="reference")

    table = prof.analytic_profile(fn, params, state)
    scopes = set(table["scopes"])
    assert any(s.startswith("serve_decode") for s in scopes), scopes
    assert any("paged_attn" in s for s in scopes), scopes
    assert any("lm_head" in s for s in scopes), scopes
    assert table["flops_scope_coverage"] > 0.9


def test_naive_generate_baseline_matches_engine(params):
    """The full-recompute baseline is the SAME greedy decode — its
    outputs must equal the paged engine's (it only pays more compute)."""
    eng = _engine(params)
    ids = [eng.add_request(p, 6) for p in PROMPTS]
    out = eng.run()
    naive, _ = serve.naive_generate(CFG, params,
                                    [(p, 6) for p in PROMPTS],
                                    max_seq_len=32)
    assert naive == [out[i] for i in ids]


# ---------------------------------------------------------------------------
# one round ahead of the tokens read (PR 30)
# ---------------------------------------------------------------------------

MIXED = [([5, 9, 17, 3, 40, 22, 8], 9), ([11, 2, 33], 1), ([7] * 16, 12),
         ([1, 2, 3, 4, 5, 6, 7, 8, 9], 2), ([60, 50, 40, 30], 6)]


def _drive(eng, requests, *, after_step=None):
    """Add ``requests`` and step until the work ends, holding at every
    step what the engine promises of its counts: a token counts only once
    its value is in ``seq.tokens``."""
    ids = [eng.add_request(p, n) for p, n in requests]
    steps = 0
    while eng.sched.has_work:
        eng.step()
        steps += 1
        seqs = eng.seqs.values()
        assert eng.tokens_generated == sum(s.num_generated for s in seqs)
        assert sum(s.in_flight for s in seqs) == \
            sum(len(e.rows) for e in eng._in_flight)
        if after_step:
            after_step(eng, steps)
        assert steps < 500
    return ids


def _synchronous(eng, steps):
    """The parent's order of work: every token read in the step that
    dispatched it."""
    eng._drain("test")


def test_scheduler_goes_by_positions_dispatched():
    """Growth and rows go by ``num_dispatched``; a ``sent`` sequence (its
    last round has gone out) takes no row, grows nothing and is no
    victim."""
    sched = Scheduler(num_pages=4, page_size=4, max_batch=2)
    a, b = _seq(0, n_prompt=6, max_new=4), _seq(1, n_prompt=3, max_new=1)
    sched.add(a)
    sched.add(b)
    assert len(sched.schedule().prefill) == 2   # 2 + 1 pages of 3 usable
    a.in_flight = b.in_flight = 1               # the prefills' tokens
    assert (a.num_dispatched, a.sent, b.sent, b.done) == (7, False, True,
                                                          False)
    assert sched.schedule().decode == [a] and len(a.pages) == 2
    a.in_flight = 2                             # position 8 is next: a page
    a.tokens.append(9)
    plan = sched.schedule()                     # none free: A is the only
    assert plan.preempted == [a]                # victim, B is ``sent``
    assert b.state == RUNNING and plan.decode == []
    # re-admission covers the tokens in flight too: 9 positions + 1
    assert sched._pages_needed(a.num_dispatched + 1) == 3


@pytest.mark.parametrize("kw", [{}, {"fp8": True}], ids=["bf16", "fp8-kv"])
def test_one_round_ahead_equals_the_synchronous_order_bit_for_bit(params,
                                                                  kw):
    """Mixed prompts and lengths through five batch rows' worth of
    admissions: tokens and every ``record_logits`` row equal those of the
    synchronous order (a drain after every step), and the tokens are
    ``naive_generate``'s."""
    ahead, sync = (_engine(params, max_batch=3, **kw) for _ in range(2))
    ids = _drive(ahead, MIXED)
    assert _drive(sync, MIXED, after_step=_synchronous) == ids
    for sid, (prompt, n) in zip(ids, MIXED):
        assert ahead.seqs[sid].tokens == sync.seqs[sid].tokens
        assert sorted(ahead.logits_log[sid]) == \
            list(range(len(prompt), len(prompt) + n))
    _assert_logits_bitwise_equal(ahead, sync, ids)
    if not kw:
        naive, _ = serve.naive_generate(CFG, params, MIXED, max_seq_len=32)
        assert naive == [ahead.seqs[i].tokens[len(p):]
                         for i, (p, _) in zip(ids, MIXED)]


def test_run_leaves_nothing_in_flight_and_compiles_nothing_new(params):
    eng = _engine(params, max_batch=3)
    prefill, scalars = eng._prefill, []

    def seen_prefill(*args):
        scalars.extend((args[3], args[6]))       # length, slot
        return prefill(*args)

    eng._prefill = seen_prefill
    ids = [eng.add_request(p, n) for p, n in MIXED]
    out = eng.run()
    eng._prefill = prefill
    # host scalars ride the call as operands: a ``jnp.int32(x)`` would be
    # one more (tiny) compiled program a prefill
    assert len(scalars) == 2 * len(MIXED)
    assert all(isinstance(x, np.int32) for x in scalars)
    assert [len(out[i]) for i in ids] == [n for _, n in MIXED]
    assert eng._in_flight == []
    assert all(s.in_flight == 0 and s.done for s in eng.seqs.values())
    assert eng.slots == [None] * 3
    assert eng.sched.allocator.free_pages == eng.ccfg.num_pages - 1
    # still one decode and one prefill program, whatever fed them
    assert (eng._decode._cache_size(), eng._prefill._cache_size()) == (1, 1)


def test_steady_run_overlaps_every_round_but_the_first(params):
    """``serve/rounds_overlapped`` == decode rounds - 1, and no drain
    while decode rounds go out: the reads that overlap nothing are the
    first step's (prefills only: no decode went out) and the last, when
    the work has ended (both ``idle``)."""
    from apex_tpu import monitor
    rec = monitor.Recorder(traced_hooks=False)
    eng = _engine(params)
    with monitor.attached(rec):
        for p in PROMPTS:
            eng.add_request(p, N_NEW)
        eng.run()
    rounds = len(eng.decode_step_times)
    assert rounds == N_NEW - 1
    assert rec.counters()["serve/rounds_overlapped"] == rounds - 1
    events = [e.get("reason", e["name"]) for e in rec.records("counter")
              if e["name"] in ("serve/pipeline_drains",
                               "serve/rounds_overlapped")]
    assert events == (["idle"] + ["serve/rounds_overlapped"] * (rounds - 1)
                      + ["idle"])
    assert all(t > 0 for t in eng.decode_step_times)


def test_forced_preempt_with_a_token_in_flight_drains_once(params):
    """``preempt()`` reads the sequence's token in flight first (one
    drain, reason ``preempt``); the sequence re-enters by its values and
    resumes BIT-exact."""
    from apex_tpu import monitor
    plain, ids, out, _ = _run(params)
    rec = monitor.Recorder(traced_hooks=False)
    eng = _engine(params)
    with monitor.attached(rec):
        assert [eng.add_request(p, N_NEW) for p in PROMPTS] == ids
        for _ in range(4):
            eng.step()
        seq = eng.seqs[ids[0]]
        kept = seq.num_dispatched
        assert seq.in_flight == 1
        eng.preempt(ids[0])
        assert (seq.in_flight, seq.num_tokens, seq.state) == (0, kept,
                                                              WAITING)
        assert eng._in_flight == []
        eng.run()
    reasons = [e["reason"] for e in rec.records("counter")
               if e["name"] == "serve/pipeline_drains"]
    assert reasons == ["idle", "preempt", "idle"]
    assert {i: eng.seqs[i].tokens[len(eng.seqs[i].prompt):]
            for i in ids} == out
    _assert_logits_bitwise_equal(plain, eng, ids)
    # replay went through the same two programs
    assert (eng._decode._cache_size(), eng._prefill._cache_size()) == (1, 1)


def test_round_is_dispatched_before_the_previous_rounds_tokens_are_read(
        params):
    """The order of a steady run, with ``_decode`` and ``_fetch`` stubbed
    to log: dispatch 1, dispatch 2, read 1, dispatch 3, read 2, ..."""
    eng = _engine(params)
    log, decode, fetch = [], eng._decode, eng._fetch

    def logged_decode(*args):
        log.append(("dispatch", sum(k == "dispatch" for k, _ in log) + 1))
        return decode(*args)

    def logged_fetch(e):
        if e.decode:
            log.append(("read", sum(k == "read" for k, _ in log) + 1))
        return fetch(e)

    eng._decode, eng._fetch = logged_decode, logged_fetch
    for p in PROMPTS:
        eng.add_request(p, 5)
    eng.run()
    want = [("dispatch", 1)]
    for n in range(2, 5):
        want += [("dispatch", n), ("read", n - 1)]
    assert log == want + [("read", 4)]
