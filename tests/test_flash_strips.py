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
