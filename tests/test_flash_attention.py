"""Flash attention kernel parity tests (Pallas interpret mode on CPU).

Mirrors ``apex/contrib/test/fmha/test_fmha.py`` and
``apex/contrib/test/multihead_attn/*``: the fused kernel must match the
unfused reference for values and gradients, including causal masking and
packed-varlen (segment id) batches.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.flash_attention import flash_attention, mha_reference


def _qkv(b=2, h=3, sq=64, sk=64, d=16, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, h, sq, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, sk, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, sk, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_flash_multiblock_online_softmax():
    """Many k blocks exercise the running (m, l, acc) rescaling."""
    q, k, v = _qkv(b=1, h=2, sq=32, sk=128, d=8, seed=1)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_flash_gradients():
    q, k, v = _qkv(b=1, h=2, sq=32, sk=32, d=8, seed=2)

    def f_fused(q, k, v):
        return jnp.sum(jnp.tanh(flash_attention(q, k, v, causal=True,
                                                block_q=16, block_k=16)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.tanh(mha_reference(q, k, v, causal=True)))

    g1 = jax.grad(f_fused, (0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    for a, r in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-3, atol=1e-4)


def test_flash_segment_ids_varlen():
    """Packed batch: two sequences per row must not attend across the
    boundary (FMHA cu_seqlens parity)."""
    b, h, s, d = 1, 2, 32, 8
    q, k, v = _qkv(b, h, s, s, d, seed=3)
    sid = jnp.asarray(np.repeat([[0] * 12 + [1] * 20], b, 0))
    out = flash_attention(q, k, v, segment_ids_q=sid, block_q=16, block_k=16)
    ref = mha_reference(q, k, v, segment_ids_q=sid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-5)

    # cross-check isolation directly: perturbing segment 1's v must not
    # change segment 0's outputs
    v2 = v.at[:, :, 20:].add(10.0)
    out2 = flash_attention(q, k, v2, segment_ids_q=sid, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out[:, :, :12]), np.asarray(out2[:, :, :12]),
                               rtol=1e-5, atol=1e-6)
    assert not np.allclose(np.asarray(out[:, :, 12:]), np.asarray(out2[:, :, 12:]))


def test_flash_bf16():
    q, k, v = _qkv(d=8)
    out = flash_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                          v.astype(jnp.bfloat16), block_q=32, block_k=32)
    assert out.dtype == jnp.bfloat16
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


def test_flash_indivisible_lengths_padded():
    """Lengths that don't divide the block size are padded internally."""
    for causal in (False, True):
        q, k, v = _qkv(sq=33, sk=33)
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


def test_flash_causal_default_blocks_odd_lengths():
    """The r4 causal DEFAULT block rule (two 512-aligned blocks per
    sequence for sq >= 1024) must stay numerically exact for sequence
    lengths that are not block multiples — sq=1100 resolves the default
    to 512 and pads to 1536; fwd and grads must match the reference."""
    q, k, v = _qkv(b=1, h=2, sq=1100, sk=1100, d=8, seed=11)
    out = flash_attention(q, k, v, causal=True)   # default block path
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)

    def loss_flash(q):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g = jax.grad(loss_flash)(q)
    g_ref = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_negative_segment_ids_are_padding():
    """id < 0 rows: zero output, no influence on real rows, zero grads in."""
    b, h, s, d = 1, 2, 32, 8
    q, k, v = _qkv(b, h, s, s, d, seed=5)
    sid = jnp.asarray(np.repeat([[1] * 20 + [-1] * 12], b, 0))

    out = flash_attention(q, k, v, segment_ids_q=sid, block_q=16, block_k=16)
    np.testing.assert_array_equal(np.asarray(out[:, :, 20:]), 0.0)

    # pad tokens must not leak into real rows: perturb padded k/v
    k2 = k.at[:, :, 20:].add(100.0)
    v2 = v.at[:, :, 20:].add(100.0)
    out2 = flash_attention(q, k2, v2, segment_ids_q=sid, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out[:, :, :20]),
                               np.asarray(out2[:, :, :20]), rtol=1e-5, atol=1e-6)

    # gradients w.r.t. padded positions are exactly zero even when the
    # cotangent is nonzero there (lse of an empty row must not produce
    # exp(0)=1 weights in the backward)
    def f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, segment_ids_q=sid,
                                       block_q=16, block_k=16))
    dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_array_equal(np.asarray(dq[:, :, 20:]), 0.0)
    np.testing.assert_array_equal(np.asarray(dk[:, :, 20:]), 0.0)
    np.testing.assert_array_equal(np.asarray(dv[:, :, 20:]), 0.0)
    assert np.isfinite(np.asarray(dq)).all()


# ---------------------------------------------------------------------------
# Additive bias (fast-MHA additive attn-mask parity)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bias_bh", [(1, 1), (2, 3)])
def test_flash_bias(bias_bh):
    b, h, s, d = 2, 3, 64, 8
    q, k, v = _qkv(b, h, s, s, d, seed=7)
    rng = np.random.RandomState(8)
    bias = jnp.asarray(rng.randn(bias_bh[0], bias_bh[1], s, s), jnp.float32)
    out = flash_attention(q, k, v, bias=bias, block_q=32, block_k=32)
    ref = mha_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)

    def f(q, k, v):
        return jnp.sum(jnp.tanh(flash_attention(q, k, v, bias=bias,
                                                block_q=32, block_k=32)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.tanh(mha_reference(q, k, v, bias=bias)))

    g1 = jax.grad(f, (0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    for a, r in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-3, atol=1e-4)


def test_flash_bias_shape_validation():
    q, k, v = _qkv(2, 3, 32, 32, 8)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, bias=jnp.zeros((2, 3, 16, 32)))


def test_flash_split_phase_blocks_match():
    """r5 API: explicit block_q_bwd/block_k_bwd different from the
    forward blocks must produce the same values and gradients as one
    uniform tiling (the phase split is a pure scheduling choice)."""
    b, h, s, d = 2, 3, 128, 8
    q, k, v = _qkv(b, h, s, s, d, seed=23)

    def loss(q, k, v, **kw):
        return jnp.sum(jnp.tanh(flash_attention(q, k, v, causal=True,
                                                **kw)))

    base = jax.grad(loss, (0, 1, 2))(q, k, v, block_q=64, block_k=64,
                                     block_q_bwd=64, block_k_bwd=64)
    split = jax.grad(loss, (0, 1, 2))(q, k, v, block_q=128, block_k=128,
                                      block_q_bwd=32, block_k_bwd=64)
    for a, b_ in zip(base, split):
        np.testing.assert_allclose(
            np.asarray(b_.astype(jnp.float32)),
            np.asarray(a.astype(jnp.float32)), rtol=1e-4, atol=1e-5)


def test_flash_single_block_causal_sq_gt_sk_dead_rows():
    """Regression (r5 single-kb specialization): causal with sq > sk
    leaves the leading q rows with NO visible key; at n_kb == 1 those
    dead blocks must still be WRITTEN (zero rows, -1e30-ish lse), not
    skipped (uninitialized VMEM on hardware)."""
    b, h, sq, sk, d = 1, 2, 64, 16, 8
    q, k, v = _qkv(b, h, sq, sk, d, seed=17)
    out = flash_attention(q, k, v, causal=True)     # single k block
    out = np.asarray(out.astype(jnp.float32))
    # rows 0..sq-sk-1 see no key (causal_offset = sk - sq < 0)
    dead = sq - sk
    np.testing.assert_array_equal(out[:, :, :dead], 0.0)
    ref = np.asarray(mha_reference(q, k, v, causal=True)
                     .astype(jnp.float32))
    np.testing.assert_allclose(out[:, :, dead:], ref[:, :, dead:],
                               rtol=1e-4, atol=1e-5)


def test_flash_single_block_neg_inf_bias_row_zero():
    """Regression (r5): a fully -inf additive-bias row at n_kb == 1
    (mask is None: non-causal, unsegmented, block-aligned) must give a
    ZERO output row, not NaN — the exact-softmax row max is floored at
    -1e30 like the carry path's m_prev."""
    b, h, s, d = 1, 2, 32, 8
    q, k, v = _qkv(b, h, s, s, d, seed=19)
    bias = jnp.zeros((1, 1, s, s), jnp.float32).at[:, :, 3, :].set(-jnp.inf)
    out = np.asarray(flash_attention(q, k, v, bias=bias)
                     .astype(jnp.float32))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[:, :, 3], 0.0)


def test_flash_causal_bias_neg_inf_row_no_future_leak():
    """Regression (r5): a -1e30 additive-bias row under causal pushes
    every LIVE score down to the causal fill value (-1e30 absorbs any
    finite logit in fp32), so the row max equals the masked fill and
    exp(s - m) = 1 on causally-masked entries unless the kernel keeps
    its post-exp guard for bias shapes. The observable contract: the
    degenerate row degrades to uniform attention over the VISIBLE
    positions — its output must be completely insensitive to future
    v rows (no causality leak), and stay finite."""
    b, h, s, d = 1, 2, 64, 8
    q, k, v = _qkv(b, h, s, s, d, seed=11)
    rng = np.random.RandomState(12)
    bias = jnp.asarray(rng.randn(1, 1, s, s) * 0.2, jnp.float32)
    dead_row = 5
    bias = bias.at[:, :, dead_row, :].set(-1e30)

    def run(v):
        return np.asarray(flash_attention(
            q, k, v, bias=bias, causal=True, block_q=32, block_k=32)
            .astype(jnp.float32))

    out = run(v)
    # perturb ONLY the future keys' values: the causal rows (incl. the
    # degenerate one) must not move at all
    v2 = v.at[:, :, dead_row + 1:].add(100.0)
    out2 = run(v2)
    np.testing.assert_array_equal(out[:, :, :dead_row + 1],
                                  out2[:, :, :dead_row + 1])
    # degenerate row = uniform average of the visible v rows
    expect = np.asarray(jnp.mean(v[:, :, :dead_row + 1].astype(jnp.float32),
                                 axis=2))
    np.testing.assert_allclose(out[:, :, dead_row], expect,
                               rtol=1e-4, atol=1e-5)
    # the other rows still match the reference
    ref = np.asarray(mha_reference(q, k, v, bias=bias, causal=True)
                     .astype(jnp.float32))
    live = [i for i in range(s) if i != dead_row]
    np.testing.assert_allclose(out[:, :, live], ref[:, :, live],
                               rtol=1e-4, atol=1e-5)
    # gradients stay finite and dv gets no contribution from the future
    # of the degenerate row beyond what live rows give it
    g = jax.grad(lambda q, k, v: jnp.sum(jnp.tanh(
        flash_attention(q, k, v, bias=bias, causal=True,
                        block_q=32, block_k=32))), (0, 1, 2))(q, k, v)
    for a in g:
        assert np.isfinite(np.asarray(a.astype(jnp.float32))).all()


# ---------------------------------------------------------------------------
# In-kernel dropout: the keep mask is a counter-based hash of
# (seed, b, h, q_pos, k_pos), so ``dropout_keep_reference`` regenerates
# the exact mask in plain XLA and the unfused reference computes the exact
# expected output and gradients (reference analog: fmha p_dropout,
# apex/contrib/csrc/fmha/fmha_api.cpp:67-110).
# ---------------------------------------------------------------------------

def _extract_keep_mask(b, h, s_q, s_k, block_q, block_k, seed, rate):
    from apex_tpu.ops.flash_attention import dropout_keep_reference
    del block_q, block_k  # the mask is block-size independent by design
    return dropout_keep_reference(seed, b, h, s_q, s_k, rate).astype(
        jnp.float32)


def _dropout_ref(q, k, v, keep, rate, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d ** -0.5)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        cm = jnp.arange(sk)[None, :] > jnp.arange(sq)[:, None] + (sk - sq)
        s = jnp.where(cm, -1e30, s)
    p = jax.nn.softmax(s, axis=-1)
    p = p * keep / (1.0 - rate)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_dropout_exact_parity(causal):
    b, h, s, d, rate, seed = 1, 2, 64, 8, 0.35, 1234
    q, k, v = _qkv(b, h, s, s, d, seed=9)
    keep = _extract_keep_mask(b, h, s, s, 32, 32, seed, rate)

    out = flash_attention(q, k, v, causal=causal, dropout_rate=rate,
                          dropout_seed=seed, block_q=32, block_k=32)
    ref = _dropout_ref(q, k, v, keep, rate, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)

    # gradients: custom-vjp Pallas backward vs autodiff of the exact
    # reference expression with the identical mask
    def f(q, k, v):
        return jnp.sum(jnp.tanh(flash_attention(
            q, k, v, causal=causal, dropout_rate=rate, dropout_seed=seed,
            block_q=32, block_k=32)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.tanh(_dropout_ref(q, k, v, keep, rate,
                                             causal=causal)))

    g1 = jax.grad(f, (0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    for a, r in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-3, atol=1e-4)


def test_flash_dropout_determinism_and_rate():
    b, h, s, d, rate = 1, 2, 64, 8, 0.25
    q, k, v = _qkv(b, h, s, s, d, seed=10)
    o1 = flash_attention(q, k, v, dropout_rate=rate, dropout_seed=7,
                         block_q=32, block_k=32)
    o2 = flash_attention(q, k, v, dropout_rate=rate, dropout_seed=7,
                         block_q=32, block_k=32)
    o3 = flash_attention(q, k, v, dropout_rate=rate, dropout_seed=8,
                         block_q=32, block_k=32)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert not np.allclose(np.asarray(o1), np.asarray(o3))

    keep = _extract_keep_mask(b, h, s, s, 32, 32, 7, rate)
    frac = float(keep.mean())
    assert abs(frac - (1.0 - rate)) < 0.05

    with pytest.raises(ValueError):
        flash_attention(q, k, v, dropout_rate=rate)  # seed required


def test_flash_dropout_zero_rate_matches_plain():
    q, k, v = _qkv(1, 2, 32, 32, 8, seed=11)
    o1 = flash_attention(q, k, v, block_q=32, block_k=32)
    o2 = flash_attention(q, k, v, dropout_rate=0.0, dropout_seed=3,
                         block_q=32, block_k=32)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


# ---------------------------------------------------------------------------
# Backward memory: the Pallas backward must not materialize [sq, sk]
# ---------------------------------------------------------------------------

def test_flash_backward_memory_flat_in_seqlen():
    """The backward jaxpr must contain no [*, *, s, s] intermediate —
    residuals and temporaries stay O(s). (On TPU hardware the same property
    is certified by compile-time memory_analysis; this structural check
    runs everywhere.)"""
    b, h, d = 1, 2, 16

    def biggest_intermediate(s):
        q, k, v = _qkv(b, h, s, s, d, seed=12)

        def f(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True))

        from apex_tpu.lint.jaxpr_checks import max_intermediate_size
        return max_intermediate_size(
            jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, k, v).jaxpr)

    small = biggest_intermediate(256)
    big = biggest_intermediate(1024)
    # O(s): 4x seqlen -> ~4x biggest buffer. An O(s^2) backward would be 16x.
    assert big <= small * 6, (small, big)


@pytest.mark.parametrize("features", ["plain", "dropout", "seg_bias"])
def test_bwd_two_kernel_fallback_matches_fused(monkeypatch, features):
    """Long-sequence fallback (two-kernel flash-attention-2 backward) and
    the fused single-pass backward must produce identical gradients —
    including the feature wiring (dropout key plumbing; the dkdv kernel's
    swapped qdim/kdim specs for segment-ids and bias)."""
    import importlib
    fa = importlib.import_module("apex_tpu.ops.flash_attention")
    rng = np.random.RandomState(11)
    b, h, s, d = 1, 2, 256, 32
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    kw = dict(causal=True, block_q=128, block_k=128)
    if features == "dropout":
        kw.update(dropout_rate=0.3, dropout_seed=17)
    elif features == "seg_bias":
        sid = jnp.asarray(rng.randint(0, 3, (b, s)).cumsum(-1) // 2,
                          jnp.int32)  # non-trivial monotone segments
        bias = jnp.asarray(rng.randn(1, 1, s, s) * 0.2, jnp.float32)
        kw.update(segment_ids_q=sid, bias=bias)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, **kw) ** 2)

    g_fused = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setattr(fa, "_FUSED_BWD_MAX_KV_BYTES", 0)
    g_two = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(g_fused, g_two):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)


def test_inherited_bwd_blocks_warns_once():
    """Explicit forward blocks silently governed the backward
    — now they warn, once, and only when the backward blocks are left to
    inherit; passing block_q_bwd/block_k_bwd stays silent."""
    import warnings
    from apex_tpu.utils import parity

    q, k, v = _qkv(sq=32, sk=32)
    key = "flash_attention.inherited_bwd_blocks"
    parity._seen.discard(key)
    with pytest.warns(UserWarning, match="govern the BACKWARD"):
        flash_attention(q, k, v, block_q=16, block_k=16)
    # once per process: second call is silent
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        flash_attention(q, k, v, block_q=16, block_k=16)
    # explicit backward blocks: no inheritance, no warning
    parity._seen.discard(key)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        flash_attention(q, k, v, block_q=16, block_k=16,
                        block_q_bwd=16, block_k_bwd=16)
        # defaults (no explicit forward blocks) stay silent too
        flash_attention(q, k, v)


def test_fmha_shim_does_not_trip_inherited_blocks_warning():
    """fmha_varlen states its backward blocks explicitly: the library's
    own shim must neither warn (unactionable through its API) nor
    consume the once-per-process key a real user call should get."""
    import warnings
    from apex_tpu.contrib.fmha import fmha_varlen
    from apex_tpu.utils import parity

    parity._seen.discard("flash_attention.inherited_bwd_blocks")
    rng = np.random.RandomState(3)
    total, h, d = 32, 2, 16
    qkv = jnp.asarray(rng.randn(total, 3, h, d), jnp.float32)
    cu = jnp.asarray([0, 16, 32], jnp.int32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        fmha_varlen(qkv, cu, block=16)
    # the key is still free for a genuine implicit-backward user call
    with pytest.warns(UserWarning, match="govern the BACKWARD"):
        q, k, v = _qkv(sq=32, sk=32)
        flash_attention(q, k, v, block_q=16, block_k=16)


# -- the forward's two results carry names (for a caller's jax.checkpoint) --------

#: (query heads, key/value heads, window): plain causal, a sliding window,
#: grouped key/value heads, both
NAMED_CALLS = {"causal": (4, 4, None), "windowed": (4, 4, 24),
               "grouped": (4, 2, None), "grouped_windowed": (4, 2, 24)}


@pytest.mark.parametrize("case", list(NAMED_CALLS))
def test_result_names_are_inert_without_a_policy(case):
    """``_fa_fwd`` tags the kernel's output and log-sum-exp with
    ``checkpoint_name``; where no ``jax.checkpoint`` policy names them the
    call is what it was: outputs and gradients bit-equal to the two jitted
    kernel calls made by hand, and one forward call in the gradient's
    jaxpr."""
    import importlib
    from apex_tpu.lint.jaxpr_checks import iter_eqns
    fa = importlib.import_module("apex_tpu.ops.flash_attention")
    h, hk, window = NAMED_CALLS[case]
    rng = np.random.RandomState(7)
    q, do = (jnp.asarray(rng.randn(1, h, 64, 16), jnp.float32)
             for _ in range(2))
    k, v = (jnp.asarray(rng.randn(1, hk, 64, 16), jnp.float32)
            for _ in range(2))
    blocks = dict(block_q=32, block_k=32, block_q_bwd=32, block_k_bwd=32)

    def call(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               interpret=True, **blocks)

    out, vjp = jax.vjp(call, q, k, v)
    got = vjp(do)

    scale, seed = 16 ** -0.5, jnp.zeros((1,), jnp.int32)
    want_out, lse = fa._flash_fwd_impl(q, k, v, None, None, None, seed,
                                       scale, True, 0.0, 32, 32, True, window)
    want = fa._flash_bwd_impl(
        (q, k, v, want_out, lse, None, None, None, seed), do, scale=scale,
        causal=True, dropout_rate=0.0, block_q=32, block_k=32,
        interpret=True, window=window)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(call(q, k, v) * do), (0, 1, 2)))(q, k, v)
    names = [e.params["name"] for e in iter_eqns(jaxpr.jaxpr)
             if e.primitive.name in ("pjit", "jit")]
    assert names.count("_flash_fwd_impl") == 1
    assert names.count("_flash_bwd_impl") == 1
    tags = [e.params["name"] for e in iter_eqns(jaxpr.jaxpr)
            if e.primitive.name == "name"]
    assert sorted(tags) == sorted([fa.FLASH_OUT, fa.FLASH_LSE])


def test_result_names_are_exported_and_documented():
    import importlib
    fa = importlib.import_module("apex_tpu.ops.flash_attention")
    assert (fa.FLASH_OUT, fa.FLASH_LSE) == ("flash_attention_out",
                                            "flash_attention_lse")
    doc = flash_attention.__doc__
    for word in ("FLASH_OUT", "FLASH_LSE", fa.FLASH_OUT, fa.FLASH_LSE,
                 "save_only_these_names"):
        assert word in doc, word
