"""The chunked gated delta rule (``ops/gated_delta.py``) against the
recurrence token by token: forward and every gradient, for both
implementations of the walk over the chunks (the Pallas kernels,
interpreted, and the ``lax.scan`` in ``jax.numpy``), at chunk boundaries,
for a sequence the chunk does not divide, and with decays that a cumulated
product would lose. On the CPU, at two heads of 128 lanes; lengths in
chunks (``C`` = ``gated_delta.CHUNK``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import monitor
from apex_tpu.ops import gated_delta as gd

DK = DV = 128
C = gd.CHUNK


def _inputs(t, *, heads=2, decay=1.0, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (1, heads, t, DK))
    k = jax.random.normal(ks[1], (1, heads, t, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (1, heads, t, DV))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (1, heads, t)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, heads, t)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _rule(impl):
    return functools.partial(gd.gated_delta_rule, impl=impl, interpret=True)


def _close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want)))
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) \
        <= tol * scale


# one chunk; a boundary; a tail of 8 past three chunks
@pytest.mark.parametrize("t", [C, 2 * C, 3 * C + 8])
@pytest.mark.parametrize("impl", gd.IMPLS)
def test_chunked_forward_is_the_recurrence(impl, t):
    args = _inputs(t)
    o_ref, s_ref = gd.gated_delta_reference(*args)
    o, s = _rule(impl)(*args)
    assert o.shape == (1, 2, t, DV) and s.shape == (1, 2, DK, DV)
    assert s.dtype == jnp.float32
    _close(o, o_ref, 2e-5)
    _close(s, s_ref, 2e-5)
    # the rows on both sides of each boundary, by themselves
    for edge in range(C, t, C):
        _close(o[:, :, edge - 1:edge + 1], o_ref[:, :, edge - 1:edge + 1],
               2e-5)


@pytest.mark.parametrize("impl", gd.IMPLS)
def test_every_gradient_is_the_recurrences(impl):
    """Through the outputs AND the last state, to q, k, v, g and beta; two
    whole chunks and a padded one."""
    args = _inputs(2 * C + 8, seed=1)
    o_ref, s_ref = gd.gated_delta_reference(*args)
    wo = jax.random.normal(jax.random.PRNGKey(9), o_ref.shape)
    ws = jax.random.normal(jax.random.PRNGKey(8), s_ref.shape)

    def scalar(fn):
        def f(*a):
            o, s = fn(*a)
            return jnp.sum(o * wo) + jnp.sum(s * ws)
        return jax.grad(f, argnums=(0, 1, 2, 3, 4))

    want = scalar(gd.gated_delta_reference)(*args)
    got = scalar(_rule(impl))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.shape == b.shape, name
        _close(a, b, 5e-5)


@pytest.mark.parametrize("impl", gd.IMPLS)
def test_decays_that_a_cumulated_product_would_lose(impl):
    """g ~ -60 a token: exp of a chunk's cumulated decay is exp(-3,800) = 0
    in float32 (at 64 tokens; sooner at more) and its reciprocal infinite;
    here every exponent is a difference of two cumulated decays of one chunk,
    and never positive."""
    args = _inputs(2 * C, decay=60.0, seed=2)
    assert float(jnp.exp(jnp.sum(args[3][0, 0, :C]))) == 0.0
    o_ref, s_ref = gd.gated_delta_reference(*args)
    o, s = _rule(impl)(*args)
    _close(o, o_ref, 2e-5)
    _close(s, s_ref, 2e-5)
    grads = jax.grad(lambda *a: jnp.sum(_rule(impl)(*a)[0] ** 2),
                     argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(lambda *a: jnp.sum(gd.gated_delta_reference(*a)[0] ** 2),
                    argnums=(0, 1, 2, 3, 4))(*args)
    # the decays' cotangent is a difference of a row sum and a column sum
    # of one matrix, in float32: 2e-4 of the largest entry where 5e-5 holds
    # at ordinary decays
    for a, b in zip(grads, want):
        _close(a, b, 2e-4)


def test_no_decay_and_full_strength_is_the_plain_delta_rule():
    """g = 0, beta = 1: the state ends holding each key's last value along
    that key (orthonormal keys: exactly)."""
    t = 64
    k = jnp.eye(DK)[None, None, :t]
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 1, t, DV))
    zeros, ones = jnp.zeros((1, 1, t)), jnp.ones((1, 1, t))
    o, s = _rule("kernel")(k, k, v, zeros, ones)
    np.testing.assert_allclose(np.asarray(s[0, 0, :t]), np.asarray(v[0, 0]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(o), np.asarray(v), atol=1e-6)


def test_the_triangular_inverse_is_exact_where_keys_repeat():
    """The worst case of the in-chunk system: every key the same, full
    strength, no decay: ``I + B`` is the all-ones lower triangle, whose
    inverse is bidiagonal while ``B``'s powers grow binomially (C(63, 31) ~
    9e17 in one 64-block: a Neumann product over the whole chunk would lose
    every digit). Inside 8-blocks they stay under 35."""
    c = C
    ri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    B = jnp.tril(jnp.ones((c, c), jnp.float32), -1)
    X = gd._unit_lower_inverse(B, ri, ci)
    want = jnp.eye(c) - jnp.eye(c, k=-1)
    np.testing.assert_allclose(np.asarray(X), np.asarray(want), atol=1e-5)
    # and random strictly lower ones, against a dense solve
    for seed in (4, 5):
        B = jnp.tril(jax.random.normal(jax.random.PRNGKey(seed), (c, c)),
                     -1) / 8
        np.testing.assert_allclose(
            np.asarray(gd._unit_lower_inverse(B, ri, ci)),
            np.linalg.inv(np.eye(c) + np.asarray(B, np.float64)),
            rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("impl", gd.IMPLS)
def test_bf16_operands_keep_a_float32_state(impl):
    """bf16 q, k, v: outputs in bf16 within bf16's rounding of the float32
    recurrence on the same (rounded) inputs; the state comes back float32."""
    args = _inputs(2 * C + C // 2, seed=6, dtype=jnp.bfloat16)
    o_ref, s_ref = gd.gated_delta_reference(*args)
    o, s = _rule(impl)(*args)
    assert o.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    _close(o, o_ref, 2e-2)
    _close(s, s_ref, 2e-2)


def test_the_kernels_are_the_scan_over_chunks():
    """The two implementations of the walk, on the same operands: equal to
    rounding, outputs and the six cotangents."""
    q, k, v, g, beta = _inputs(2 * C, seed=7)
    chunked = lambda x: x.reshape((2, 2, C) + x.shape[3:])
    q, k, v, g, beta = map(chunked, (q, k, v, g, beta))
    ops = jax.vmap(jax.vmap(gd._chunk_operands))(
        q, k, v, jnp.cumsum(g, -1)[:, :, None], beta[:, :, None])
    wo = jax.random.normal(jax.random.PRNGKey(1), (2, 2, C, DV))

    def loss(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a)[0] * wo) + jnp.sum(fn(*a)[1]),
            argnums=tuple(range(6)))

    v_k, g_k = loss(lambda *a: gd._scan(*a, True))(*ops)
    v_r, g_r = loss(gd._scan_reference)(*ops)
    assert float(v_k) == pytest.approx(float(v_r), rel=1e-5)
    for a, b in zip(g_k, g_r):
        _close(a, b, 2e-5)


def test_what_a_chunk_knows_alone_has_the_backward_jax_derives():
    """The chunk-local kernels against their own per-chunk body under
    ``jax.vmap``, which JAX differentiates: the six operands of the walk
    and, through arbitrary cotangents of all six, dq, dk, dv and the
    cotangents of the cumulated decays and of beta (the hand-written
    backward kernel). Decays fast enough that the upper triangle's
    exponents are large."""
    q, k, v, g, beta = _inputs(4 * C, decay=8.0, seed=12)
    chunked = lambda x: x.reshape((2, 4, C) + x.shape[3:])
    q, k, v, g, beta = map(chunked, (q, k, v, g, beta))
    rows = jnp.cumsum(g, -1)[:, :, None], beta[:, :, None]
    ws = [jax.random.normal(jax.random.PRNGKey(20 + i), shape) for i, shape
          in enumerate([(2, 4, C, DV)] + [(2, 4, C, DK)] * 3
                       + [(2, 4, C, C), (2, 4, 1, DV)])]

    def scalar(fn):
        def f(*a):
            ops = fn(*a)
            return sum(jnp.sum(o * w) for o, w in zip(ops, ws)), ops
        return jax.grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True)

    got, ops_k = scalar(lambda *a: gd._local(*a, True))(q, k, v, *rows)
    want, ops_p = scalar(jax.vmap(jax.vmap(gd._chunk_operands)))(
        q, k, v, *rows)
    for a, b in zip(ops_k, ops_p):
        _close(a, b, 2e-5)
    for a, b in zip(got, want):
        _close(a, b, 5e-5)


def test_chunks_are_counted_once_a_shape():
    """``gdn/chunks``: heads x chunks, by direction, from the TRACE of the
    jitted kernel call: a second call of the same shape counts nothing."""
    args = _inputs(2 * C + 8, heads=4, seed=11)  # no other test's shape
    gd._fwd_call.clear_cache()
    gd._bwd_call.clear_cache()
    rec = monitor.Recorder(name="gdn", traced_hooks=False)
    monitor.attach(rec)
    try:
        grad = jax.grad(lambda *a: jnp.sum(_rule("kernel")(*a)[0]))
        grad(*args)
        grad(*args)
    finally:
        monitor.detach()
    events = [e for e in rec.records() if e.get("name") == "gdn/chunks"]
    assert sorted((e["direction"], e["value"], e["chunk"], e["heads"],
                   e["seq"]) for e in events) == [
        ("bwd", 12.0, C, 4, 3 * C), ("fwd", 12.0, C, 4, 3 * C)]


def test_what_the_call_refuses():
    q, k, v, g, beta = _inputs(64)
    with pytest.raises(ValueError, match="impl must be one of"):
        gd.gated_delta_rule(q, k, v, g, beta, impl="triton")
    with pytest.raises(ValueError, match="multiples of 128"):
        gd.gated_delta_rule(q[..., :64], k[..., :64], v, g, beta,
                            interpret=True)
    # the scan takes any head size
    o, _ = gd.gated_delta_rule(q[..., :64], k[..., :64], v, g, beta,
                               impl="reference")
    assert o.shape == v.shape
