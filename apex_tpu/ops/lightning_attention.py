"""Lightning (linear) attention with a per-head scalar decay: the chunked
prefill form and the one-step decode form over a recurrent state.

A head's state is ``S_t = lam S_{t-1} + k_t^T v_t`` in ``f32[d, d]`` and its
output ``o_t = q_t S_t`` (the caller folds the ``1/sqrt(d)`` into ``q``; no
softmax, no normaliser). ``lam_h = exp(-slope_h)`` with the Lightning
Attention slopes ``slope_h = 2^(-8 (h + 1) / H)`` (:func:`decay_slopes`).

**Prefill** (:func:`lightning_prefill`) takes ``C`` tokens of which the
first ``n`` are live and the state the tokens before them left, and returns
the outputs and the state after token ``n - 1``. Over a sub-chunk of ``c``
tokens, with ``m_a = min(a + 1, live tokens of the sub-chunk)`` the count of
live tokens up to row ``a``:

    O     = ((Q K^T) * D) V + diag(lam^m) Q S_prev,  D_ab = lam^(m_a - m_b), a >= b
    S_new = lam^(m_last) S_prev + sum_b lam^(m_last - m_b) k_b^T v_b

Every exponent is >= 0, so nothing overflows however fast a head decays; a
row past ``n`` adds nothing to the state and decays nothing (its key is
zeroed by the caller's mask here, its ``m`` stands still). The intra-chunk
part is two MXU products a head under the decay mask, the inter-chunk part
one product with the state, the update one more; the state is float32 in
VMEM across a head's sub-chunks and read and written once a call, at the
sequence's batch row of the state leaf, in place.

**Decode** (:func:`lightning_decode`) is one step of the recurrence for
every batch row: read, decay, rank-1 update, write, project. It is bound by
the state's bytes (2 x ``4 d^2`` a head a row) and updates the leaf in
place; an inactive row's state is left as it is.

Both have a plain-XLA implementation (``impl="reference"``: the off-TPU path
and the parity baseline) and a Pallas kernel (``impl="kernel"``), named
``apx_lightning_prefill`` and ``apx_lightning_decode`` in a device trace.
There is no backward: these are the serving forms (the chunked scan's
backward is ROADMAP R4).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu import _compat
from apex_tpu.monitor import profile as _prof

IMPLS = ("reference", "kernel")

#: tokens a sub-chunk of the prefill kernel: two [c, c] float32 blocks
#: (scores, decay mask) and three [c, d] operands a head fit VMEM many
#: times over, and a chunk of 1,024 is 4 sequential steps a head
SUB_CHUNK = 256

#: heads a program of the decode kernel: 8 x [128, 128] float32 in and out,
#: double-buffered, is 2 MB of VMEM, and 8 heads' q, k, v are one (8, 128)
#: tile each
DECODE_HEADS = 8


def decay_slopes(num_heads: int):
    """``slope_h = 2^(-8 (h + 1) / H)``, python floats; ``lam_h =
    exp(-slope_h)``."""
    return [2.0 ** (-8.0 * (h + 1) / num_heads) for h in range(num_heads)]


def _check_impl(impl):
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")


# -- prefill -------------------------------------------------------------------

def _live_counts(n_live, r0, c):
    """``m`` [c, 1] int32: live tokens of the sub-chunk at rows ``r0 ..``
    up to and including each row, and the sub-chunk's own count."""
    n_i = jnp.clip(n_live - r0, 0, c)
    a = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    return jnp.minimum(a + 1, n_i), n_i


def _sub_chunk(q, k, v, S, log_lam, m, n_i):
    """One sub-chunk of one head. ``q, k, v`` [c, d] (pad rows' ``k``
    zeroed), ``S`` f32 [d, d], ``log_lam`` a (negative) scalar, ``m`` [c, 1]
    the live count up to each row. Returns ``(o f32 [c, d], S_new)``."""
    c = q.shape[0]
    mf = m.astype(jnp.float32)
    a = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    b = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # lam^(m_a - m_b) under the causal mask; the exponent is clamped so the
    # masked upper triangle never overflows before it is zeroed
    gap = jnp.maximum(mf - mf.reshape(1, c), 0.0)
    decay = jnp.where(a >= b, jnp.exp(log_lam * gap), 0.0)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    intra = jnp.dot((s * decay).astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    q_in = (q.astype(jnp.float32) * jnp.exp(log_lam * mf)).astype(q.dtype)
    inter = jnp.dot(q_in, S.astype(q.dtype),
                    preferred_element_type=jnp.float32)
    n_f = n_i.astype(jnp.float32)
    k_out = (k.astype(jnp.float32)
             * jnp.exp(log_lam * (n_f - mf))).astype(k.dtype)
    S_new = jnp.exp(log_lam * n_f) * S + jax.lax.dot_general(
        k_out, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return intra + inter, S_new


def _prefill_kernel(sc_ref, q_ref, k_ref, v_ref, ll_ref, s_ref, o_ref,
                    s_out, s_scr, *, c):
    i = pl.program_id(1)
    start, n_live = sc_ref[1], sc_ref[2]

    @pl.when(i == 0)
    def _():
        # a sequence's first chunk starts from nothing, whatever the row's
        # last owner left
        s_scr[...] = jnp.where(start == 0, 0.0, s_ref[0, 0])

    m, n_i = _live_counts(n_live, i * c, c)
    o, S = _sub_chunk(q_ref[0], k_ref[0], v_ref[0], s_scr[...],
                      ll_ref[pl.program_id(0)], m, n_i)
    o_ref[0] = o.astype(o_ref.dtype)
    s_scr[...] = S

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        s_out[0, 0] = S


@functools.partial(jax.jit, static_argnames=("c", "interpret"))
def _prefill_call(q, k, v, log_lam, state, scalars, *, c, interpret):
    H, C, d = q.shape
    qkv = pl.BlockSpec((1, c, d), lambda h, i, sc: (h, i, 0))
    row = pl.BlockSpec((1, 1, d, d), lambda h, i, sc: (sc[0], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(H, C // c),
        in_specs=[qkv, qkv, qkv, pl.BlockSpec(memory_space=pltpu.SMEM),
                  row],
        out_specs=[qkv, row],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
    )
    with _prof.scope("lightning_prefill"):
        return pl.pallas_call(
            functools.partial(_prefill_kernel, c=c),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((H, C, d), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            # operands: the scalars, q, k, v, the decays, the state leaf
            input_output_aliases={5: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(scalars, q, k, v, log_lam, state)


def _prefill_reference(q, k, v, log_lam, state, slot, start, n_live, c):
    H, C, d = q.shape
    S0 = jnp.where(start == 0, 0.0, state[slot])            # [H, d, d]

    def head(qh, kh, vh, ll, S):
        def step(S, xs):
            qc, kc, vc, r0 = xs
            m, n_i = _live_counts(n_live, r0, c)
            o, S = _sub_chunk(qc, kc, vc, S, ll, m, n_i)
            return S, o
        split = lambda x: x.reshape(C // c, c, d)
        S, o = jax.lax.scan(step, S, (split(qh), split(kh), split(vh),
                                      jnp.arange(0, C, c, dtype=jnp.int32)))
        return o.reshape(C, d), S

    o, S = jax.vmap(head)(q, k, v, log_lam, S0)
    return o, state.at[slot].set(S)


def lightning_prefill(q, k, v, state, slot, start, n_live, *,
                      impl: str = "reference",
                      interpret: Optional[bool] = None):
    """``C`` tokens of one sequence through every head.

    ``q, k, v``: ``[H, C, d]`` (``q`` scaled; rows past ``n_live`` may hold
    anything); ``state``: the layer's leaf ``f32[rows, H, d, d]``; ``slot``:
    the sequence's row of it; ``start``: the position of token 0 (at 0 the
    row's state is taken as zero); ``n_live``: live tokens. Returns ``(o f32
    [H, C, d], state)`` with row ``slot`` the state after token ``n_live -
    1``, the leaf updated in place under donation."""
    _check_impl(impl)
    H, C, d = q.shape
    c = math.gcd(C, SUB_CHUNK)
    live = jnp.arange(C, dtype=jnp.int32)[None, :, None] < n_live
    k = jnp.where(live, k, 0)
    log_lam = -jnp.asarray(decay_slopes(H), jnp.float32)
    if impl == "reference":
        with _prof.scope("lightning_prefill"):
            return _prefill_reference(q, k, v, log_lam, state, slot, start,
                                      n_live, c)
    if d % 128 or c % 8:
        raise ValueError(f"the kernel takes d % 128 == 0 and chunks of a "
                         f"multiple of 8 tokens, got d={d}, C={C}")
    scalars = jnp.stack([jnp.asarray(x, jnp.int32).reshape(())
                         for x in (slot, start, n_live)])
    return tuple(_prefill_call(q, k, v, log_lam, state, scalars, c=c,
                               interpret=_compat.resolve_interpret(interpret)))


# -- decode --------------------------------------------------------------------

def _decode_kernel(q_ref, k_ref, v_ref, lam_ref, s_ref, o_ref, s_out):
    hb = q_ref.shape[1]
    q_t = q_ref[0].T                    # [d, hb]: a head's q down a column
    k_t = k_ref[0].T
    v, lam = v_ref[0], lam_ref[0]       # [hb, d]: a head's v along a row
    rows = []
    for h in range(hb):
        S = s_ref[0, h] * lam[h:h + 1, :] + k_t[:, h:h + 1] * v[h:h + 1, :]
        s_out[0, h] = S
        rows.append(jnp.sum(q_t[:, h:h + 1] * S, axis=0, keepdims=True))
    o_ref[0] = jnp.concatenate(rows, axis=0)


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def _decode_call(q, k, v, lam, state, *, hb, interpret):
    B, H, d = q.shape
    vec = pl.BlockSpec((1, hb, d), lambda b, j: (b, j, 0))
    mat = pl.BlockSpec((1, hb, d, d), lambda b, j: (b, j, 0, 0))
    with _prof.scope("lightning_decode"):
        return pl.pallas_call(
            _decode_kernel,
            grid=(B, H // hb),
            in_specs=[vec, vec, vec, vec, mat],
            out_specs=[vec, mat],
            out_shape=[jax.ShapeDtypeStruct((B, H, d), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            input_output_aliases={4: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(q, k, v, lam, state)


def lightning_decode(q, k, v, state, active, *, impl: str = "reference",
                     interpret: Optional[bool] = None):
    """One token a batch row. ``q, k, v``: ``[B, H, d]`` (``q`` scaled);
    ``state``: ``f32[B, H, d, d]``; ``active``: bool ``[B]``. Returns ``(o
    f32 [B, H, d], state)``; an inactive row's state is unchanged and its
    output means nothing."""
    _check_impl(impl)
    B, H, d = q.shape
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k = jnp.where(active[:, None, None], k, 0.0)
    lam = jnp.where(active[:, None],
                    jnp.exp(-jnp.asarray(decay_slopes(H), jnp.float32))[None],
                    1.0)                                     # [B, H]
    if impl == "reference":
        with _prof.scope("lightning_decode"):
            state = state * lam[..., None, None] \
                + k[..., :, None] * v[..., None, :]
            return jnp.einsum("bhi,bhij->bhj", q, state), state
    hb = math.gcd(H, DECODE_HEADS)
    if d % 128 or hb % 8:
        raise ValueError(f"the kernel takes d % 128 == 0 and heads in "
                         f"eights, got d={d}, H={H}")
    lanes = jnp.broadcast_to(lam[..., None], (B, H, d))
    return tuple(_decode_call(q, k, v, lanes, state, hb=hb,
                              interpret=_compat.resolve_interpret(interpret)))
