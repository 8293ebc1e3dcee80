"""The gated delta rule (Gated DeltaNet linear attention), chunked, with its
backward: the training form of a recurrent ``f32[d_k, d_v]`` state a head.

A head's state starts at zero and, a token ``t``, decays, is CORRECTED
towards the token's value along its key, and is read by the query::

    S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T
    o_t = S^T q_t

(``g_t <= 0`` the log-decay, ``beta_t`` the writing strength; equivalently
``S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T``). The
caller normalises and scales ``q`` and ``k``. :func:`gated_delta_reference`
is that recurrence token by token (the tests' baseline).

**In chunks** (:func:`gated_delta_rule`, ``C`` = :data:`CHUNK` tokens). With
``gam_i`` the sum of ``g`` from the chunk's first token to its ``i``-th and
``S_0`` the state the chunk meets, the corrections of a chunk solve a unit
lower-triangular system, and everything else is matrix products::

    A_ij = exp(gam_i - gam_j) (k_i . k_j)      j < i
    T    = (I + diag(beta) A)^-1 diag(beta)
    W_v  = T V          W_k = T (exp(gam) * K)
    U    = W_v - W_k S_0
    O    = (exp(gam) * Q) S_0 + ((Q K^T) * exp(gam_i - gam_j))_{j <= i} U
    S_C  = exp(gam_C) S_0 + (exp(gam_C - gam) * K)^T U

Every exponent is a difference ``gam_i - gam_j`` with ``j <= i`` (or
``gam_i`` itself), so it is ``<= 0``: nothing overflows and nothing is
divided by a decay that has vanished, however fast a head forgets.

Two parts, split where the sequence's dependence is, each a Pallas kernel
with a hand-written backward (four kernels; nothing is differentiated
through a kernel):

- *what a chunk knows alone* (:func:`_local`; ``apx_gdn_chunk_fwd`` /
  ``_bwd``): ``W_v``, ``W_k``, the decayed ``Q`` and ``K``, the masked ``Q
  K^T`` and a chunk's whole decay, :data:`CHUNKS` chunks of one head a
  program, every chunk of every head in parallel. The triangular inverse is
  exact in twelve products (the Neumann product inside 8 x 8 diagonal
  blocks, where its terms stay small, then four doublings ``X <- X - X E
  X``), in float32
  at ``highest`` precision; its ``[c, c]`` intermediates never leave VMEM.
  The backward rebuilds them from q, k, v and the two rows (``dB = -X^T dX
  X^T``) and hands back dq, dk, dv and the cotangents of the cumulated decay
  and of beta.
- *the walk over the chunks* (:func:`_scan`; ``apx_gdn_scan_fwd`` /
  ``_bwd``): :data:`HEADS` heads a program, their float32 states in VMEM
  across a head's chunks, four MXU products a head a chunk. Differentiated,
  the forward also writes the state each chunk met (float32, ``[heads,
  chunks, d_k, d_v]``), and the backward walks the same tiles in reverse
  with the state's cotangent in VMEM, nine products a head a chunk. A block
  under ``jax.checkpoint`` that keeps nothing of this call rebuilds the
  states in its backward (both forward kernels run twice a step, the states
  of one layer live at a time); nothing here is named for a policy.

``impl="reference"`` is the same forward in ``jax.numpy`` (the kernels'
per-chunk body under ``jax.vmap``, the walk a ``lax.scan``), differentiated
by JAX: the off-TPU path, and what the tests hold the two backward kernels
to, beside the recurrence token by token. Products
take their operands in the inputs' dtype and accumulate in float32 (on a
TPU a float32 product at the default precision is one bf16 pass: float32
inputs come back to float32's digits only under
``jax.default_matmul_precision("highest")``; the inverse asks for it
itself); the state is float32 between chunks in both.

Counters (a trace of the walk's jitted kernel call: one event a shape a
process, as ``flash/*``): ``gdn/chunks`` = heads x chunks, with ``direction``
(``fwd`` | ``bwd``), ``chunk``, ``heads``, ``seq``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu import _compat
from apex_tpu.monitor import hooks as _mon
from apex_tpu.monitor import profile as _prof

IMPLS = ("kernel", "reference")

#: tokens a chunk. The triangular system is C x C a head a chunk and the walk
#: is T / C sequential steps a head. The published kernels take 64; measured
#: on a v5e at 32 heads x 16,384 tokens (PERF.md section 6, PR 50), 64 | 128:
#: the rule forward 14.7 | 12.0 ms, forward + backward 36.3 | 28.5 (what a
#: chunk knows alone 13.4 + 18.1 | 10.9 + 13.6, the walk 2.3 + 4.2 | 1.3 +
#: 3.3): a [128, 128] tile is the MXU's own, and the walk has half the steps
CHUNK = 128

#: heads a program of the walk: 8 float32 [128, 128] states are 512 KB of
#: VMEM, and 8 heads' independent products fill the MXU's pipeline where one
#: head's chain of four would wait on itself
HEADS = 8

_HI = jax.lax.Precision.HIGHEST


def gated_delta_reference(q, k, v, g, beta):
    """The recurrence token by token, in float32. ``q, k`` ``[b, h, t,
    d_k]``, ``v`` ``[b, h, t, d_v]``, ``g, beta`` ``[b, h, t]``. Returns
    ``(o f32 [b, h, t, d_v], state f32 [b, h, d_k, d_v])``."""
    f32 = jnp.float32

    def head(q, k, v, g, beta):
        def step(S, xs):
            q_t, k_t, v_t, g_t, b_t = xs
            S = jnp.exp(g_t) * S
            u = b_t * (v_t - jnp.dot(k_t, S, precision=_HI))
            S = S + k_t[:, None] * u[None, :]
            return S, jnp.dot(q_t, S, precision=_HI)

        S0 = jnp.zeros((q.shape[-1], v.shape[-1]), f32)
        S, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
        return o, S

    args = [x.astype(f32) for x in (q, k, v, g, beta)]
    return jax.vmap(jax.vmap(head))(*args)


# -- what a chunk knows alone ------------------------------------------------------
#
# One chunk of one head, on ``[c, .]`` tiles: the kernels' body, and under
# ``jax.vmap`` over heads and chunks the ``jax.numpy`` implementation. A
# program of the kernel holds :data:`CHUNKS` chunks of one head in VMEM, so
# the [c, c] intermediates never reach HBM (as XLA operations each of the
# inverse's products was written out: 268 MB a product a layer at 16,384
# tokens of 32 heads).

#: chunks a program: four [128, 128] tiles an operand are one DMA of 128 KB
CHUNKS = 4

_NEG = -1e30


def _dot(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a b^T
_TN = ((0,), (0,))      # a^T b


def _dot32(a, b, dims=((1,), (0,))):
    """float32 operands, every bit of them (the triangular inverse)."""
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _col(row, eye):
    """A ``[1, c]`` row as a ``[c, 1]`` column (no transpose of a vector:
    the diagonal of its broadcast, summed along lanes)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _unit_lower_inverse(B, ri, ci):
    """``(I + B)^-1`` for a strictly lower-triangular float32 ``B`` ``[c,
    c]`` (``c`` a power of two times 8, or below 8), exact in ``2 + 2 log2(c
    / 4)`` products; ``ri``, ``ci`` its row and column indices."""
    c = B.shape[0]
    eye = (ri == ci).astype(jnp.float32)
    m = min(8, c)

    def same(m):                    # m a power of two
        shift = m.bit_length() - 1
        return jnp.right_shift(ri, shift) == jnp.right_shift(ci, shift)

    # inside the 8 x 8 diagonal blocks B^8 = 0 and the binomial growth of
    # its powers stays under 35: (I - B)(I + B^2)(I + B^4) is the inverse
    Bd = jnp.where(same(m), B, 0.0)
    B2 = _dot32(Bd, Bd)
    X = _dot32(_dot32(eye - Bd, eye + B2), eye + _dot32(B2, B2))
    # [[X1, 0], [-X2 E X1, X2]] a pair of neighbouring blocks: E maps a
    # pair's upper half to its lower half, so E X E = 0 and this is exact
    while m < c:
        E = jnp.where(same(2 * m) & jnp.logical_not(same(m)), B, 0.0)
        X = X - _dot32(_dot32(X, E), X)
        m *= 2
    return X


def _chunk_forward(q, k, gam_r, beta_r):
    """What both directions need of one chunk: the masks, the decays, the
    pair products and ``X = (I + diag(beta) A)^-1`` (float32). ``gam_r``,
    ``beta_r``: the chunk's cumulated log-decays and its writing strengths
    as float32 rows ``[1, c]``."""
    c = q.shape[0]
    ri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye, low = ri == ci, ri >= ci
    gam_c, beta_c = _col(gam_r, eye), _col(beta_r, eye)
    # exp(gam_i - gam_j) under the mask; the exponent is masked BEFORE the
    # exp, so the upper triangle's positive differences are never raised
    D = jnp.exp(jnp.where(low, gam_c - gam_r, _NEG))
    A = jnp.where(ri > ci, _dot(k, k, _NT) * D, 0.0)
    X = _unit_lower_inverse(beta_c * A, ri, ci)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1) == c - 1
    gam_last = jnp.sum(jnp.where(last, gam_r, 0.0), axis=1, keepdims=True)
    return dict(eye=eye, low=low, strict=ri > ci, last=last, beta_c=beta_c,
                D=D, A=A, X=X, qk=_dot(q, k, _NT), e_in=jnp.exp(gam_c),
                e_out=jnp.exp(gam_last - gam_c), dec=jnp.exp(gam_last))


def _chunk_operands(q, k, v, gam_r, beta_r):
    """The walk's operands of one chunk: ``(W_v, W_k, exp(gam) Q, exp(gam_C
    - gam) K, the masked decayed Q K^T`` (in ``v.dtype``)``, exp(gam_C)
    along the state's lanes f32 [1, d_v])``."""
    dt, f32 = v.dtype, jnp.float32
    f = _chunk_forward(q, k, gam_r, beta_r)
    T = (f["X"] * beta_r).astype(dt)
    k32 = k.astype(f32)
    return (_dot(T, v).astype(dt),
            _dot(T, (k32 * f["e_in"]).astype(dt)).astype(dt),
            (q.astype(f32) * f["e_in"]).astype(dt),
            (k32 * f["e_out"]).astype(dt), (f["qk"] * f["D"]).astype(dt),
            jnp.broadcast_to(f["dec"], (1, v.shape[-1])))


def _local_fwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, *out_refs, nb):
    for j in range(nb):
        ops = _chunk_operands(q_ref[0, j], k_ref[0, j], v_ref[0, j],
                              gam_ref[0, j], beta_ref[0, j])
        for ref, x in zip(out_refs, ops):
            ref[0, j] = x


def _local_bwd_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, dwv_ref,
                      dwk_ref, dqg_ref, dkd_ref, dp_ref, ddec_ref, dq_ref,
                      dk_ref, dv_ref, dgam_ref, dbeta_ref, *, nb):
    dt, f32 = v_ref.dtype, jnp.float32
    for j in range(nb):
        q, k, v = q_ref[0, j], k_ref[0, j], v_ref[0, j]
        beta_r = beta_ref[0, j]
        f = _chunk_forward(q, k, gam_ref[0, j], beta_r)
        eye, X, D, A = f["eye"], f["X"], f["D"], f["A"]
        e_in, e_out = f["e_in"], f["e_out"]
        q32, k32 = q.astype(f32), k.astype(f32)
        T = (X * beta_r).astype(dt)
        kg = (k32 * e_in).astype(dt)
        dwv, dwk = dwv_ref[0, j], dwk_ref[0, j]
        dqg, dkd = dqg_ref[0, j].astype(f32), dkd_ref[0, j].astype(f32)
        # W_v = T V, W_k = T (exp(gam) K)
        dT = _dot(dwv, v, _NT) + _dot(dwk, kg, _NT)
        dv_ref[0, j] = _dot(T, dwv, _TN).astype(dv_ref.dtype)
        dkg = _dot(T, dwk, _TN)
        # T = X diag(beta); X = (I + B)^-1; B = diag(beta) A; A = K K^T * D
        dbeta_r = jnp.sum(X * dT, axis=0, keepdims=True)
        dB = -_dot32(_dot32(X, dT * beta_r, _TN), X, _NT)
        dB = jnp.where(f["strict"], dB, 0.0)
        dbeta_c = jnp.sum(dB * A, axis=1, keepdims=True)
        dA = f["beta_c"] * dB
        # P = Q K^T * D
        dP = jnp.where(f["low"], dp_ref[0, j].astype(f32), 0.0)
        dkk, dqk = (dA * D).astype(dt), (dP * D).astype(dt)
        dq = _dot(dqk, k) + dqg * e_in
        dk = _dot(dkk, k) + _dot(dkk, k, _TN) + _dot(dqk, q, _TN) \
            + dkg * e_in + dkd * e_out
        dq_ref[0, j] = dq.astype(dq_ref.dtype)
        dk_ref[0, j] = dk.astype(dk_ref.dtype)
        # the decays: D_ij = exp(gam_i - gam_j), exp(gam), exp(gam_C - gam)
        M = dA * A + dP * (f["qk"] * D)
        out = jnp.sum(dkd * (k32 * e_out), axis=1, keepdims=True)
        dgam_c = jnp.sum(M, axis=1, keepdims=True) - out \
            + jnp.sum(dqg * (q32 * e_in) + dkg * (k32 * e_in), axis=1,
                      keepdims=True)
        dlast = jnp.sum(out, axis=0, keepdims=True) + f["dec"] * jnp.sum(
            ddec_ref[0, j], axis=1, keepdims=True)
        dgam_ref[0, j] = _row(dgam_c, eye) - jnp.sum(M, axis=0, keepdims=True) \
            + jnp.where(f["last"], dlast, 0.0)
        dbeta_ref[0, j] = dbeta_r + _row(dbeta_c, eye)


def _chunks_a_program(N: int) -> int:
    nb = CHUNKS
    while N % nb:
        nb //= 2
    return nb


def _local_specs(H, N, c, dk, dv):
    nb = _chunks_a_program(N)

    def at(*tail):
        return pl.BlockSpec((1, nb) + tail,
                            lambda h, n: (h, n) + (0,) * len(tail))
    return nb, {"k": at(c, dk), "v": at(c, dv), "p": at(c, c),
                "row": at(1, c), "dec": at(1, dv)}


def _every_chunk_alone():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _local_fwd_call(q, k, v, gam, beta, *, interpret):
    H, N, c, dk = q.shape
    dv = v.shape[-1]
    nb, s = _local_specs(H, N, c, dk, dv)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, v.dtype)
    with _prof.scope("gdn_chunk_fwd"):
        return pl.pallas_call(
            functools.partial(_local_fwd_kernel, nb=nb), grid=(H, N // nb),
            in_specs=[s["k"], s["k"], s["v"], s["row"], s["row"]],
            out_specs=[s["v"], s["k"], s["k"], s["k"], s["p"], s["dec"]],
            out_shape=[like(v), like(k), like(q), like(k),
                       jax.ShapeDtypeStruct((H, N, c, c), v.dtype),
                       jax.ShapeDtypeStruct((H, N, 1, dv), jnp.float32)],
            compiler_params=_every_chunk_alone(), interpret=interpret,
        )(q, k, v, gam, beta)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _local_bwd_call(q, k, v, gam, beta, dwv, dwk, dqg, dkd, dp, ddec, *,
                    interpret):
    H, N, c, dk = q.shape
    dv = v.shape[-1]
    nb, s = _local_specs(H, N, c, dk, dv)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    with _prof.scope("gdn_chunk_bwd"):
        return pl.pallas_call(
            functools.partial(_local_bwd_kernel, nb=nb), grid=(H, N // nb),
            in_specs=[s["k"], s["k"], s["v"], s["row"], s["row"], s["v"],
                      s["k"], s["k"], s["k"], s["p"], s["dec"]],
            out_specs=[s["k"], s["k"], s["v"], s["row"], s["row"]],
            out_shape=[like(q), like(k), like(v), like(gam), like(beta)],
            compiler_params=_every_chunk_alone(), interpret=interpret,
        )(q, k, v, gam, beta, dwv, dwk, dqg, dkd, dp, ddec)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _local(q, k, v, gam, beta, interpret):
    """``_chunk_operands`` of every chunk of every head: ``q, k, v`` ``[H,
    N, c, .]``, ``gam`` (a chunk's cumulated log-decays) and ``beta`` as rows
    f32 ``[H, N, 1, c]``; the decay comes back ``[H, N, 1, d_v]``."""
    return tuple(_local_fwd_call(q, k, v, gam, beta, interpret=interpret))


def _local_fwd(q, k, v, gam, beta, interpret):
    return _local(q, k, v, gam, beta, interpret), (q, k, v, gam, beta)


def _local_bwd(interpret, res, cts):
    cts = [ct.astype(x.dtype) for ct, x in zip(
        cts, (res[2], res[1], res[0], res[1], res[2], res[3]))]
    return tuple(_local_bwd_call(*res, *cts, interpret=interpret))


_local.defvjp(_local_fwd, _local_bwd)


# -- the walk over the chunks ------------------------------------------------------

def _walk_chunk(S, w_v, w_k, q_g, k_d, p, dec):
    """One chunk of one head: ``(o f32 [c, d_v], S_C f32)`` from the state
    ``S`` it meets (f32 ``[d_k, d_v]``); ``dec`` broadcasts against it."""
    dt = w_v.dtype
    Sb = S.astype(dt)
    u = (w_v.astype(jnp.float32) - _dot(w_k, Sb)).astype(dt)
    o = _dot(q_g, Sb) + _dot(p, u)
    return o, dec * S + _dot(k_d, u, _TN)


def _fwd_kernel(wv_ref, wk_ref, qg_ref, kd_ref, p_ref, dec_ref, o_ref,
                sN_ref, *rest, hb, keep):
    s_scr = rest[-1]
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    for h in range(hb):
        S = s_scr[h]
        if keep:
            rest[0][h, 0] = S
        o, S = _walk_chunk(S, wv_ref[h, 0], wk_ref[h, 0], qg_ref[h, 0],
                           kd_ref[h, 0], p_ref[h, 0], dec_ref[h, 0])
        o_ref[h, 0] = o.astype(o_ref.dtype)
        s_scr[h] = S

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        sN_ref[...] = s_scr[...]


def _bwd_kernel(wv_ref, wk_ref, qg_ref, kd_ref, p_ref, dec_ref, s0_ref,
                do_ref, dsN_ref, dwv_ref, dwk_ref, dqg_ref, dkd_ref, dp_ref,
                ddec_ref, g_scr, *, hb):
    @pl.when(pl.program_id(1) == 0)         # the sequence's LAST chunk
    def _():
        g_scr[...] = dsN_ref[...]

    dt = wv_ref.dtype
    for h in range(hb):
        S0, G = s0_ref[h, 0], g_scr[h]
        Sb, Gb = S0.astype(dt), G.astype(dt)
        w_k, do = wk_ref[h, 0], do_ref[h, 0]
        u = (wv_ref[h, 0].astype(jnp.float32) - _dot(w_k, Sb)).astype(dt)
        du = (_dot(p_ref[h, 0], do, _TN) + _dot(kd_ref[h, 0], Gb)).astype(dt)
        dp_ref[h, 0] = _dot(do, u, _NT).astype(dp_ref.dtype)
        dqg_ref[h, 0] = _dot(do, Sb, _NT).astype(dqg_ref.dtype)
        dkd_ref[h, 0] = _dot(u, Gb, _NT).astype(dkd_ref.dtype)
        dwv_ref[h, 0] = du
        dwk_ref[h, 0] = (-_dot(du, Sb, _NT)).astype(dwk_ref.dtype)
        ddec_ref[h, 0] = jnp.sum(S0 * G, axis=0, keepdims=True)
        g_scr[h] = _dot(qg_ref[h, 0], do, _TN) + dec_ref[h, 0] * G \
            - _dot(w_k, du, _TN)


def _heads_a_program(H: int) -> int:
    hb = HEADS
    while H % hb:
        hb //= 2
    return hb


def _count(direction, H, N, c):
    _mon.counter("gdn/chunks", H * N, direction=direction, chunk=c, heads=H,
                 seq=N * c)


def _specs(H, N, c, dk, dv, order):
    """Block specs of the walk's operands, chunk ``order(n)`` a step."""
    def at(*tail):
        return pl.BlockSpec((_heads_a_program(H), 1) + tail,
                            lambda i, n: (i, order(n)) + (0,) * len(tail))
    return {"k": at(c, dk), "v": at(c, dv), "p": at(c, c), "dec": at(1, dv),
            "state": at(dk, dv)}


@functools.partial(jax.jit, static_argnames=("keep", "interpret"))
def _fwd_call(w_v, w_k, q_g, k_d, p, dec, *, keep, interpret):
    H, N, c, dv = w_v.shape
    dk = w_k.shape[-1]
    hb = _heads_a_program(H)
    _count("fwd", H, N, c)
    s = _specs(H, N, c, dk, dv, lambda n: n)
    final = pl.BlockSpec((hb, dk, dv), lambda i, n: (i, 0, 0))
    out_specs = [s["v"], final] + ([s["state"]] if keep else [])
    out_shape = [jax.ShapeDtypeStruct((H, N, c, dv), w_v.dtype),
                 jax.ShapeDtypeStruct((H, dk, dv), jnp.float32)]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((H, N, dk, dv), jnp.float32))
    with _prof.scope("gdn_scan_fwd"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, hb=hb, keep=keep),
            grid=(H // hb, N),
            in_specs=[s["v"], s["k"], s["k"], s["k"], s["p"], s["dec"]],
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(w_v, w_k, q_g, k_d, p, dec)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd_call(w_v, w_k, q_g, k_d, p, dec, states, do, dsN, *, interpret):
    H, N, c, dv = w_v.shape
    dk = w_k.shape[-1]
    hb = _heads_a_program(H)
    _count("bwd", H, N, c)
    s = _specs(H, N, c, dk, dv, lambda n: N - 1 - n)
    final = pl.BlockSpec((hb, dk, dv), lambda i, n: (i, 0, 0))
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    with _prof.scope("gdn_scan_bwd"):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, hb=hb),
            grid=(H // hb, N),
            in_specs=[s["v"], s["k"], s["k"], s["k"], s["p"], s["dec"],
                      s["state"], s["v"], final],
            out_specs=[s["v"], s["k"], s["k"], s["k"], s["p"], s["dec"]],
            out_shape=[like(w_v), like(w_k), like(q_g), like(k_d), like(p),
                       like(dec)],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(w_v, w_k, q_g, k_d, p, dec, states, do, dsN)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(w_v, w_k, q_g, k_d, p, dec, interpret):
    """``(o [H, N, c, d_v], S f32 [H, d_k, d_v])``: the walk, as a kernel."""
    o, sN = _fwd_call(w_v, w_k, q_g, k_d, p, dec, keep=False,
                      interpret=interpret)
    return o, sN


def _scan_fwd(w_v, w_k, q_g, k_d, p, dec, interpret):
    o, sN, states = _fwd_call(w_v, w_k, q_g, k_d, p, dec, keep=True,
                              interpret=interpret)
    return (o, sN), (w_v, w_k, q_g, k_d, p, dec, states)


def _scan_bwd(interpret, res, cts):
    do, dsN = cts
    return tuple(_bwd_call(*res, do.astype(res[0].dtype),
                           dsN.astype(jnp.float32), interpret=interpret))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _scan_reference(w_v, w_k, q_g, k_d, p, dec):
    """The walk as a ``lax.scan`` over chunks, every head at once."""
    H, N, c, dv = w_v.shape

    def step(S, xs):
        o, S = jax.vmap(_walk_chunk)(S, *xs)
        return S, o.astype(w_v.dtype)

    chunks = [jnp.moveaxis(x, 1, 0) for x in (w_v, w_k, q_g, k_d, p, dec)]
    S, o = jax.lax.scan(step, jnp.zeros((H, w_k.shape[-1], dv), jnp.float32),
                        tuple(chunks))
    return jnp.moveaxis(o, 0, 1), S


def gated_delta_rule(q, k, v, g, beta, *, impl: str = "kernel",
                     interpret: Optional[bool] = None):
    """The gated delta rule over whole sequences that start from a zero
    state, differentiable to all five inputs.

    ``q, k``: ``[b, h, t, d_k]`` (normalised and scaled by the caller);
    ``v``: ``[b, h, t, d_v]``; ``g`` (``<= 0``) and ``beta``: ``[b, h, t]``,
    taken to float32. ``t`` need not be a multiple of :data:`CHUNK`: the
    tail is padded with tokens that write nothing and decay nothing.
    Returns ``(o [b, h, t, d_v] in v.dtype, the state after the last token
    f32 [b, h, d_k, d_v])``. Under ``apx:gdn_scan``."""
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    c = CHUNK
    pad = -t % c
    n = (t + pad) // c
    if impl == "kernel" and (dk % 128 or dv % 128):
        raise ValueError(f"the kernel takes head sizes that are multiples "
                         f"of 128, got d_k={dk}, d_v={dv}")

    def chunked(x, dtype=None):
        x = x.reshape((b * h, t) + x.shape[3:])
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape((b * h, n, c) + x.shape[2:]).astype(dtype or x.dtype)

    with _prof.scope("gdn_scan"):
        qkv = chunked(q, v.dtype), chunked(k, v.dtype), chunked(v)
        rows = (jnp.cumsum(chunked(g, jnp.float32), axis=-1)[:, :, None],
                chunked(beta, jnp.float32)[:, :, None])
        if impl == "kernel":
            interpret = _compat.resolve_interpret(interpret)
            o, S = _scan(*_local(*qkv, *rows, interpret), interpret)
        else:
            o, S = _scan_reference(
                *jax.vmap(jax.vmap(_chunk_operands))(*qkv, *rows))
    o = o.reshape(b, h, n * c, dv)[:, :, :t]
    return o, S.reshape(b, h, dk, dv)
