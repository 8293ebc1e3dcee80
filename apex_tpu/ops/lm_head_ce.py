"""Fused LM-head + cross entropy: Pallas TPU kernels that never
materialize the ``[tokens, V]`` logits matrix in HBM.

Reference targets (SURVEY §2.2/§2.3):
- ``apex/contrib/csrc/xentropy/xentropy_kernel.cu`` — fused
  softmax-cross-entropy whose backward recomputes the softmax from saved
  row statistics instead of storing it;
- ``apex/transformer/tensor_parallel/cross_entropy.py:23`` — the
  vocab-parallel loss (three allreduces: max, predicted logit, sum-exp).

TPU design: both are subsumed by fusing the LM-head matmul itself into
the loss. The classic composition (``wte.attend`` then CE) writes the
step's single largest tensor — bf16 logits ``[tokens, V]`` — to HBM,
reads it for the loss reductions, and in the backward forms an equally
large ``softmax - onehot`` gradient that is written once and read twice
(for dx and dE). Here the forward streams ``(vocab-block x token-block)``
logit tiles through VMEM, reducing each tile to per-token online-softmax
partials (row max, rescaled sum-exp, predicted logit, row sum); the
tiles are dropped on the floor. The backward recomputes each tile from
``x`` and the embedding (bitwise the same dot), forms the
``softmax - target`` gradient tile in VMEM, and immediately contracts it
into ``dE`` (accumulated across token blocks in VMEM) and per-vocab-block
``dx`` partials. Peak HBM cost is O(tokens + V) instead of O(tokens*V):
at GPT-bench shape (8x1024 tokens, V=32k) this removes ~0.5 GB of
logits round trips per step, and it is what makes 100k+ vocabularies
trainable at long sequence length on a 16 GB chip.

Vocab parallelism composes exactly as in ``vocab_parallel_cross_entropy``:
the kernels run on the local vocab shard (targets pre-shifted to local
coordinates), and the same three collectives (pmax of the row max, psum
of the rescaled sum-exp, psum of the predicted logit) combine the
per-shard partials. The backward needs no extra collective: per-rank
``dx`` is the partial sum over the local vocab shard, reduced by the
model's existing pre-LM-head "f" (copy-to-tensor-region) gradient
all-reduce.

Numerics: the logit tiles are computed with bf16 operands and fp32 MXU
accumulation — bitwise the dot ``wte.attend`` performs — and every
reduction (max, sum-exp, predicted logit, gradient formation) is fp32.
``dE`` is accumulated in fp32 in VMEM (the unfused path rounds it
through bf16). ``dx`` tiles are emitted in the activation dtype, summed
across vocab blocks in fp32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu import _compat
from apex_tpu._compat import tpu_compiler_params
from apex_tpu.transformer import parallel_state as ps

from apex_tpu.amp.policy import dtype_transparent

_NEG_INF = -1e30

# Mosaic's default scoped-VMEM budget is 16 MB; the backward's resident
# set at the swept-optimal tiles (bt=512, bv=2048, h=1024) is ~24 MB
# standalone but the accounting grows when the kernel sits inside a
# lax.while/scan or remat body (loop state shares the scope): measured
# 41.84 MB at s=8192 under remat_blocks — which a 32 MB cap rejected
# (r4 regression of the long-seq-remat path, caught by the s=8192
# re-verify). v5e VMEM is 128 MB; 64 MB keeps the measured-fastest
# tiles valid in every shipping context with headroom for the
# compiler's own buffers. The constant (and the resident-set model the
# autotuner prunes with) lives in tune/vmem.py — one shared envelope.
from apex_tpu.tune.vmem import LM_HEAD_VMEM_LIMIT as _VMEM_LIMIT


def _compiler_params():
    # built at call time, never at import (APX001)
    return tpu_compiler_params(vmem_limit_bytes=_VMEM_LIMIT)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pow2_at_most(x: int) -> int:
    return 1 << (x.bit_length() - 1) if x & (x - 1) else x


def _pick_blocks(n: int, v: int, h: int, block_t: Optional[int],
                 block_v: Optional[int], itemsize: int = 2):
    """Block sizes fitting Mosaic's ~16 MB scoped-VMEM budget.

    The backward's resident set is dominated by the fp32 ``dE`` block
    (block_v*h*4) plus the double-buffered bf16 E/x blocks, the fp32
    logits tile (block_t*block_v*4) and the dx tile — ~22 MB at the
    defaults (bt=512, bv=2048, h=1024), which is why the kernels carry a
    raised ``vmem_limit_bytes``. (That budget math is promoted into
    ``apex_tpu.tune.vmem.vmem_estimate`` — shared with the autotuner's
    config pruning.) v5e sweeps at the GPT bench shape
    (n=8192, V=32k, h=1024), full-step ms: interleaved A/B gave
    (512,2048) 102.5 < (256,1024) 105.0 on the same clock; an earlier
    sweep ranked (256,1024) 97.1 < (256,512) 98.9 < (1024,512) 101.1 ~
    (512,512) 101.5 < (128,1024) 103.6 across runs (±3 ms thermal
    drift between runs — only interleaved comparisons rank reliably).
    A big vocab block halves the dx-partial count (the HBM reduce after
    the kernel); the token block trades logits-tile VMEM against x
    re-fetches.

    A HALF-explicit pair (exactly one of ``block_t``/``block_v``
    passed) used to silently inherit the other knob's default and could
    exceed the kernel's raised VMEM limit — the estimate is now checked
    and the defaulted knob shrunk to the nearest legal value (the
    explicit knob only as a last resort), with a one-time warning
    naming the legal pair. Fully-explicit pairs are the user's
    responsibility (unchanged), and the both-``None`` heuristic is
    bit-for-bit what it always was."""
    from apex_tpu.tune import vmem
    explicit_t, explicit_v = block_t is not None, block_v is not None
    if block_t is None:
        block_t = min(512, _ceil_to(n, 8))
    if block_v is None:
        cap = max(128, (8 * 1024 * 1024) // (4 * h))
        block_v = min(_pow2_at_most(cap), _ceil_to(v, 128))
    if explicit_t != explicit_v:
        est = vmem.vmem_estimate("lm_head_ce", block_t=block_t,
                                 block_v=block_v, h=h, itemsize=itemsize)
        if est > _VMEM_LIMIT:
            bt, bv = block_t, block_v
            # shrink the DEFAULTED knob first — the explicit one is the
            # user's stated intent — then the explicit one if the
            # explicit choice alone cannot fit
            while vmem.vmem_estimate(
                    "lm_head_ce", block_t=bt, block_v=bv, h=h,
                    itemsize=itemsize) > _VMEM_LIMIT:
                if explicit_t and bv > 128:
                    bv //= 2
                elif explicit_v and bt > 8:
                    bt = max(8, bt // 2)
                elif bv > 128:
                    bv //= 2
                elif bt > 8:
                    bt = max(8, bt // 2)
                else:
                    break
            bv = max(128, bv)
            from apex_tpu.utils.parity import warn_inert_once
            warn_inert_once(
                f"fused_lm_head_cross_entropy: explicit "
                f"{'block_t' if explicit_t else 'block_v'}="
                f"{block_t if explicit_t else block_v} with the default "
                f"{'block_v' if explicit_t else 'block_t'} estimates "
                f"{est / 2**20:.1f} MB resident VMEM, over the "
                f"{_VMEM_LIMIT / 2**20:.0f} MB kernel limit; using the "
                f"nearest legal pair (block_t={bt}, block_v={bv}). Pass "
                "both knobs explicitly to pin an exact tiling.",
                key="lm_head_ce.half_explicit_over_budget")
            block_t, block_v = bt, bv
    return block_t, block_v


def _fwd_kernel(x_ref, e_ref, tgt_ref, m_ref, l_ref, p_ref, *out_refs,
                block_v: int, v_local: int, upcast: bool,
                with_ssum: bool):
    """One (vocab-block, token-block) tile of online-softmax partials.

    Logit tile is computed TRANSPOSED — ``[block_v, block_t]`` — so every
    per-token reduction runs over sublanes and lands directly in the
    ``[1, block_t]`` lanes-on-tokens output layout (no in-kernel
    transposes; see the tpu layout rule about trailing unit dims)."""
    vi = pl.program_id(0)
    # upcast: interpret mode runs on CPU XLA, whose dot thunk has no
    # bf16xbf16->f32 path; on TPU bf16 operands + fp32 accumulation is
    # the MXU-native (and measured-fastest) form
    x_b = x_ref[...].astype(jnp.float32) if upcast else x_ref[...]
    e_b = e_ref[...].astype(jnp.float32) if upcast else e_ref[...]
    # s_t[vv, tt] = sum_h e[vv, h] * x[tt, h]
    s_t = jax.lax.dot_general(
        e_b, x_b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    rows = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, s_t.shape, 0)
    valid = rows < v_local
    s_m = jnp.where(valid, s_t, _NEG_INF)
    m = jnp.max(s_m, axis=0, keepdims=True)                  # [1, bt]
    l = jnp.sum(jnp.exp(s_m - m), axis=0, keepdims=True)     # [1, bt]
    hit = valid & (rows == tgt_ref[...])                     # [bv, bt]
    pred = jnp.sum(jnp.where(hit, s_t, 0.0), axis=0, keepdims=True)
    m_ref[...] = m[None]
    l_ref[...] = l[None]
    p_ref[...] = pred[None]
    if with_ssum:
        # label smoothing only: sum of the raw logit tile over the vocab
        out_refs[0][...] = jnp.sum(jnp.where(valid, s_t, 0.0), axis=0,
                                   keepdims=True)[None]


def _bwd_kernel(x_ref, e_ref, tgt_ref, m_ref, l_ref, dl_ref,
                de_ref, dxp_ref, *, block_v: int, v_local: int,
                v_total: int, label_smoothing: float, upcast: bool):
    """Recompute one logit tile, form the (softmax - target) gradient in
    VMEM, contract into dE (accumulated over the inner token-block grid
    dim) and a per-vocab-block dx partial."""
    vi = pl.program_id(0)
    ti = pl.program_id(1)
    x_b = x_ref[...].astype(jnp.float32) if upcast else x_ref[...]
    e_b = e_ref[...].astype(jnp.float32) if upcast else e_ref[...]
    s_t = jax.lax.dot_general(
        e_b, x_b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    rows = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, s_t.shape, 0)
    valid = rows < v_local
    p = jnp.exp(jnp.where(valid, s_t, _NEG_INF) - m_ref[...]) / l_ref[...]
    hit = (valid & (rows == tgt_ref[...])).astype(jnp.float32)
    if label_smoothing > 0.0:
        target = (1.0 - label_smoothing) * hit + label_smoothing / v_total
        target = jnp.where(valid, target, 0.0)
    else:
        target = hit
    g = ((p - target) * dl_ref[...]).astype(x_b.dtype)       # [bv, bt]
    # dE[v, h] += g[v, t] @ x[t, h]; fp32 accumulator resident across the
    # (consecutive) inner token-block steps
    contrib = jax.lax.dot_general(
        g, x_b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ti == 0)
    def _init():
        de_ref[...] = contrib

    @pl.when(ti > 0)
    def _acc():
        de_ref[...] += contrib

    # dx partial for this vocab block: g^T[t, v] @ e[v, h]
    dxp_ref[...] = jax.lax.dot_general(
        g, e_b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dxp_ref.dtype)[None]


def _fwd_partials(x, e, tgt_local, block_t, block_v, v_local, interpret,
                  with_ssum):
    n, h = x.shape
    n_tb = n // block_t
    n_vb = pl.cdiv(e.shape[0], block_v)
    kern = functools.partial(_fwd_kernel, block_v=block_v, v_local=v_local,
                             upcast=interpret, with_ssum=with_ssum)
    n_out = 4 if with_ssum else 3
    outs = pl.pallas_call(
        kern,
        grid=(n_vb, n_tb),
        in_specs=[
            pl.BlockSpec((block_t, h), lambda v, t: (t, 0)),
            pl.BlockSpec((block_v, h), lambda v, t: (v, 0)),
            pl.BlockSpec((1, block_t), lambda v, t: (0, t)),
        ],
        out_specs=[
            # [n_vb, 1, n]: tpu block rules need the (1, block_t) tile's
            # sublane dim to span its whole array axis
            pl.BlockSpec((1, 1, block_t), lambda v, t: (v, 0, t))] * n_out,
        out_shape=[jax.ShapeDtypeStruct((n_vb, 1, n), jnp.float32)] * n_out,
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(x, e, tgt_local)
    m, l, pred = (a[:, 0] for a in outs[:3])
    # combine the per-vocab-block online-softmax partials (tiny: [n_vb, n])
    m_loc = jnp.max(m, axis=0)
    l_loc = jnp.sum(l * jnp.exp(m - m_loc), axis=0)
    ssum_loc = jnp.sum(outs[3][:, 0], axis=0) if with_ssum else None
    return m_loc, l_loc, jnp.sum(pred, axis=0), ssum_loc


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fused_ce(x, e, tgt, label_smoothing, axis_name, block_t, block_v,
              v_local, interpret):
    loss, _ = _fused_ce_fwd(x, e, tgt, label_smoothing, axis_name,
                            block_t, block_v, v_local, interpret)
    return loss


def _fused_ce_fwd(x, e, tgt, label_smoothing, axis_name, block_t, block_v,
                  v_local, interpret):
    ec = e.astype(x.dtype)
    # the kernel is named by the innermost scope around its call:
    # ``apx_lm_head_ce_fwd`` here, ``_bwd`` in the backward rule
    from apex_tpu.monitor import profile as _prof
    with _prof.scope("lm_head_ce_fwd"):
        m_loc, l_loc, pred_loc, ssum_loc = _fwd_partials(
            x, ec, tgt, block_t, block_v, v_local, interpret,
            with_ssum=label_smoothing > 0.0)
    if axis_name is None:
        m_g, l_g, pred_g = m_loc, l_loc, pred_loc
    else:
        # the three vocab-parallel collectives (cross_entropy.py:28-69)
        m_g = ps.pmax_if_bound(m_loc, axis_name)
        l_g = ps.psum_if_bound(l_loc * jnp.exp(m_loc - m_g), axis_name)
        pred_g = ps.psum_if_bound(pred_loc, axis_name)
    loss = jnp.log(l_g) + m_g - pred_g
    if label_smoothing > 0.0:
        v_total = v_local * ps.axis_size_if_bound(axis_name)
        ssum_g = (ssum_loc if axis_name is None
                  else ps.psum_if_bound(ssum_loc, axis_name))
        mean_logp = ssum_g / v_total - m_g - jnp.log(l_g)
        loss = (1.0 - label_smoothing) * loss - label_smoothing * mean_logp
    return loss, (x, e, tgt, m_g, l_g)


def _fused_ce_bwd(label_smoothing, axis_name, block_t, block_v, v_local,
                  interpret, res, dloss):
    x, e, tgt, m_g, l_g = res
    n, h = x.shape
    ec = e.astype(x.dtype)
    v_total = v_local * ps.axis_size_if_bound(axis_name)
    n_tb = n // block_t
    n_vb = pl.cdiv(v_local, block_v)
    kern = functools.partial(
        _bwd_kernel, block_v=block_v, v_local=v_local, v_total=v_total,
        label_smoothing=label_smoothing, upcast=interpret)
    bwd = pl.pallas_call(
        kern,
        grid=(n_vb, n_tb),
        in_specs=[
            pl.BlockSpec((block_t, h), lambda v, t: (t, 0)),
            pl.BlockSpec((block_v, h), lambda v, t: (v, 0)),
            pl.BlockSpec((1, block_t), lambda v, t: (0, t)),
            pl.BlockSpec((1, block_t), lambda v, t: (0, t)),
            pl.BlockSpec((1, block_t), lambda v, t: (0, t)),
            pl.BlockSpec((1, block_t), lambda v, t: (0, t)),
        ],
        out_specs=[
            pl.BlockSpec((block_v, h), lambda v, t: (v, 0)),
            pl.BlockSpec((1, block_t, h), lambda v, t: (v, t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_vb * block_v, h), jnp.float32),
            jax.ShapeDtypeStruct((n_vb, n, h), x.dtype),
        ],
        interpret=interpret,
        compiler_params=_compiler_params(),
    )
    from apex_tpu.monitor import profile as _prof
    with _prof.scope("lm_head_ce_bwd"):
        de, dxp = bwd(x, ec, tgt, m_g[None], l_g[None],
                      dloss.astype(jnp.float32)[None])
    # e arrives padded to a block multiple (see wrapper); the pad's own
    # transpose slices the padded rows (all-zero gradients) back off
    de = de[:e.shape[0]].astype(e.dtype)
    dx = jnp.sum(dxp, axis=0, dtype=jnp.float32).astype(x.dtype)
    return dx, de, None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


@dtype_transparent('vocab-chunked logits+CE reduce in fp32 internally')
def fused_lm_head_cross_entropy(
        x, embedding, targets, label_smoothing: float = 0.0,
        axis_name: Optional[str] = None,
        block_t: Optional[int] = None, block_v: Optional[int] = None,
        interpret: Optional[bool] = None,
        autotune: Optional[str] = None):
    """Per-token cross entropy of ``x @ embedding.T`` without ever
    materializing the logits.

    Args:
      x: activations ``[..., h]`` (any leading shape; typically
        ``[b, s, h]``), in the compute dtype (bf16 on the fast path).
      embedding: LM-head / tied-embedding table ``[V_local, h]`` — the
        local vocab shard when ``axis_name`` is a bound mesh axis, the
        full table otherwise.
      targets: int32 ``[...]`` of GLOBAL vocab ids, matching ``x``'s
        leading shape.
      label_smoothing: as in ``vocab_parallel_cross_entropy``.
      axis_name: mesh axis of the vocab sharding (``None`` / unbound =
        single shard).
      block_t / block_v: token/vocab tile sizes (v5e-tuned defaults).
      interpret: force Pallas interpret mode (defaults to True off-TPU).
      autotune: block-resolution policy when both tile knobs are
        ``None`` — ``"cache"`` (default; ``$APEX_TPU_AUTOTUNE``)
        resolves from the persistent tuned-block cache
        (``python -m apex_tpu.ops tune``), ``"off"`` pins the heuristic
        defaults bit-for-bit, ``"online"`` sweeps-and-caches on first
        miss. Explicit blocks always win.

    Returns: fp32 per-token loss with ``x``'s leading shape.
    """
    lead = x.shape[:-1]
    h = x.shape[-1]
    n = 1
    for d in lead:
        n *= d
    v_local = embedding.shape[0]
    xf = x.reshape(n, h)
    tgt = targets.reshape(n).astype(jnp.int32)
    if axis_name is not None and ps.axis_size_if_bound(axis_name) > 1:
        tgt = tgt - ps._axis_rank(axis_name) * v_local
    if block_t is None and block_v is None:
        from apex_tpu.tune import runtime as _tune_rt
        policy = _tune_rt.resolve_policy(autotune)
        if policy != "off":
            cfg = _tune_rt.resolve(
                "lm_head_ce",
                {"n": n, "v": v_local, "h": h,
                 "itemsize": x.dtype.itemsize},
                x.dtype.name, {"smoothing": label_smoothing > 0.0},
                policy=policy, interpret=_compat.resolve_interpret(interpret))
            if cfg is not None:
                block_t, block_v = cfg["block_t"], cfg["block_v"]
    elif autotune is not None:
        from apex_tpu.tune import runtime as _tune_rt
        _tune_rt.resolve_policy(autotune)
    block_t, block_v = _pick_blocks(n, v_local, h, block_t, block_v,
                                    itemsize=x.dtype.itemsize)
    n_pad = _ceil_to(n, block_t)
    if n_pad != n:
        xf = jnp.pad(xf, ((0, n_pad - n), (0, 0)))
        tgt = jnp.pad(tgt, (0, n_pad - n), constant_values=-1)
    v_pad = _ceil_to(v_local, block_v)
    if v_pad != v_local:
        # defined zeros in the padded rows (in-kernel masking by v_local
        # keeps them out of every reduction; OOB reads would be garbage)
        embedding = jnp.pad(embedding, ((0, v_pad - v_local), (0, 0)))
    # profile scope (monitor.profile): the fused LM-head CE kernel (fwd
    # + custom-vjp backward) attributed as one module; metadata-only
    from apex_tpu.monitor import profile as _prof
    with _prof.scope("lm_head_ce"):
        loss = _fused_ce(xf, embedding, tgt[None], label_smoothing,
                         axis_name, block_t, block_v, v_local,
                         _compat.resolve_interpret(interpret))
    return loss[:n].reshape(lead)
