"""The ONE fused softmax cross entropy: Pallas TPU kernels + the
pure-XLA reference twin, behind a single resolved entry point.

Reference: ``apex/contrib/csrc/xentropy/xentropy_kernel.cu`` (721 LoC)
via ``apex/contrib/xentropy/softmax_xentropy.py:4-31``: one kernel
computes ``(losses, max_log_sum_exp)`` from logits+labels with label
smoothing; the backward reconstructs the softmax from the saved
logsumexp instead of storing probabilities.

TPU design (ISSUE 13 tentpole b): the kernels reuse the online-softmax
shapes of ``ops/lm_head_ce.py`` minus the matmul — the forward streams
``(vocab-block x token-block)`` logit tiles through VMEM and reduces
each to per-token partials (row max, rescaled sum-exp, predicted logit,
and the raw row sum when smoothing is on); the backward recomputes each
tile's probabilities from the saved global ``(m, lse)`` and emits the
``(softmax - target) * dloss`` gradient tile directly, so the fp32
probability matrix and the one-hot target are never materialized in HBM
(the unfused composition writes both). The reference twin
(:func:`softmax_cross_entropy_reference`) is bit-for-bit the pre-kernel
implementation — it runs off-TPU, backs interpret-mode parity tests,
and IS the default path: resolution is

    explicit (block_t, block_v)  >  tuned cache (apex_tpu.tune)  >  twin

so callers that pass nothing trace the same program as before the
kernel existed. ``python -m apex_tpu.ops tune --kernel xentropy``
sweeps it.

``apex_tpu.ops.xentropy`` and ``apex_tpu.contrib.xentropy`` are thin
re-exports over this module (the pyprof-shim precedent from PR 2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu import _compat
from apex_tpu.amp.policy import dtype_transparent
from apex_tpu.tune.vmem import ceil_to as _ceil_to

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# reference twin (bit-for-bit the pre-kernel ops/xentropy.py)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
@dtype_transparent('log-sum-exp reduces in fp32; grad emitted in logits dtype')
def softmax_cross_entropy_reference(logits, labels, smoothing=0.0,
                                    padding_idx: int | None = None):
    """Pure-XLA twin of the fused CE kernels (and the default path —
    module docstring). Per-example loss; ``logits``: [..., V];
    ``labels``: int [...]. With smoothing s:
    loss = (1-s)·nll(target) + s·mean_v(nll(v)). ``padding_idx`` rows
    get zero loss (the reference's padding handling)."""
    loss, _ = _xent_fwd(logits, labels, smoothing, padding_idx)
    return loss


def _lse(logits32):
    m = jnp.max(logits32, axis=-1, keepdims=True)
    return (m + jnp.log(jnp.sum(jnp.exp(logits32 - m), axis=-1, keepdims=True)))[..., 0]


def _xent_fwd(logits, labels, smoothing, padding_idx):
    logits32 = logits.astype(jnp.float32)
    lse = _lse(logits32)
    target_logit = jnp.take_along_axis(logits32, labels[..., None], axis=-1)[..., 0]
    nll = lse - target_logit
    if smoothing > 0.0:
        v = logits.shape[-1]
        mean_logit = jnp.mean(logits32, axis=-1)
        smooth_loss = lse - mean_logit
        loss = (1.0 - smoothing) * nll + smoothing * smooth_loss
        del v
    else:
        loss = nll
    if padding_idx is not None:
        loss = jnp.where(labels == padding_idx, 0.0, loss)
    return loss, (logits, labels, lse)


def _xent_bwd(smoothing, padding_idx, res, dloss):
    logits, labels, lse = res
    logits32 = logits.astype(jnp.float32)
    probs = jnp.exp(logits32 - lse[..., None])
    v = logits.shape[-1]
    one_hot = jax.nn.one_hot(labels, v, dtype=jnp.float32)
    if smoothing > 0.0:
        target = (1.0 - smoothing) * one_hot + smoothing / v
    else:
        target = one_hot
    g = probs - target
    if padding_idx is not None:
        g = jnp.where((labels == padding_idx)[..., None], 0.0, g)
    g = g * dloss[..., None].astype(jnp.float32)
    return g.astype(logits.dtype), None


softmax_cross_entropy_reference.defvjp(_xent_fwd, _xent_bwd)


# ---------------------------------------------------------------------------
# Pallas kernels (the lm_head_ce online-softmax shapes, minus the dot)
# ---------------------------------------------------------------------------

def _ce_fwd_kernel(lg_ref, tgt_ref, m_ref, l_ref, p_ref, *out_refs,
                   block_v: int, v_total: int, with_ssum: bool):
    """One (vocab-block, token-block) tile of online-softmax partials.

    The logit tile arrives ``[block_t, block_v]`` and is transposed
    in-VMEM to ``[block_v, block_t]`` so every per-token reduction runs
    over sublanes and lands in the ``[1, block_t]`` lanes-on-tokens
    output layout — the exact reduction body of lm_head_ce's forward,
    with the tile read from HBM instead of computed on the MXU."""
    vi = pl.program_id(0)
    s_t = jnp.transpose(lg_ref[...]).astype(jnp.float32)     # [bv, bt]
    rows = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, s_t.shape, 0)
    valid = rows < v_total
    s_m = jnp.where(valid, s_t, _NEG_INF)
    m = jnp.max(s_m, axis=0, keepdims=True)                  # [1, bt]
    l = jnp.sum(jnp.exp(s_m - m), axis=0, keepdims=True)     # [1, bt]
    hit = valid & (rows == tgt_ref[...])                     # [bv, bt]
    pred = jnp.sum(jnp.where(hit, s_t, 0.0), axis=0, keepdims=True)
    m_ref[...] = m[None]
    l_ref[...] = l[None]
    p_ref[...] = pred[None]
    if with_ssum:
        # label smoothing only: raw logit sum over the (valid) vocab
        out_refs[0][...] = jnp.sum(jnp.where(valid, s_t, 0.0), axis=0,
                                   keepdims=True)[None]


def _ce_bwd_kernel(lg_ref, tgt_ref, m_ref, l_ref, dl_ref, dlg_ref, *,
                   block_v: int, v_total: int, smoothing: float):
    """Recompute one probability tile from the saved global (m, lse)
    partials and emit the ``(softmax - target) * dloss`` gradient tile.
    ``dl_ref`` is pre-zeroed at padding rows by the wrapper, so padded
    tokens contribute exact zeros."""
    vi = pl.program_id(0)
    s_t = jnp.transpose(lg_ref[...]).astype(jnp.float32)     # [bv, bt]
    rows = vi * block_v + jax.lax.broadcasted_iota(
        jnp.int32, s_t.shape, 0)
    valid = rows < v_total
    p = jnp.exp(jnp.where(valid, s_t, _NEG_INF) - m_ref[...]) / l_ref[...]
    hit = (valid & (rows == tgt_ref[...])).astype(jnp.float32)
    if smoothing > 0.0:
        target = (1.0 - smoothing) * hit + smoothing / v_total
        target = jnp.where(valid, target, 0.0)
    else:
        target = hit
    g = (p - target) * dl_ref[...]                           # [bv, bt]
    dlg_ref[...] = jnp.transpose(g).astype(dlg_ref.dtype)


def _ce_fwd_partials(logits2d, tgt, block_t, block_v, v_total, interpret,
                     with_ssum):
    n = logits2d.shape[0]
    n_tb = n // block_t
    n_vb = logits2d.shape[1] // block_v
    kern = functools.partial(_ce_fwd_kernel, block_v=block_v,
                             v_total=v_total, with_ssum=with_ssum)
    n_out = 4 if with_ssum else 3
    outs = pl.pallas_call(
        kern,
        grid=(n_vb, n_tb),
        in_specs=[
            pl.BlockSpec((block_t, block_v), lambda v, t: (t, v)),
            pl.BlockSpec((1, block_t), lambda v, t: (0, t)),
        ],
        out_specs=[
            # [n_vb, 1, n]: same tpu block rule as lm_head_ce — the
            # (1, block_t) tile's sublane dim spans its whole array axis
            pl.BlockSpec((1, 1, block_t), lambda v, t: (v, 0, t))] * n_out,
        out_shape=[jax.ShapeDtypeStruct((n_vb, 1, n), jnp.float32)] * n_out,
        interpret=interpret,
    )(logits2d, tgt)
    m, l, pred = (a[:, 0] for a in outs[:3])
    m_g = jnp.max(m, axis=0)
    l_g = jnp.sum(l * jnp.exp(m - m_g), axis=0)
    ssum = jnp.sum(outs[3][:, 0], axis=0) if with_ssum else None
    return m_g, l_g, jnp.sum(pred, axis=0), ssum


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _fused_xent(logits2d, tgt, smoothing, v_total, block_t, block_v,
                interpret):
    loss, _ = _fused_xent_fwd(logits2d, tgt, smoothing, v_total, block_t,
                              block_v, interpret)
    return loss


def _fused_xent_fwd(logits2d, tgt, smoothing, v_total, block_t, block_v,
                    interpret):
    m_g, l_g, pred, ssum = _ce_fwd_partials(
        logits2d, tgt, block_t, block_v, v_total, interpret,
        with_ssum=smoothing > 0.0)
    nll = jnp.log(l_g) + m_g - pred
    if smoothing > 0.0:
        mean_logp = ssum / v_total - m_g - jnp.log(l_g)
        loss = (1.0 - smoothing) * nll - smoothing * mean_logp
    else:
        loss = nll
    return loss, (logits2d, tgt, m_g, l_g)


def _fused_xent_bwd(smoothing, v_total, block_t, block_v, interpret, res,
                    dloss):
    logits2d, tgt, m_g, l_g = res
    n, v_pad = logits2d.shape
    kern = functools.partial(_ce_bwd_kernel, block_v=block_v,
                             v_total=v_total, smoothing=smoothing)
    dlogits = pl.pallas_call(
        kern,
        grid=(v_pad // block_v, n // block_t),
        in_specs=[
            pl.BlockSpec((block_t, block_v), lambda v, t: (t, v)),
            pl.BlockSpec((1, block_t), lambda v, t: (0, t)),
            pl.BlockSpec((1, block_t), lambda v, t: (0, t)),
            pl.BlockSpec((1, block_t), lambda v, t: (0, t)),
            pl.BlockSpec((1, block_t), lambda v, t: (0, t)),
        ],
        out_specs=pl.BlockSpec((block_t, block_v), lambda v, t: (t, v)),
        out_shape=jax.ShapeDtypeStruct((n, v_pad), logits2d.dtype),
        interpret=interpret,
    )(logits2d, tgt, m_g[None], l_g[None],
      dloss.astype(jnp.float32)[None])
    return dlogits, None


_fused_xent.defvjp(_fused_xent_fwd, _fused_xent_bwd)


def _pick_ce_blocks(n: int, v: int, block_t, block_v, itemsize: int):
    """Fill a half-explicit pair from the coarse defaults, shrunk to the
    shared VMEM envelope (the lm_head_ce half-explicit contract)."""
    from apex_tpu.tune import vmem
    if block_t is None:
        block_t = min(256, _ceil_to(n, 8))
    if block_v is None:
        block_v = min(2048, _ceil_to(v, 128))
    while not vmem.fits("xentropy", block_t=block_t, block_v=block_v,
                        itemsize=itemsize):
        if block_v > 128:
            block_v //= 2
        elif block_t > 8:
            block_t = max(8, block_t // 2)
        else:
            break
    return int(block_t), int(block_v)


# ---------------------------------------------------------------------------
# public resolved entry
# ---------------------------------------------------------------------------

@dtype_transparent('log-sum-exp reduces in fp32; grad emitted in logits dtype')
def softmax_cross_entropy_with_smoothing(logits, labels, smoothing=0.0,
                                         padding_idx: int | None = None,
                                         *, block_t=None, block_v=None,
                                         interpret=None, autotune=None):
    """Per-example fused softmax cross entropy, kernel-or-twin resolved
    (module docstring). Same contract as the historical
    ``ops.xentropy.softmax_cross_entropy_with_smoothing``; the kernel
    knobs are additive and default to the pre-kernel program."""
    explicit = block_t is not None or block_v is not None
    v = logits.shape[-1]
    lead = logits.shape[:-1]
    n = 1
    for d in lead:
        n *= d
    if not explicit:
        from apex_tpu.tune import runtime as _tune_rt
        policy = _tune_rt.resolve_policy(autotune)
        # no lane-alignment gate on v: the kernels pad ragged vocabs and
        # mask by v_total (a gate here would strand entries tuned at the
        # shipped v=30522 BERT sweep shape — nothing could resolve them)
        if policy != "off" and logits.ndim >= 2:
            cfg = _tune_rt.resolve(
                "xentropy",
                {"n": n, "v": v, "itemsize": logits.dtype.itemsize},
                logits.dtype.name, {"smoothing": smoothing > 0.0},
                policy=policy, interpret=_compat.resolve_interpret(interpret))
            if cfg is not None:
                block_t, block_v = cfg["block_t"], cfg["block_v"]
                explicit = True
    elif autotune is not None:
        from apex_tpu.tune import runtime as _tune_rt
        _tune_rt.resolve_policy(autotune)      # validate the string
    from apex_tpu.monitor import profile as _prof
    if not explicit:
        with _prof.scope("xentropy"):
            return softmax_cross_entropy_reference(logits, labels,
                                                   smoothing, padding_idx)
    if logits.ndim < 2:
        raise ValueError(
            "fused CE kernel needs [..., V] logits with a leading axis; "
            f"got shape {logits.shape} (drop the block knobs to use the "
            "XLA reference)")
    block_t, block_v = _pick_ce_blocks(n, v, block_t, block_v,
                                       logits.dtype.itemsize)
    lg = logits.reshape(n, v)
    tgt = labels.reshape(n).astype(jnp.int32)
    n_pad = _ceil_to(n, block_t)
    if n_pad != n:
        lg = jnp.pad(lg, ((0, n_pad - n), (0, 0)))
        tgt = jnp.pad(tgt, (0, n_pad - n), constant_values=-1)
    v_pad = _ceil_to(v, block_v)
    if v_pad != v:
        # defined zeros in the padded columns; in-kernel masking by
        # v_total keeps them out of every reduction
        lg = jnp.pad(lg, ((0, 0), (0, v_pad - v)))
    with _prof.scope("xentropy"):
        loss = _fused_xent(lg, tgt[None], float(smoothing), v,
                           int(block_t), int(block_v),
                           _compat.resolve_interpret(interpret))
        loss = loss[:n].reshape(lead)
        if padding_idx is not None:
            # zero loss AND zero gradient for padding rows: the loss
            # mask's cotangent zeroes dloss before it reaches the
            # backward kernel, which multiplies every tile by it
            loss = jnp.where(labels == padding_idx, 0.0, loss)
    return loss


class SoftmaxCrossEntropyLoss:
    """Module-style wrapper mirroring
    ``apex.contrib.xentropy.SoftmaxCrossEntropyLoss``
    (``apex/contrib/xentropy/softmax_xentropy.py:4``)."""

    @staticmethod
    def apply(logits, labels, smoothing=0.0, padding_idx=0,
              half_to_float=False):
        loss = softmax_cross_entropy_with_smoothing(logits, labels,
                                                    smoothing, padding_idx)
        return loss.astype(jnp.float32) if half_to_float \
            else loss.astype(logits.dtype)
