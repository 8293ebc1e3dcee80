"""Fused LayerNorm / RMSNorm with explicit custom VJP.

Reference: ``csrc/layer_norm_cuda_kernel.cu`` (warp-shuffle Welford; saves
``(mean, invvar)`` for backward — ``csrc/layer_norm_cuda.cpp:260-265``)
and the ``--fast_layer_norm`` contrib variant
(``apex/contrib/csrc/layer_norm/ln_fwd_cuda_kernel.cu``), both folded into
this one implementation per SURVEY §7.3.

Math is fp32 regardless of input dtype (matching the kernels' float
accumulators); the residuals saved for backward are ``(x, mean, invvar)``
like the reference, so the backward recomputes xhat instead of storing it.

A Pallas LN kernel pair (single-pass backward computing dx and
accumulating dgamma/dbeta over one read of (x, dy)) was built and
measured on a v5e in round 2: standalone it exactly matched the XLA
composition (~300 us per [8192, 1024] bf16 fwd+bwd), and inside a GPT
block it was a net 3% step REGRESSION — the custom call breaks XLA's
fusion of the LN with the surrounding residual adds and pays per-call
overhead. The jnp composition below therefore stays the DEFAULT: with
no block knob and no tuned cache entry, ``fused_layer_norm_affine``
traces the exact same program it always has. The Pallas pair now ships
alongside it (ISSUE 13 tentpole a), resolved the same way the flash /
LM-head kernels resolve their tiles::

    explicit block_r  >  tuned cache entry (apex_tpu.tune)  >  jnp shim

so the kernel only engages where a measurement said it wins — the
round-2 lesson ("a kernel must beat the shim on THIS shape in THIS
context") is encoded in the resolution order instead of a hard-coded
retreat. ``python -m apex_tpu.ops tune --kernel fused_layer_norm``
sweeps it; the fwd and single-pass bwd share the ``block_r`` knob (what
a train step pays). ``out_dtype`` exists so bf16 models get bf16 in ->
bf16 out with fp32 params/math and zero call-site casts.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from apex_tpu import _compat
from apex_tpu.amp.policy import dtype_transparent
from apex_tpu.tune.vmem import ceil_to as _ceil_to


def _norm_axes(x, normalized_shape):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    n_axes = len(normalized_shape)
    if tuple(x.shape[-n_axes:]) != tuple(normalized_shape):
        raise ValueError(
            f"normalized_shape {normalized_shape} does not match input tail {x.shape[-n_axes:]}")
    return tuple(range(x.ndim - n_axes, x.ndim))


def _stats(x32, axes):
    mean = jnp.mean(x32, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=axes, keepdims=True)
    return mean, var


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
@dtype_transparent('stats accumulate in fp32 at any input dtype (module docstring)')
def fused_layer_norm_affine_reference(x, weight, bias, normalized_shape,
                                      eps=1e-5, out_dtype=None):
    """The pure-XLA twin of the Pallas LN kernels (and the DEFAULT path
    — see :func:`fused_layer_norm_affine`): LayerNorm with affine
    params; output dtype follows ``weight`` dtype unless ``out_dtype``
    overrides it (this single function covers the reference's
    ``forward_affine_mixed_dtypes`` — ``csrc/layer_norm_cuda.cpp:264``:
    bf16 input with fp32 params yields fp32 out in "mixed" mode, while
    ``MixedFusedLayerNorm`` passes bf16 params to get bf16 out). Pass
    ``out_dtype`` when you want bf16 in → bf16 out with fp32 params and
    fp32 internal math without any casts at the call site."""
    y, _, _ = _ln_fwd_affine(x, weight, bias, normalized_shape, eps, out_dtype)
    return y


def _ln_fwd_affine(x, weight, bias, normalized_shape, eps, out_dtype=None):
    out_dtype = weight.dtype if out_dtype is None else out_dtype
    axes = _norm_axes(x, normalized_shape)
    x32 = x.astype(jnp.float32)
    mean, var = _stats(x32, axes)
    invvar = jax.lax.rsqrt(var + eps)
    xhat = (x32 - mean) * invvar
    y = xhat * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(out_dtype), mean, invvar


def _ln_fwd_affine_vjp(x, weight, bias, normalized_shape, eps, out_dtype):
    y, mean, invvar = _ln_fwd_affine(x, weight, bias, normalized_shape, eps,
                                     out_dtype)
    return y, (x, weight, mean, invvar)


def _ln_bwd_affine(normalized_shape, eps, out_dtype, res, dy):
    x, weight, mean, invvar = res
    axes = _norm_axes(x, normalized_shape)
    x32 = x.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    xhat = (x32 - mean) * invvar
    w32 = weight.astype(jnp.float32)
    dxhat = dy32 * w32
    n = np.prod([x.shape[a] for a in axes])
    # dx = invvar/N * (N*dxhat - sum(dxhat) - xhat * sum(dxhat*xhat))
    s1 = jnp.sum(dxhat, axis=axes, keepdims=True)
    s2 = jnp.sum(dxhat * xhat, axis=axes, keepdims=True)
    dx = (invvar / n) * (n * dxhat - s1 - xhat * s2)
    red_axes = tuple(range(x.ndim - len(axes)))
    dw = jnp.sum(dy32 * xhat, axis=red_axes)
    db = jnp.sum(dy32, axis=red_axes)
    return dx.astype(x.dtype), dw.astype(weight.dtype), db.astype(weight.dtype)


fused_layer_norm_affine_reference.defvjp(_ln_fwd_affine_vjp, _ln_bwd_affine)


# ---------------------------------------------------------------------------
# Pallas kernel pair (tentpole a): fused one-pass forward, single-pass
# backward (dx + dgamma/dbeta accumulated over ONE read of (x, dy)).
# Statistics are RECOMPUTED in the backward from the saved x — the
# reference's save-(mean, invvar) trade costs two [n, 1]-shaped HBM
# round trips plus a lane-thin layout Mosaic handles badly; recompute is
# two cheap lane reductions on a tile already resident in VMEM.
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, *, eps: float):
    x32 = x_ref[...].astype(jnp.float32)                     # [br, h]
    mean = jnp.mean(x32, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=1, keepdims=True)
    xhat = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = xhat * w_ref[...].astype(jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _ln_bwd_kernel(x_ref, w_ref, dy_ref, dx_ref, dw_ref, db_ref, *,
                   eps: float, h: int):
    """dx for this row block + dgamma/dbeta partials accumulated across
    the (sequential) row-block grid in the fp32 [1, h] output refs."""
    ri = pl.program_id(0)
    x32 = x_ref[...].astype(jnp.float32)                     # [br, h]
    dy32 = dy_ref[...].astype(jnp.float32)
    mean = jnp.mean(x32, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=1, keepdims=True)
    invvar = jax.lax.rsqrt(var + eps)
    xhat = (x32 - mean) * invvar
    w32 = w_ref[...].astype(jnp.float32)
    dxhat = dy32 * w32
    s1 = jnp.sum(dxhat, axis=1, keepdims=True)
    s2 = jnp.sum(dxhat * xhat, axis=1, keepdims=True)
    dx = (invvar / h) * (h * dxhat - s1 - xhat * s2)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    cw = jnp.sum(dy32 * xhat, axis=0, keepdims=True)         # [1, h]
    cb = jnp.sum(dy32, axis=0, keepdims=True)

    @pl.when(ri == 0)
    def _init():
        dw_ref[...] = cw
        db_ref[...] = cb

    @pl.when(ri > 0)
    def _acc():
        dw_ref[...] += cw
        db_ref[...] += cb


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ln_affine_pallas(x2d, weight, bias, eps, out_dtype, block_r,
                      interpret):
    y, _ = _ln_pallas_fwd(x2d, weight, bias, eps, out_dtype, block_r,
                          interpret)
    return y


def _ln_pallas_fwd(x2d, weight, bias, eps, out_dtype, block_r, interpret):
    n, h = x2d.shape
    y = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=(n // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, h), lambda r: (r, 0)),
            pl.BlockSpec((1, h), lambda r: (0, 0)),
            pl.BlockSpec((1, h), lambda r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, h), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((n, h), out_dtype),
        interpret=interpret,
    )(x2d, weight[None], bias[None])
    return y, (x2d, weight)


def _ln_pallas_bwd(eps, out_dtype, block_r, interpret, res, dy):
    x2d, weight = res
    n, h = x2d.shape
    dx, dw, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=eps, h=h),
        grid=(n // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, h), lambda r: (r, 0)),
            pl.BlockSpec((1, h), lambda r: (0, 0)),
            pl.BlockSpec((block_r, h), lambda r: (r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_r, h), lambda r: (r, 0)),
            # dgamma/dbeta: ONE [1, h] fp32 block revisited by every
            # grid step — the in-VMEM accumulator of the single-pass
            # backward (the pattern lm_head_ce's dE block established)
            pl.BlockSpec((1, h), lambda r: (0, 0)),
            pl.BlockSpec((1, h), lambda r: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2d.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        interpret=interpret,
    )(x2d, weight[None], dy)
    return (dx, dw[0].astype(weight.dtype), db[0].astype(weight.dtype))


_ln_affine_pallas.defvjp(_ln_pallas_fwd, _ln_pallas_bwd)


def _ln_kernel_eligible(x, normalized_shape) -> bool:
    """The kernel covers the shape the models actually use: a single
    normalized trailing axis, lane-aligned, with at least one leading
    axis. Everything else (multi-axis normalized_shape, ragged h) stays
    on the reference — same resolution contract as flash's clamp."""
    if isinstance(normalized_shape, int):
        n_axes = 1
    else:
        n_axes = len(tuple(normalized_shape))
    return (n_axes == 1 and x.ndim >= 2 and x.shape[-1] % 128 == 0
            and x.shape[-1] > 0)


@dtype_transparent('stats accumulate in fp32 at any input dtype (module docstring)')
def fused_layer_norm_affine(x, weight, bias, normalized_shape, eps=1e-5,
                            out_dtype=None, *, block_r=None,
                            interpret=None, autotune=None):
    """Affine LayerNorm, kernel-or-shim resolved (module docstring).

    ``block_r`` pins the Pallas row-block explicitly; ``autotune``
    ("off"/"cache"/"online", default ``$APEX_TPU_AUTOTUNE`` or "cache")
    governs the tuned-cache lookup when ``block_r`` is ``None``. With no
    knob and no cache entry this is bit-for-bit the jnp reference —
    callers that pass nothing trace the same program as before the
    kernel existed."""
    from apex_tpu.monitor import profile as _prof
    if block_r is None:
        from apex_tpu.tune import runtime as _tune_rt
        policy = _tune_rt.resolve_policy(autotune)
        if policy != "off" and _ln_kernel_eligible(x, normalized_shape):
            h = x.shape[-1]
            n = 1
            for d in x.shape[:-1]:
                n *= d
            cfg = _tune_rt.resolve(
                "fused_layer_norm",
                {"n": n, "h": h, "itemsize": x.dtype.itemsize},
                x.dtype.name, {}, policy=policy,
                interpret=_compat.resolve_interpret(interpret))
            if cfg is not None:
                block_r = cfg["block_r"]
    elif autotune is not None:
        from apex_tpu.tune import runtime as _tune_rt
        _tune_rt.resolve_policy(autotune)      # validate the string
    if block_r is not None:
        if not _ln_kernel_eligible(x, normalized_shape):
            raise ValueError(
                "fused_layer_norm_affine: the Pallas kernel needs a "
                "single 128-aligned trailing normalized axis; got "
                f"normalized_shape={normalized_shape} for input shape "
                f"{x.shape} (drop block_r to use the XLA reference)")
        h = x.shape[-1]
        lead = x.shape[:-1]
        n = 1
        for d in lead:
            n *= d
        out_dt = weight.dtype if out_dtype is None else out_dtype
        block_r = max(8, min(int(block_r), _ceil_to(n, 8)))
        x2d = x.reshape(n, h)
        n_pad = _ceil_to(n, block_r)
        if n_pad != n:
            # padded rows normalize garbage-free zeros (var 0 ->
            # rsqrt(eps)); sliced off below, and their dy is zero in the
            # backward so dgamma/dbeta never see them
            x2d = jnp.pad(x2d, ((0, n_pad - n), (0, 0)))
        with _prof.scope("fused_layer_norm"):
            y = _ln_affine_pallas(x2d, weight, bias, float(eps), out_dt,
                                  int(block_r),
                                  _compat.resolve_interpret(interpret))
        return y[:n].reshape(lead + (h,))
    with _prof.scope("fused_layer_norm"):
        return fused_layer_norm_affine_reference(
            x, weight, bias, normalized_shape, eps, out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
@dtype_transparent('stats accumulate in fp32 at any input dtype (module docstring)')
def fused_layer_norm(x, normalized_shape, eps=1e-5):
    """Non-affine LayerNorm (``csrc/layer_norm_cuda.cpp:260`` ``forward``)."""
    axes = _norm_axes(x, normalized_shape)
    x32 = x.astype(jnp.float32)
    mean, var = _stats(x32, axes)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def _ln_fwd(x, normalized_shape, eps):
    axes = _norm_axes(x, normalized_shape)
    x32 = x.astype(jnp.float32)
    mean, var = _stats(x32, axes)
    invvar = jax.lax.rsqrt(var + eps)
    y = (x32 - mean) * invvar
    return y.astype(x.dtype), (x, mean, invvar)


def _ln_bwd(normalized_shape, eps, res, dy):
    x, mean, invvar = res
    axes = _norm_axes(x, normalized_shape)
    x32 = x.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    xhat = (x32 - mean) * invvar
    n = np.prod([x.shape[a] for a in axes])
    s1 = jnp.sum(dy32, axis=axes, keepdims=True)
    s2 = jnp.sum(dy32 * xhat, axis=axes, keepdims=True)
    dx = (invvar / n) * (n * dy32 - s1 - xhat * s2)
    return (dx.astype(x.dtype),)


fused_layer_norm.defvjp(_ln_fwd, _ln_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
@dtype_transparent('stats accumulate in fp32 at any input dtype (module docstring)')
def fused_rms_norm_affine(x, weight, normalized_shape, eps=1e-5):
    """RMSNorm with affine weight (newer apex ``fused_rms_norm_affine``,
    ``apex/normalization/fused_layer_norm.py`` upstream API parity)."""
    y, _ = _rms_fwd_core(x, weight, normalized_shape, eps)
    return y


def _rms_fwd_core(x, weight, normalized_shape, eps):
    axes = _norm_axes(x, normalized_shape)
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=axes, keepdims=True)
    invrms = jax.lax.rsqrt(ms + eps)
    y = x32 * invrms * weight.astype(jnp.float32)
    return y.astype(weight.dtype), invrms


def _rms_fwd_vjp(x, weight, normalized_shape, eps):
    y, invrms = _rms_fwd_core(x, weight, normalized_shape, eps)
    return y, (x, weight, invrms)


def _rms_bwd(normalized_shape, eps, res, dy):
    x, weight, invrms = res
    axes = _norm_axes(x, normalized_shape)
    x32 = x.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    w32 = weight.astype(jnp.float32)
    xhat = x32 * invrms
    dxhat = dy32 * w32
    n = np.prod([x.shape[a] for a in axes])
    dx = invrms * (dxhat - xhat * (jnp.sum(dxhat * xhat, axis=axes, keepdims=True) / n))
    red_axes = tuple(range(x.ndim - len(axes)))
    dw = jnp.sum(dy32 * xhat, axis=red_axes)
    return dx.astype(x.dtype), dw.astype(weight.dtype)


fused_rms_norm_affine.defvjp(_rms_fwd_vjp, _rms_bwd)


@dtype_transparent('stats accumulate in fp32 at any input dtype (module docstring)')
def fused_rms_norm(x, normalized_shape, eps=1e-5):
    axes = _norm_axes(x, normalized_shape)
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=axes, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps)).astype(x.dtype)
