"""Fused fp8 dequant-matmul for serve weight-streaming.

Decode is memory-bound: at batch sizes the serve engine runs, every
weight matrix is read once per token and the MXU idles on the bytes.
Storing the block linears' kernels as **e4m3 with one per-tensor amax
scale** (the ``amp/fp8.py`` codec — the same wire format the fp8-KV
pages use) halves the bytes streamed per step; this module is the
matmul that consumes them:

- :func:`fp8_dequant_matmul_reference` — the pure-XLA twin and the
  bit-for-bit DEFAULT path: dequantize the weight
  (``q.astype(f32) / scale``), contract with fp32 accumulation, cast
  out. Off-TPU (and with ``autotune="off"``) this is the whole story.
- :func:`fp8_dequant_matmul` — the resolved entry. A Pallas kernel
  tiles the contraction ``[m, K] @ [K, N]`` over ``(block_k, block_n)``
  grid steps: the e4m3 weight block is dequantized **in-VMEM** (the
  scale rides SMEM, 4 bytes total), partial products accumulate in an
  fp32 output block revisited across the ``k`` grid axis — HBM sees
  1-byte weight elements and an fp32 result, never a dequantized
  weight. Blocks resolve ``explicit > tuned cache > reference``
  (``python -m apex_tpu.ops tune --kernel fp8_matmul`` sweeps them)
  exactly like the PR 13 kernels: with no knob and no cache entry the
  call traces the reference jaxpr unchanged.
- :func:`quantize_weight` — the build-time half: per-tensor amax scale
  (``compute_scale`` against the e4m3 max with optional margin) +
  saturating e4m3 cast. ``serve.model.quantize_gpt_weights`` applies it
  across a GPT tree once at engine construction.

Numerics: dequant-then-matmul in fp32 is exact in the scale (a single
f32 divide per element) — the only loss is the e4m3 round-trip of the
weights (~2% per element, the fp8-KV measurement), characterized
teacher-forced in tests/test_serve_spec.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu import _compat
from apex_tpu.amp import fp8
from apex_tpu.amp.policy import dtype_transparent
from apex_tpu.tune.vmem import ceil_to as _ceil_to


@dtype_transparent('fp8 codec op: e4m3 storage dtype is the contract, '
                   'not an autocast choice')
def quantize_weight(w, *, margin: float = 0.0):
    """One weight matrix -> ``(q e4m3, scale f32 scalar)`` through the
    ``amp.fp8`` codec: per-tensor amax scale with ``margin`` powers of
    two of headroom, saturating e4m3 cast (e4m3fn has no inf — the clip
    is correctness). Runs eagerly at engine build; the scale is what
    :func:`fp8_dequant_matmul` divides back out."""
    scale = fp8.compute_scale(fp8.amax(w), fp8.E4M3_MAX, margin)
    return fp8.quantize(w, scale, fp8.E4M3), scale


@dtype_transparent('operands are fixed-dtype (e4m3 weight, f32 scale); '
                   'accumulates in fp32, output follows x.dtype')
def fp8_dequant_matmul_reference(x, q, scale, out_dtype=None):
    """The pure-XLA twin (and default path): dequantize the e4m3 weight
    to f32, contract with fp32 accumulation, cast to ``out_dtype``
    (default ``x.dtype``). ``x``: [..., k] any float dtype; ``q``:
    [k, n] e4m3; ``scale``: f32 scalar."""
    out_dtype = jnp.dtype(x.dtype if out_dtype is None else out_dtype)
    w = fp8.dequantize(q, scale, jnp.float32)
    y = jnp.dot(x.astype(jnp.float32), w,
                preferred_element_type=jnp.float32)
    return y.astype(out_dtype)


def _fp8_mm_kernel(s_ref, x_ref, q_ref, y_ref):
    """One ``[m8, block_k] @ [block_k, block_n]`` partial product: the
    e4m3 block dequantizes in-VMEM against the SMEM scale, accumulates
    into the fp32 output block revisited across the k grid axis."""
    ki = pl.program_id(1)
    x32 = x_ref[...].astype(jnp.float32)
    w32 = q_ref[...].astype(jnp.float32) / s_ref[0]
    part = jax.lax.dot_general(
        x32, w32, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == 0)
    def _init():
        y_ref[...] = part

    @pl.when(ki > 0)
    def _acc():
        y_ref[...] += part


def _fp8_mm_eligible(x, q) -> bool:
    """The kernel covers the serve linears: a 2D+ activation against a
    lane-aligned 2D e4m3 weight. Ragged extents stay on the reference —
    the layer_norm resolution contract."""
    return (q.ndim == 2 and x.ndim >= 2 and x.shape[-1] == q.shape[0]
            and q.shape[0] % 128 == 0 and q.shape[1] % 128 == 0)


def _fp8_mm_pallas(x2d, q, scale, out_dtype, block_k, block_n, interpret):
    m, K = x2d.shape
    N = q.shape[1]
    # bf16 sublane tiling wants 16-row x blocks; fp32 is happy at 16 too
    m8 = _ceil_to(max(m, 1), 16)
    k_pad = _ceil_to(K, block_k)
    n_pad = _ceil_to(N, block_n)
    if m8 != m:
        x2d = jnp.pad(x2d, ((0, m8 - m), (0, 0)))
    if k_pad != K:
        # zero rows of w against zero cols of x contribute exact zeros
        x2d = jnp.pad(x2d, ((0, 0), (0, k_pad - K)))
        q = jnp.pad(q, ((0, k_pad - K), (0, 0)))
    if n_pad != N:
        q = jnp.pad(q, ((0, 0), (0, n_pad - N)))
    y = pl.pallas_call(
        _fp8_mm_kernel,
        grid=(n_pad // block_n, k_pad // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((m8, block_k), lambda j, ki: (0, ki)),
            pl.BlockSpec((block_k, block_n), lambda j, ki: (ki, j)),
        ],
        out_specs=pl.BlockSpec((m8, block_n), lambda j, ki: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m8, n_pad), jnp.float32),
        interpret=interpret,
    )(scale.reshape(1).astype(jnp.float32), x2d, q)
    return y[:m, :N].astype(out_dtype)


@dtype_transparent('operands are fixed-dtype (e4m3 weight, f32 scale); '
                   'accumulates in fp32, output follows x.dtype')
def fp8_dequant_matmul(x, q, scale, out_dtype=None, *,
                       block_k: Optional[int] = None,
                       block_n: Optional[int] = None,
                       interpret: Optional[bool] = None,
                       autotune: Optional[str] = None):
    """``x @ dequantize(q, scale)``, kernel-or-reference resolved
    (module docstring).

    ``block_k``/``block_n`` pin the Pallas tiles explicitly (both or
    neither); ``autotune`` ("off"/"cache"/"online", default
    ``$APEX_TPU_AUTOTUNE`` or "cache") governs the tuned-cache lookup
    when the blocks are ``None``. With no knob and no cache entry this
    is bit-for-bit :func:`fp8_dequant_matmul_reference` — callers that
    pass nothing trace the same program the reference always traced."""
    if jnp.dtype(q.dtype) != jnp.dtype(fp8.E4M3):
        raise ValueError(
            f"fp8_dequant_matmul: weight must be e4m3, got {q.dtype}")
    if x.shape[-1] != q.shape[0]:
        raise ValueError(
            f"fp8_dequant_matmul: contraction mismatch, "
            f"x[..., {x.shape[-1]}] @ q[{q.shape[0]}, ...]")
    from apex_tpu.monitor import profile as _prof
    out_dt = jnp.dtype(x.dtype if out_dtype is None else out_dtype)
    if (block_k is None) != (block_n is None):
        raise ValueError("fp8_dequant_matmul: pass both block_k and "
                         "block_n, or neither")
    if block_k is None:
        from apex_tpu.tune import runtime as _tune_rt
        policy = _tune_rt.resolve_policy(autotune)
        if policy != "off" and _fp8_mm_eligible(x, q):
            m = 1
            for dim in x.shape[:-1]:
                m *= dim
            cfg = _tune_rt.resolve(
                "fp8_matmul",
                {"m": m, "k": q.shape[0], "n": q.shape[1],
                 "itemsize": x.dtype.itemsize},
                x.dtype.name, {}, policy=policy,
                interpret=_compat.resolve_interpret(interpret))
            if cfg is not None:
                block_k, block_n = cfg["block_k"], cfg["block_n"]
    elif autotune is not None:
        from apex_tpu.tune import runtime as _tune_rt
        _tune_rt.resolve_policy(autotune)      # validate the string
    if block_k is not None:
        if not _fp8_mm_eligible(x, q):
            raise ValueError(
                "fp8_dequant_matmul: the Pallas kernel needs a 2D+ "
                "activation against a 128-aligned 2D e4m3 weight; got "
                f"x {x.shape} @ q {q.shape} (drop the blocks to use "
                "the XLA reference)")
        K, N = q.shape
        block_k = max(128, min(int(block_k), _ceil_to(K, 128)))
        block_n = max(128, min(int(block_n), _ceil_to(N, 128)))
        lead = x.shape[:-1]
        m = 1
        for dim in lead:
            m *= dim
        with _prof.scope("fp8_matmul"):
            y = _fp8_mm_pallas(x.reshape(m, K), q,
                               jnp.asarray(scale, jnp.float32), out_dt,
                               block_k, block_n,
                               _compat.resolve_interpret(interpret))
        return y.reshape(lead + (N,))
    with _prof.scope("fp8_matmul"):
        return fp8_dequant_matmul_reference(x, q, scale, out_dt)
