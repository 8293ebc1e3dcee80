"""True multi-tensor optimizer update: ONE Pallas kernel sweeping the
flat ZeRO shard in blocked chunks (ISSUE 13 tentpole c).

``zero/update.py`` is the element MATH every tier runs; this module is
its kernel twin. The tree-map/flat-jnp form lowers to a chain of
elementwise HLO ops that XLA fuses per leaf — each tier-3 leaf still
pays its own kernel launch and the fp32 state (p, g, m, v -> p, m, v)
makes seven HBM round trips per fusion boundary. The fused form views
the whole shard as ``[rows, 128]`` fp32 and walks it in ``block_n``-
element chunks: each program reads its p/g/m/v blocks once, runs the
complete Adam(W) (or pre-trust-ratio LAMB term) update in registers,
and writes the three outputs once — the TPU analog of apex's
``multi_tensor_apply`` chunking (``csrc/multi_tensor_apply.cuh``: many
tensors, one kernel launch, one sweep).

Numerics contract: the kernel body is the SAME sequence of elementwise
fp32 ops as :func:`apex_tpu.zero.update.adam_shard_step` /
:func:`lamb_shard_term` (the scalar bias-correction denominators are
computed outside with the identical expression and passed in through
SMEM), so in the compiled step the fused update is BIT-identical to the
tree-map on every tier — asserted across tiers 1/2/3 and the elastic
dp=8→4→8 round trip in ``tests/test_fused_kernels.py``. (Compared OUT
of the step context, the final ``p - lr*upd`` axpy can differ by one
fp32 ULP: XLA's mul+add contraction choice is per-fusion-cluster, and a
bare elementwise chain and a pallas loop body are different clusters.)

Resolution: :class:`~apex_tpu.zero.optimizer.ZeroOptimizer` (and the
``DistributedFusedAdam``/``DistributedFusedLAMB`` subclasses) consult
the tuned cache for a ``multi_tensor_update`` entry at the shard's
bucket; no entry (or ``autotune="off"``) keeps the historical tree-map
path bit-for-bit. ``python -m apex_tpu.ops tune --kernel
multi_tensor_update`` sweeps the chunk size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu import _compat

_LANES = 128


def _mtu_kernel(scal_ref, p_ref, g_ref, m_ref, v_ref, o_ref, mo_ref,
                vo_ref, *, kind: str, betas, eps: float,
                weight_decay: float, adam_w_mode: bool,
                bias_correction: bool, grad_averaging: bool):
    """One ``[block_n/128, 128]`` chunk of the flat shard: the complete
    update term in one read of (p, g, m, v), one write of (out, m, v).
    The op sequence mirrors ``zero/update.py`` exactly (bit-parity
    contract, module docstring); ``scal_ref`` holds the traced scalars
    ``[lr, 1-b1^t, 1-b2^t]`` in SMEM."""
    b1, b2 = betas
    lr = scal_ref[0]
    p = p_ref[...]
    g = g_ref[...]
    m = m_ref[...]
    v = v_ref[...]
    if not adam_w_mode and weight_decay:
        g = g + weight_decay * p
    if kind == "adam":
        m = b1 * m + (1 - b1) * g
    else:
        beta3 = (1 - b1) if grad_averaging else 1.0
        m = b1 * m + beta3 * g
    v = b2 * v + (1 - b2) * g * g
    if bias_correction:
        mhat = m / scal_ref[1]
        vhat = v / scal_ref[2]
    else:
        mhat, vhat = m, v
    upd = mhat / (jnp.sqrt(vhat) + eps)
    if adam_w_mode and weight_decay:
        upd = upd + weight_decay * p
    o_ref[...] = (p - lr * upd) if kind == "adam" else upd
    mo_ref[...] = m
    vo_ref[...] = v


def fused_shard_update(p, g, m, v, step, *, kind: str, lr, betas, eps,
                       weight_decay, adam_w_mode, bias_correction,
                       grad_averaging: bool = True, block_n: int,
                       interpret=None):
    """Fused twin of ``adam_shard_step`` (``kind="adam"``: returns
    ``(new_p, new_m, new_v)``) / ``lamb_shard_term`` (``kind="lamb"``:
    returns ``(upd, new_m, new_v)`` — trust-ratio norms stay with the
    caller, whose layout knows the leaf ranges). ``p/g/m/v`` are fp32
    arrays of any shape; the sweep runs over the raveled buffer."""
    if kind not in ("adam", "lamb"):
        raise ValueError(f"kind must be 'adam' or 'lamb', got {kind!r}")
    if block_n % (8 * _LANES) != 0:
        raise ValueError(
            f"block_n must cover whole fp32 (8, {_LANES}) tiles "
            f"(a multiple of {8 * _LANES}), got {block_n}")
    shape = p.shape
    n = p.size
    lr = jnp.asarray(lr, jnp.float32)
    b1, b2 = betas
    if bias_correction:
        # the identical expressions zero/update.py evaluates inline —
        # computed ONCE per step here instead of per leaf
        sf = step.astype(jnp.float32)
        c1 = 1 - jnp.power(b1, sf)
        c2 = 1 - jnp.power(b2, sf)
    else:
        c1 = c2 = jnp.asarray(1.0, jnp.float32)
    scal = jnp.stack([lr, c1, c2]).astype(jnp.float32)

    from apex_tpu.tune.vmem import ceil_to
    n_pad = ceil_to(n, block_n)
    rows = n_pad // _LANES
    block_rows = block_n // _LANES

    def _blocked(x):
        x = x.reshape(-1)
        if n_pad != n:
            # padded slots run the update on zeros (rsqrt-free math:
            # sqrt(0)+eps is finite) and are sliced off below
            x = jnp.pad(x, (0, n_pad - n))
        return x.reshape(rows, _LANES)

    kern = functools.partial(
        _mtu_kernel, kind=kind, betas=betas, eps=eps,
        weight_decay=weight_decay, adam_w_mode=adam_w_mode,
        bias_correction=bias_correction, grad_averaging=grad_averaging)
    blk = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    # profile scope (monitor.profile): the fused sweep attributed as one
    # module beside the zero step's update phase; metadata-only
    from apex_tpu.monitor import profile as _prof
    with _prof.scope("multi_tensor_update"):
        out, mo, vo = pl.pallas_call(
            kern,
            grid=(rows // block_rows,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [blk] * 4,
            out_specs=[blk] * 3,
            out_shape=[jax.ShapeDtypeStruct((rows, _LANES),
                                            jnp.float32)] * 3,
            interpret=_compat.resolve_interpret(interpret),
        )(scal, _blocked(p), _blocked(g), _blocked(m), _blocked(v))

    def _unblocked(x):
        return x.reshape(-1)[:n].reshape(shape)

    return _unblocked(out), _unblocked(mo), _unblocked(vo)
