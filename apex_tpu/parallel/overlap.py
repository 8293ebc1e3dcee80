"""Explicit communication/computation overlap: chunked collective matmul
and bucketed gradient all-reduce.

Reference: apex's two flagship overlap mechanisms —

- DDP's greedy gradient bucketing with side-stream all-reduce
  (``apex/parallel/distributed.py:425-468``): gradients are packed into
  ``message_size``-byte buckets and each bucket's all-reduce is kicked
  off on a communication stream while the backward keeps producing the
  next bucket.
- Megatron's interleaved tensor-parallel collectives (the
  async-allreduce-in-backward column linear,
  ``apex/transformer/tensor_parallel/layers.py:206-234``).

Elsewhere in this package those are "ported" by *policy*: XLA's
latency-hiding scheduler is left to overlap the one fused collective
with compute. That works when the dependency structure permits it — but
the hot TP patterns are **blocking by construction**: a sequence-parallel
``ColumnParallelLinear`` cannot start its matmul until the full
``all_gather`` of the activation lands, and a sequence-parallel
``RowParallelLinear``'s ``reduce_scatter`` cannot start until the full
matmul finishes. No scheduler can overlap ops that depend on each other.

The collective-matmul literature ("Overlapping Communication with
Dependent Computation via Decomposition", Wang et al.; the Megatron-LM
sequence-parallel work — PAPERS.md) breaks the dependency by hand: ring-
decompose the collective into ``tp`` per-shard steps so that step *k*'s
partial matmul is data-independent of step *k+1*'s ``ppermute``, which
the scheduler then runs concurrently. This module implements the ring
where the chip showed it winning (the reduce-scatter) plus the bucketed
gradient-allreduce path that finally gives apex's ``message_size`` knob
real TPU semantics:

- :func:`matmul_reduce_scatter` — ``psum_scatter(dot(x, w))`` as a
  ring: per-destination-block partial matmuls overlapping the travelling
  accumulator's hops. The payload travels in two halves, opposite ways
  round the ring (a chip's links carry both directions at once).
- :func:`all_gather_matmul`   — ``dot(all_gather(x), w)`` with the
  device's own all-gather (on the chip it is several times as fast as a
  ring's hops: :func:`_gathered`), and a ``custom_vjp`` whose backward is
  the conjugate ring: the cotangent of an all-gather→matmul is exactly a
  matmul→reduce-scatter, and vice versa. ONE gather a collective: where a
  backward needs the gathered array for two products both read the same
  one, and the gather form keeps forward's gathered activation for the
  weight gradient. These two are what a sequence-parallel
  ``ColumnParallelLinear`` / ``RowParallelLinear`` runs at tp > 1
  (``tensor_parallel/layers.py``).
- :func:`bucketed_allreduce` / :func:`accumulate_gradients` — partition
  a gradient tree into ``message_size``-byte buckets, one fused ``psum``
  per bucket; in the gradient-accumulation loop each microbatch's bucket
  psums are issued data-independent of the next microbatch's compute.
  ``compress="fp8"`` (the amp O4 comm path) quantizes each bucket to
  float8_e5m2 through the shared ``amp.fp8`` codec before the psum, so
  the collective's operands — and the accounted wire bytes — are 1
  byte/element: half of bf16, a quarter of fp32.

Numerics: ``all_gather_matmul`` is *bitwise* identical to the gather-
then-matmul program (each output row block is the same full-contraction
dot). ``matmul_reduce_scatter`` and the bucketed psums reassociate the
cross-rank additions, so they match the fused forms to dtype-appropriate
tolerance only (fp32 ~1e-6, bf16 ~1e-2 relative).

Everything here takes ``axis_name`` explicitly and must run inside
``shard_map``/``pmap`` with that axis bound (same contract as
``transformer/tensor_parallel/mappings.py``). At axis size 1 every
function degrades to its local form with zero collectives.

Trace-time ``ppermute`` byte/count accounting is threaded through
``apex_tpu.monitor`` (the collective table previously only saw
psum/all_gather/psum_scatter).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.flatten_util import ravel_pytree

from apex_tpu._compat import axis_size as _axis_size
from apex_tpu.monitor import hooks as _mon

__all__ = [
    "all_gather_matmul",
    "matmul_reduce_scatter",
    "ring_all_gather",
    "ring_psum_scatter",
    "bucket_partition",
    "bucketed_allreduce",
    "accumulate_gradients",
]


# ---------------------------------------------------------------------------
# ring building blocks
# ---------------------------------------------------------------------------


def _ring_perm(tp: int, step: int = 1):
    """The ring one way: rank j sends to ``(j + step) % tp``, so after
    each hop rank i holds what rank ``i - step`` held. ``step=-1`` is
    the same ring the other way round."""
    return [(j, (j + step) % tp) for j in range(tp)]


def _dot(a, w, out_dtype):
    """The layers' matmul convention: fp32 MXU accumulation, activation
    storage dtype (``tensor_parallel/layers.py``)."""
    return jnp.dot(a, w, preferred_element_type=jnp.float32).astype(out_dtype)


def _account_ring(axis_name, chunk, hops: int):
    """Trace-time ppermute accounting: ``hops`` permutes of ``chunk``."""
    if hops > 0 and _mon.traced_enabled():
        _mon.collective("ppermute", axis_name,
                        nbytes=hops * _mon.tree_bytes(chunk), count=hops)


class _Lane(NamedTuple):
    """One travelling piece of a ring's payload: ``size`` entries from
    ``lo`` along ``dim`` (``dim`` None: the whole payload), hopping
    ``step`` ranks (+1 or -1) a hop."""
    dim: int | None
    lo: int
    size: int
    step: int

    def of(self, a):
        if self.dim is None:
            return a
        return jax.lax.slice_in_dim(a, self.lo, self.lo + self.size,
                                    axis=self.dim)


def _lanes(shape, ring_dim: int) -> tuple:
    """How a ring's payload of ``shape`` travels. A chip's links carry
    both directions at once, so the payload is cut in two halves along
    the first even dimension that is neither the ring's nor the
    contraction's (the last), and the halves go round opposite ways:
    each direction moves half the bytes a hop. A payload with no such
    dimension (2-D ``[s, h]``, a batch of 1) travels whole, one way."""
    for d, n in enumerate(shape[:-1]):
        if d != ring_dim and n >= 2 and n % 2 == 0:
            return (_Lane(d, 0, n // 2, 1), _Lane(d, n // 2, n // 2, -1))
    return (_Lane(None, 0, 0, 1),)


def _block(a, lane: _Lane, ring_dim: int, rank, s_local: int):
    """``lane``'s entries of rank ``rank``'s row block of the
    full-length ``a``."""
    return jax.lax.dynamic_slice_in_dim(
        lane.of(a), rank * s_local, s_local, axis=ring_dim)


def _gathered(x, w, axis_name, gather_dim: int):
    """``g = all_gather(x, gather_dim)`` and its product: ``(dot(g, w), g)``.

    The gather is the device's own collective, not a ring. Measured on a
    v5e 2x2 (PR 41, PERF.md section 6): XLA's all-gather moves a shard in
    58 us inside cell 4's step where a ring's three ``ppermute`` hops take
    150-210 us (a hop is 42 GB/s a direction), so the gather ring lost at
    every width the chip was shown (960, 1,280) and went. The scatter form
    is the other way round: the device's reduce-scatter holds the core for
    0.22-0.26 ms, longer than the ring's whole wire.
    """
    if _axis_size(axis_name) > 1:
        _mon.collective("all_gather", axis_name, x)
        x = jax.lax.all_gather(x, axis_name, axis=gather_dim, tiled=True)
    return _dot(x, w, x.dtype), x


def _ring_matmul_reduce_scatter(x, w, axis_name, scatter_dim: int):
    """``psum_scatter(dot(x, w), scatter_dim)`` as tp ring steps.

    A partial-sum accumulator travels each way round the ring; at step t
    rank i slices the row block destined for rank ``i - t - 1`` (``i + t
    + 1`` the other way), matmuls it, and adds it to the arriving
    accumulator. The slice+matmul for step t is independent of step
    t-1's hop, so compute hides the permute. After tp-1 hops each rank
    holds its own fully-reduced output block. The wire carries the
    activation dtype, as the blocking form's does: every hop adds in
    float32 and rounds once.
    """
    tp = _axis_size(axis_name)
    if tp == 1:
        return _dot(x, w, x.dtype)
    scatter_dim = scatter_dim % x.ndim
    s_full = x.shape[scatter_dim]
    if s_full % tp != 0:
        raise ValueError(
            f"matmul_reduce_scatter: dim {scatter_dim} of size {s_full} is "
            f"not divisible by axis '{axis_name}' size {tp}")
    for lane in _lanes(x.shape, scatter_dim):
        piece = list(lane.of(x).shape[:-1]) + [w.shape[-1]]
        piece[scatter_dim] = s_full // tp
        _account_ring(axis_name, jax.ShapeDtypeStruct(piece, x.dtype), tp - 1)
    return _scatter_ring(x, w, axis_name, scatter_dim)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _scatter_ring(x, w, axis_name, scatter_dim: int):
    """The ring of :func:`_ring_matmul_reduce_scatter`, jitted: a model's
    layers of one shape (cell 4: 36 each of four) share ONE trace and ONE
    function in the lowered module, which XLA inlines; unrolled into the
    caller they cost the step's lowering 4-5 s of every warm start."""
    tp = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = x.shape[scatter_dim] // tp
    lanes = _lanes(x.shape, scatter_dim)
    accs = [None] * len(lanes)
    for t in range(tp):
        for n, lane in enumerate(lanes):
            dst = (idx - lane.step * (t + 1)) % tp
            part = jnp.dot(_block(x, lane, scatter_dim, dst, s_local), w,
                           preferred_element_type=jnp.float32)
            if accs[n] is not None:
                part = part + jax.lax.ppermute(
                    accs[n], axis_name,
                    _ring_perm(tp, lane.step)).astype(jnp.float32)
            accs[n] = part.astype(x.dtype)
    if lanes[0].dim is None:
        return accs[0]
    return jnp.concatenate(accs, axis=lanes[0].dim)


def _weight_grad(a, b):
    """``a^T @ b`` over every dimension but the last, in fp32 (the MXU
    convention): a weight gradient as ONE matmul over the whole sequence
    (a product a ring piece leaves the MXU waiting on the fp32 sums)."""
    axes = (tuple(range(a.ndim - 1)),) * 2
    return jnp.tensordot(a, b, axes=axes, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# bare ring collectives (no fused compute): the ZeRO-3 parameter
# gather/scatter building blocks (``apex_tpu.zero``). Decomposing a
# parameter all-gather into tp-1 ppermutes makes each hop an independent
# eqn, so XLA's scheduler can run leaf A's remaining hops underneath the
# layers that only consume leaf B — the per-leaf analog of the fused
# collective-matmul rings above, for consumers that need the whole leaf
# (embedding lookups, norms, bias adds) and therefore cannot fuse the
# matmul into the ring.
# ---------------------------------------------------------------------------


def ring_all_gather(x, axis_name, gather_dim: int = 0):
    """``all_gather(x, axis=gather_dim, tiled=True)`` as tp-1 ppermute
    hops. Each arriving chunk is written straight into its origin rank's
    block of the output, so the values (and the result) are *bitwise*
    identical to the blocking all_gather — only the schedulability
    changes."""
    tp = _axis_size(axis_name)
    if tp == 1:
        return x
    gather_dim = gather_dim % x.ndim
    idx = jax.lax.axis_index(axis_name)
    s_local = x.shape[gather_dim]
    out_shape = list(x.shape)
    out_shape[gather_dim] = s_local * tp
    y = jnp.zeros(tuple(out_shape), x.dtype)
    perm = _ring_perm(tp)
    _account_ring(axis_name, x, tp - 1)
    chunk = x
    for k in range(tp):
        src = (idx - k) % tp
        y = jax.lax.dynamic_update_slice_in_dim(
            y, chunk, src * s_local, axis=gather_dim)
        if k < tp - 1:
            chunk = jax.lax.ppermute(chunk, axis_name, perm)
    return y


def ring_psum_scatter(x, axis_name, scatter_dim: int = 0):
    """``psum_scatter(x, scatter_dimension=scatter_dim, tiled=True)`` as
    a travelling partial-sum accumulator: at step t rank i slices the
    block destined for rank ``i - t - 1`` and adds it to the arriving
    accumulator; after tp-1 hops each rank holds its own fully-reduced
    block. The cross-rank additions are reassociated relative to the
    fused collective, so parity is dtype-tolerance (fp32 ~1e-6), same
    as :func:`matmul_reduce_scatter`."""
    tp = _axis_size(axis_name)
    if tp == 1:
        return x
    scatter_dim = scatter_dim % x.ndim
    s_full = x.shape[scatter_dim]
    if s_full % tp != 0:
        raise ValueError(
            f"ring_psum_scatter: dim {scatter_dim} of size {s_full} is "
            f"not divisible by axis '{axis_name}' size {tp}")
    idx = jax.lax.axis_index(axis_name)
    s_local = s_full // tp
    perm = _ring_perm(tp)
    acc = None
    for t in range(tp):
        b = (idx - t - 1) % tp
        blk = jax.lax.dynamic_slice_in_dim(
            x, b * s_local, s_local, axis=scatter_dim)
        if acc is None:
            acc = blk
        else:
            acc = jax.lax.ppermute(acc, axis_name, perm) + blk
    _account_ring(axis_name, acc, tp - 1)
    return acc


# ---------------------------------------------------------------------------
# collective matmul primitives (custom_vjp: overlapped fwd AND bwd)
# ---------------------------------------------------------------------------


def _check_operands(x, w, dim: int, what: str):
    if w.ndim != 2:
        raise ValueError(f"{what}: weight must be 2D [in, out], got {w.shape}")
    if x.shape[-1] != w.shape[0]:
        raise ValueError(
            f"{what}: contraction mismatch, x[..., {x.shape[-1]}] @ "
            f"w[{w.shape[0]}, ...]")
    if not (-x.ndim <= dim < x.ndim - 1) or (dim % x.ndim) == x.ndim - 1:
        raise ValueError(
            f"{what}: ring dim {dim} must be a non-contraction axis of "
            f"x with shape {x.shape}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def all_gather_matmul(x, w, axis_name, gather_dim: int = 0):
    """``dot(all_gather(x, axis=gather_dim, tiled=True), w)``: the
    device's all-gather, then one matmul (:func:`_gathered`).

    ``x``: the local sequence shard ``[..., s/tp at gather_dim, ..., h]``;
    ``w``: the local weight shard ``[h, n_local]``. Returns
    ``[..., s, ..., n_local]``. Bitwise-equal to the blocking form.

    Backward: ``dx`` is the conjugate :func:`matmul_reduce_scatter` of
    ``dy @ w^T`` (the ring), ``dw`` one matmul over forward's gathered
    ``x``, which is kept for it (as the blocking layer kept it).
    """
    return _agm_fwd(x, w, axis_name, gather_dim)[0]


def _agm_fwd(x, w, axis_name, gather_dim):
    _check_operands(x, w, gather_dim, "all_gather_matmul")
    y, g = _gathered(x, w, axis_name, gather_dim % x.ndim)
    return y, (g, w)


def _agm_bwd(axis_name, gather_dim, res, dy):
    g, w = res
    # d(gathered x) = dy @ w^T, and the gather's transpose re-shards while
    # summing cross-rank partials: exactly matmul→reduce-scatter.
    dx = _ring_matmul_reduce_scatter(
        dy, jnp.swapaxes(w, 0, 1).astype(dy.dtype), axis_name,
        gather_dim % g.ndim)
    # dw = gathered(x)^T @ dy in ONE matmul over forward's gathered x,
    # kept: gathering the local shard again costs cell 4 a second
    # all-gather a column layer on the links the scatter ring is using,
    # and the memory it saves (1.1 GB of 8.2) the step does not need
    return dx, _weight_grad(g, dy).astype(w.dtype)


all_gather_matmul.defvjp(_agm_fwd, _agm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def matmul_reduce_scatter(x, w, axis_name, scatter_dim: int = 0):
    """``psum_scatter(dot(x, w), scatter_dim, tiled=True)`` with the
    reduce-scatter ring-decomposed: per-destination-block partial matmuls
    overlap the travelling accumulator's hops.

    ``x``: the full-sequence activation holding this rank's contraction
    shard ``[..., s at scatter_dim, ..., h_local]``; ``w``: the local
    weight shard ``[h_local, n]``. Returns ``[..., s/tp, ..., n]``.
    Matches the fused form to dtype tolerance (the cross-rank additions
    are reassociated).

    Backward: ONE gather of the scattered cotangent (the device's
    all-gather) feeds both ``dx = g @ w^T`` and
    ``dw = x^T @ g``.
    """
    return _mrs_fwd(x, w, axis_name, scatter_dim)[0]


def _mrs_fwd(x, w, axis_name, scatter_dim):
    _check_operands(x, w, scatter_dim, "matmul_reduce_scatter")
    return _ring_matmul_reduce_scatter(x, w, axis_name, scatter_dim), (x, w)


def _mrs_bwd(axis_name, scatter_dim, res, dy):
    x, w = res
    # d(x @ w) = all_gather(dy) @ w^T and dw = x^T @ all_gather(dy): ONE
    # gather of the cotangent shard feeds both products
    dx, g = _gathered(dy, jnp.swapaxes(w, 0, 1).astype(dy.dtype), axis_name,
                      scatter_dim % x.ndim)
    return dx.astype(x.dtype), _weight_grad(x, g).astype(w.dtype)


matmul_reduce_scatter.defvjp(_mrs_fwd, _mrs_bwd)


# ---------------------------------------------------------------------------
# bucketed gradient all-reduce (apex message_size semantics, live on TPU)
# ---------------------------------------------------------------------------


def _is_float(g) -> bool:
    return jnp.issubdtype(g.dtype, jnp.floating)


def bucket_partition(leaves: Sequence, message_size: int,
                     *, allreduce_always_fp32: bool = False) -> list:
    """Greedy in-order partition of the floating leaves of a flattened
    gradient tree into buckets of ~``message_size`` bytes.

    Mirrors apex's bucketing (``apex/parallel/distributed.py:425-468``):
    leaves are appended whole (never split) in tree order and a bucket
    closes once it reaches the byte target, so a leaf may straddle the
    nominal boundary and a bucket holds at least one leaf regardless of
    its size. ``allreduce_always_fp32`` sizes bf16/fp16 leaves at the 4
    bytes they occupy on the wire after the upcast. Returns a list of
    index lists into ``leaves``; non-floating leaves appear in no bucket.
    """
    if message_size <= 0:
        raise ValueError(f"message_size must be > 0, got {message_size}")
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes = 0
    for i, g in enumerate(leaves):
        if not _is_float(g):
            continue
        itemsize = 4 if allreduce_always_fp32 else jnp.dtype(g.dtype).itemsize
        cur.append(i)
        cur_bytes += int(g.size) * itemsize
        if cur_bytes >= message_size:
            buckets.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(cur)
    return buckets


def _psum_bucket(ops: Sequence, axis_name: str) -> list:
    """ONE ``psum`` eqn for a bucket's leaves: apex's flatten ->
    all-reduce -> unflatten. ``jax.lax.psum`` of a tuple binds one eqn
    a leaf, so the leaves are raveled into one buffer first — one
    buffer per wire dtype, so no leaf's reduction changes precision
    (a gradient tree of one dtype gives one eqn a bucket)."""
    by_dtype: dict = {}
    for j, g in enumerate(ops):
        by_dtype.setdefault(jnp.dtype(g.dtype), []).append(j)
    if _mon.traced_enabled():
        _mon.collective("psum", axis_name, nbytes=_mon.tree_bytes(ops),
                        count=len(by_dtype))
    out = list(ops)
    for idx in by_dtype.values():
        flat, unravel = ravel_pytree([ops[j] for j in idx])
        for j, g in zip(idx, unravel(jax.lax.psum(flat, axis_name))):
            out[j] = g
    return out


def bucketed_allreduce(
    grads: Any,
    axis_name: str = "data",
    *,
    message_size: int = 10_000_000,
    gradient_average: bool = True,
    allreduce_always_fp32: bool = False,
    gradient_predivide_factor: float = 1.0,
    compress: str | None = None,
) -> Any:
    """``allreduce_gradients`` with apex's bucket semantics made real:
    one fused ``psum`` *per bucket* instead of one per leaf.

    Each bucket's psum is a single collective eqn over that bucket's
    leaves raveled into one buffer (one a wire dtype where a bucket
    mixes them), data-independent of every other bucket's — XLA pipelines the
    bucket collectives against each other and against whatever consumes
    the already-reduced buckets (per-bucket optimizer math, the next
    microbatch's compute in :func:`accumulate_gradients`). Scaling
    options match :func:`apex_tpu.parallel.allreduce_gradients` exactly;
    per-leaf numerics are identical to the unbucketed path (bucketing
    changes grouping, not any leaf's reduction).

    ``compress="fp8"`` — the amp O4 gradient-comm path (the ONE fp8
    codec, ``apex_tpu.amp.fp8``; ``zero.comm.quantized_all_gather
    (scaled=True)`` is the parameter-gather face of the same helpers):
    each bucket takes one cross-rank amax (a scalar ``pmax``), scales by
    ``E5M2_MAX / (amax * world)`` — the ``world`` predivide guarantees
    no partial sum of the psum can exceed the e5m2 max, so accumulation
    in the wire dtype cannot saturate — casts to float8_e5m2, psums the
    fp8 operands in ONE eqn, and rescales. Wire (and accounted) bytes
    per bucket are 1 byte/element vs 2 for bf16 / 4 for fp32; numerics
    are e5m2-lossy (2 mantissa bits — relative error ~2^-2 per leaf
    value; gradient *direction* is preserved, see docs/perf.md), so this
    is an opt-in, never a default. Incompatible with
    ``allreduce_always_fp32`` (the knobs contradict: one widens the
    wire, the other narrows it).
    """
    from apex_tpu.parallel.distributed import (_postscale_leaf,
                                               _prescale_leaf)

    if compress not in (None, "fp8"):
        raise ValueError(f"compress must be None or 'fp8', got {compress!r}")
    if compress == "fp8":
        from apex_tpu.amp import fp8 as _fp8
    if compress and allreduce_always_fp32:
        raise ValueError(
            "compress='fp8' contradicts allreduce_always_fp32=True: one "
            "narrows the wire to 1 byte/elt, the other widens it to 4")

    world = _axis_size(axis_name)
    leaves, treedef = jax.tree.flatten(grads)
    buckets = bucket_partition(leaves, message_size,
                               allreduce_always_fp32=allreduce_always_fp32)
    out = list(leaves)
    for bucket in buckets:
        ops = [_prescale_leaf(leaves[i], allreduce_always_fp32,
                              gradient_predivide_factor) for i in bucket]
        if compress == "fp8":
            # one delayed-scaling-style scale per bucket, agreed across
            # ranks (pmax of the local amaxes — a 4-byte scalar, counted
            # in the accounting so the byte comparison stays honest)
            local_amax = jnp.max(jnp.stack([_fp8.amax(g) for g in ops]))
            bucket_amax = jax.lax.pmax(local_amax, axis_name)
            if _mon.traced_enabled():
                _mon.collective("pmax", axis_name, nbytes=4, count=1)
            scale = _fp8.compute_scale(bucket_amax * world, _fp8.E5M2_MAX)
            wire = [_fp8.quantize(g, scale, _fp8.E5M2) for g in ops]
            summed = _psum_bucket(wire, axis_name)   # fp8 on the wire
            reduced = [_fp8.dequantize(q, scale, jnp.float32)
                       for q in summed]
        else:
            reduced = _psum_bucket(ops, axis_name)
        for i, g in zip(bucket, reduced):
            out[i] = _postscale_leaf(g, leaves[i].dtype, world,
                                     gradient_average,
                                     gradient_predivide_factor)
    return jax.tree.unflatten(treedef, out)


def accumulate_gradients(
    grad_fn: Callable,
    params: Any,
    microbatches: Sequence,
    *,
    axis_name: str = "data",
    message_size: int = 10_000_000,
    overlap_comm: bool = True,
    delay_allreduce: bool = False,
    gradient_average: bool = True,
    allreduce_always_fp32: bool = False,
    gradient_predivide_factor: float = 1.0,
    compress: str | None = None,
) -> Any:
    """Gradient accumulation with the reduction placed for overlap.

    ``grad_fn(params, microbatch) -> grad_tree``; the loop is unrolled
    (``len(microbatches)`` is static), grads are **summed** across
    microbatches and all-reduced over ``axis_name``:

    - ``overlap_comm=True, delay_allreduce=False`` (apex's default DDP
      regime): each microbatch's grads are bucket-psummed immediately.
      Bucket *b* of microbatch *i* is data-independent of microbatch
      *i+1*'s forward/backward, so XLA overlaps the collectives with the
      next microbatch's compute — the TPU translation of apex's
      side-stream bucket all-reduce. Same wire volume as apex's
      per-backward all-reduce; the overlap is what pays for it.
    - ``overlap_comm=True, delay_allreduce=True``: accumulate locally,
      bucket-psum once at the end (minimum wire volume; the bucket psums
      still pipeline against each other and the consumer).
    - ``overlap_comm=False``: accumulate locally and flush through the
      per-leaf :func:`apex_tpu.parallel.allreduce_gradients` — byte-
      identical to the hand-written accumulate-then-allreduce loop this
      helper replaces (asserted in tests).

    All three modes compute the same value (psum is linear; per-leaf
    tolerance only from fp reassociation in the streamed mode).
    ``compress="fp8"`` rides the bucketed paths (see
    :func:`bucketed_allreduce`; requires ``overlap_comm=True`` — the
    per-leaf fallback has no bucket to scale).
    """
    if not len(microbatches):
        raise ValueError("accumulate_gradients: need at least 1 microbatch")
    if compress and not overlap_comm:
        raise ValueError(
            "compress='fp8' requires overlap_comm=True: the fp8 codec "
            "scales per message_size bucket (bucketed_allreduce)")
    scaling = dict(gradient_average=gradient_average,
                   allreduce_always_fp32=allreduce_always_fp32,
                   gradient_predivide_factor=gradient_predivide_factor)
    acc = None
    for mb in microbatches:
        g = grad_fn(params, mb)
        if overlap_comm and not delay_allreduce:
            g = bucketed_allreduce(g, axis_name, message_size=message_size,
                                   compress=compress, **scaling)
        acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
    if overlap_comm and delay_allreduce:
        acc = bucketed_allreduce(acc, axis_name, message_size=message_size,
                                 compress=compress, **scaling)
    elif not overlap_comm:
        from apex_tpu.parallel.distributed import allreduce_gradients
        acc = allreduce_gradients(acc, axis_name, **scaling)
    return acc
