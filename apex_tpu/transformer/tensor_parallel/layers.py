"""Tensor-parallel layers: Column/RowParallelLinear, VocabParallelEmbedding.

Reference: ``apex/transformer/tensor_parallel/layers.py`` —
``ColumnParallelLinear`` (:243, weight shard [out/tp, in], optional
``gather_output``), ``RowParallelLinear`` (:365, weight shard [out, in/tp],
``input_is_parallel``), ``VocabParallelEmbedding`` (:127, row-sharded
vocab with range masking + allreduce), partition attributes
(:37-57), and the async-allreduce-in-backward column linear (:206-234).

TPU design: modules hold the **local shard** as their parameter (sized by
``parallel_state.get_tensor_model_parallel_world_size()``, a static host
value) and communicate through the ``mappings`` collectives, so they run
under ``shard_map`` over the ``tensor`` mesh axis — and degrade to plain
dense/embedding at tp=1. The reference's async-allreduce-overlapped-
with-weight-grad trick (:221-234) needs no code here: XLA's latency-hiding
scheduler overlaps the backward ``psum`` with the weight-gradient matmul
automatically. The sequence-parallel collectives, though —
all-gather→matmul and matmul→reduce-scatter, where the dependency chain
defeats any scheduler — ARE the collective-matmul forms of
``apex_tpu/parallel/overlap.py``: ``sequence_parallel=True`` at tp > 1
means the reduce-scatter rides the ring beside its own matmul, forward
(row layer) and backward (column layer), from what the call sees (tp,
the shapes); there is no switch.

Per-partition init matches the reference's ``_initialize_affine_weight``
strategy (:59-124): the full weight is materialized deterministically from
the seed and the local slice taken, so results are identical for any tp.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.monitor import hooks as _mon
from apex_tpu.monitor import profile as _prof
from apex_tpu.parallel import overlap
from apex_tpu.transformer import parallel_state as ps
from apex_tpu.transformer.tensor_parallel import mappings
from apex_tpu.transformer.tensor_parallel.utils import divide, VocabUtility


def _count_sp_linear():
    """Each sequence-parallel linear call at ``world > 1`` counts its two
    collectives at trace time, forward's and its backward's conjugate, by
    the form they take: ``tp/sp_linear_ring`` for the reduce-scatter, which
    travels the ring beside the pieces of its own matmul
    (``overlap.matmul_reduce_scatter``), ``tp/sp_linear_blocking`` for the
    all-gather, which the device runs whole (``overlap._gathered``: a gather
    ring lost on the chip). ``benchmarks/layer_metrics/sp_ring_share.py``
    reads the two: 50 today; a tree that sends a collective the other way
    counts it there, and the share moves."""
    _mon.counter("tp/sp_linear_ring")
    _mon.counter("tp/sp_linear_blocking")


def set_tensor_model_parallel_attributes(param, is_parallel: bool, dim: int, stride: int = 1):
    """Parity shim for the reference's param attribute stamping
    (``layers.py:37-45``). JAX params are plain arrays; partition info
    lives in the module config / sharding annotations, so this is a no-op
    that returns the param (kept so ported code runs)."""
    return param


def default_tp_sharded_filter(path_names: tuple[str, ...], leaf=None) -> bool:
    """Heuristic tp-SHARDED classifier for trees built from this stack's
    layers under their conventional scope names: Column layers (qkv, fc1,
    mlm_dense, lm_head) shard kernel AND bias, Row layers (proj, fc2)
    shard the kernel only, VocabParallelEmbedding shards the table.
    Models with exact knowledge should provide their own filter (e.g.
    ``GPT.tensor_parallel_sharded_filter``); this is the fallback the
    optimizers' ``tp_sharded_filter`` option can use for quick ports."""
    del leaf
    names = [str(n).lower() for n in path_names]
    column = any(n in ("qkv", "fc1", "mlm_dense", "lm_head") for n in names)
    row = any(n in ("proj", "fc2") for n in names)
    if column:
        return True                       # kernel + bias both sharded
    if row:
        return "kernel" in names          # row bias is replicated
    return "wte" in names and "embedding" in names


def param_is_not_tensor_parallel_duplicate(path_names: tuple[str, ...],
                                           leaf=None,
                                           sharded_filter=None):
    """True when a param must be counted in cross-rank norm reductions:
    it is tp-partitioned (every rank owns a distinct shard), or it is
    replicated and this is tp rank 0 (``layers.py:47-57``). Inside
    ``shard_map`` the rank-0 term is a traced bool; outside (tp=1) it is
    statically True."""
    if (sharded_filter or default_tp_sharded_filter)(path_names, leaf):
        return True
    # python bool outside shard_map (rank is the int 0), traced inside
    return ps.get_tensor_model_parallel_rank() == 0


def _tp_rank_static():
    """Static local helper: inside shard_map we need the traced index."""
    return ps.get_tensor_model_parallel_rank()


def _sliced_init(base_init: Callable, full_shape, axis: int, axis_name: str):
    """Initialize the full weight from the seed, return the local slice.

    Mirrors ``_initialize_affine_weight_cpu`` (``layers.py:59-97``):
    deterministic master weight + per-rank slice, so tp=k and tp=1 runs
    start from the same logical weights.
    """

    def init(key, local_shape, dtype):
        full = base_init(key, tuple(full_shape), dtype)
        world = ps._axis_size(axis_name)
        if world == 1:
            return full
        size = full_shape[axis] // world
        try:
            rank = jax.lax.axis_index(axis_name)
            return jax.lax.dynamic_slice_in_dim(full, rank * size, size, axis=axis)
        except NameError:
            # outside shard_map (e.g. eval_shape/init on host): rank-0 slice
            return jax.lax.slice_in_dim(full, 0, size, axis=axis)

    return init


class ColumnParallelLinear(nn.Module):
    """Y = XW + b with W column-sharded: local W is [in, out/tp].

    Args mirror ``layers.py:243-337``: ``gather_output`` all-gathers the
    sharded output (else downstream must be row-parallel);
    ``skip_bias_add`` returns (out, bias) for fusion into a later kernel.
    ``sequence_parallel`` applies the Megatron-SP all-gather on the input
    (sequence-sharded activations, tensor-sharded weights).
    """

    input_size: int
    output_size: int
    use_bias: bool = True
    gather_output: bool = True
    skip_bias_add: bool = False
    sequence_parallel: bool = False
    sequence_dim: int = 0          # 0 = [s, b, h] (Megatron), 1 = [b, s, h]
    axis_name: str = ps.TENSOR_AXIS
    init_method: Callable = nn.initializers.lecun_normal()
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        world = ps._axis_size(self.axis_name)
        out_per = divide(self.output_size, world)
        kernel = self.param(
            "kernel",
            _sliced_init(self.init_method, (self.input_size, self.output_size), 1, self.axis_name),
            (self.input_size, out_per), self.param_dtype)
        # profile scope (monitor.profile): the per-module attribution
        # tag — metadata only, the jaxpr is byte-identical without it
        with _prof.scope(self.name or "column_linear"):
            if self.sequence_parallel and world > 1:
                # the device's all-gather -> matmul, with the conjugate
                # matmul -> reduce-scatter ring in its custom_vjp backward
                _count_sp_linear()
                y = overlap.all_gather_matmul(
                    x, kernel.astype(x.dtype), self.axis_name,
                    self.sequence_dim)
            else:
                if world > 1:
                    x = mappings.copy_to_tensor_model_parallel_region(x, self.axis_name)
                y = jnp.dot(x, kernel.astype(x.dtype),
                            preferred_element_type=jnp.float32).astype(x.dtype)
            bias = None
            if self.use_bias:
                bias = self.param(
                    "bias",
                    _sliced_init(nn.initializers.zeros, (self.output_size,), 0, self.axis_name),
                    (out_per,), self.param_dtype)
                if not self.skip_bias_add:
                    y = y + bias.astype(y.dtype)
            if self.gather_output and world > 1:
                y = mappings.gather_from_tensor_model_parallel_region(y, self.axis_name)
        if self.skip_bias_add:
            return y, bias
        return y


class RowParallelLinear(nn.Module):
    """Y = XW + b with W row-sharded: local W is [in/tp, out].

    Mirrors ``layers.py:365-477``: with ``input_is_parallel`` the input is
    already the matching column shard (from a ColumnParallelLinear with
    ``gather_output=False``); output is allreduced (or reduce-scattered
    for sequence parallel), bias added once after the reduction.
    """

    input_size: int
    output_size: int
    use_bias: bool = True
    input_is_parallel: bool = False
    skip_bias_add: bool = False
    sequence_parallel: bool = False
    sequence_dim: int = 0          # 0 = [s, b, h] (Megatron), 1 = [b, s, h]
    axis_name: str = ps.TENSOR_AXIS
    init_method: Callable = nn.initializers.lecun_normal()
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        world = ps._axis_size(self.axis_name)
        in_per = divide(self.input_size, world)
        kernel = self.param(
            "kernel",
            _sliced_init(self.init_method, (self.input_size, self.output_size), 0, self.axis_name),
            (in_per, self.output_size), self.param_dtype)
        # profile scope (monitor.profile): metadata-only attribution tag
        with _prof.scope(self.name or "row_linear"):
            if not self.input_is_parallel and world > 1:
                x = mappings.scatter_to_tensor_model_parallel_region(x, self.axis_name)
            if self.sequence_parallel and world > 1:
                # transpose pattern of the column layer: the sequence
                # reduce-scatter is ring-decomposed, each partial matmul
                # hiding the travelling accumulator's ppermute hop (the
                # sequence must split evenly over the axis, as for the
                # blocking psum_scatter). Reassociates the cross-rank sum —
                # dtype-tolerance parity with the fused psum_scatter, not
                # bitwise. Backward gathers the cotangent once.
                _count_sp_linear()
                y = overlap.matmul_reduce_scatter(
                    x, kernel.astype(x.dtype), self.axis_name,
                    self.sequence_dim)
            else:
                y = jnp.dot(x, kernel.astype(x.dtype),
                            preferred_element_type=jnp.float32).astype(x.dtype)
                if world > 1:
                    y = mappings.reduce_from_tensor_model_parallel_region(y, self.axis_name)
            bias = None
            if self.use_bias:
                bias = self.param("bias", nn.initializers.zeros,
                                  (self.output_size,), self.param_dtype)
                if not self.skip_bias_add:
                    y = y + bias.astype(y.dtype)
        if self.skip_bias_add:
            return y, bias
        return y


class VocabParallelEmbedding(nn.Module):
    """Embedding with the vocab dimension sharded across tp ranks.

    Mirrors ``layers.py:127-204``: each rank owns rows
    ``[rank*V/tp, (rank+1)*V/tp)``; out-of-range ids are masked to 0
    locally, looked up, zeroed, and the partial embeddings allreduced.
    ``attend(x)`` produces vocab-parallel logits against the (tied) table
    — the LM-head pairing used with ``vocab_parallel_cross_entropy``.
    """

    num_embeddings: int
    embedding_dim: int
    axis_name: str = ps.TENSOR_AXIS
    init_method: Callable = nn.initializers.normal(stddev=0.02)
    param_dtype: Any = jnp.float32

    def setup(self):
        world = ps._axis_size(self.axis_name)
        per = divide(self.num_embeddings, world)
        self._per = per
        self.embedding = self.param(
            "embedding",
            _sliced_init(self.init_method, (self.num_embeddings, self.embedding_dim), 0, self.axis_name),
            (per, self.embedding_dim), self.param_dtype)

    def __call__(self, ids):
        with _prof.scope(self.name or "vocab_embedding"):
            world = ps._axis_size(self.axis_name)
            table = self.embedding
            if world == 1:
                return jnp.take(table, ids, axis=0)
            rank = ps.get_tensor_model_parallel_rank()
            start = rank * self._per
            local = ids - start
            in_range = (local >= 0) & (local < self._per)
            local = jnp.where(in_range, local, 0)
            emb = jnp.take(table, local, axis=0)
            emb = jnp.where(in_range[..., None], emb, 0.0)
            return mappings.reduce_from_tensor_model_parallel_region(
                emb, self.axis_name)

    def attend(self, x):
        """Logits against the table shard: [..., h] -> [..., V/tp].

        Logits come out in the activation dtype (MXU accumulation is fp32
        internally either way): an fp32 [..., V/tp] output doubles the
        write traffic of the step's single largest tensor and forces the
        embedding-backward matmuls onto fp32 operands.
        ``vocab_parallel_cross_entropy`` does its reductions in fp32.
        """
        with _prof.scope(f"{self.name or 'vocab_embedding'}_attend"):
            return jnp.einsum("...h,vh->...v", x,
                              self.embedding.astype(x.dtype))

# O1 default-cast coverage: TP projections are matmul-class (the
# FP16_FUNCS row). The layers compute in x.dtype (kernel.astype(x.dtype)
# above), so the interceptor's input cast alone moves them to the policy
# half dtype; fp32 param storage is untouched (O1 master weights).
# VocabParallelEmbedding's __call__ takes integer ids (the cast is a
# no-op there), but its ``attend`` — the LM-head logits matmul, the
# largest matmul of a GPT step — takes float hiddens, and the
# interceptor covers attend too.
from apex_tpu.amp import lists as _amp_lists  # noqa: E402
_amp_lists.register_half_module(ColumnParallelLinear)
_amp_lists.register_half_module(RowParallelLinear)
_amp_lists.register_half_module(VocabParallelEmbedding)
