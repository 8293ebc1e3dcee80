"""TP collective mappings: the f/g conjugate autograd pairs.

Reference: ``apex/transformer/tensor_parallel/mappings.py:31-138`` — four
``torch.autograd.Function`` pairs:

- ``copy_to``:    fwd identity,   bwd all-reduce      (:77-89, "f")
- ``reduce_from``: fwd all-reduce, bwd identity       (:92-103, "g")
- ``scatter_to``:  fwd split last dim, bwd all-gather (:106-118)
- ``gather_from``: fwd all-gather last dim, bwd split (:121-133)

plus the sequence-parallel variants (scatter/gather/reduce-scatter along
the *sequence* dim) from upstream Megatron.

TPU: each pair is a ``jax.custom_vjp`` over ``lax`` collectives, usable
inside ``shard_map`` over the ``tensor`` mesh axis. Under pure GSPMD
(sharding constraints) these are implicit; this explicit layer exists for
Megatron API parity and for kernels that need manual collectives.

The sequence-parallel pairs here are *blocking*: the consumer matmul
cannot start until ``gather_from_sequence_parallel_region`` lands, and
``reduce_scatter_to_sequence_parallel_region`` cannot start until the
producer matmul finishes. When the collective is immediately adjacent to
a matmul, prefer the collective-matmul forms —
:func:`apex_tpu.parallel.overlap.all_gather_matmul` /
:func:`apex_tpu.parallel.overlap.matmul_reduce_scatter` (re-exported
below) — which decompose the collective into ppermute hops overlapped
with per-shard partial matmuls wherever that wins; a sequence-parallel
``ColumnParallelLinear`` / ``RowParallelLinear`` at tp > 1 runs them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from apex_tpu.monitor import hooks as _mon
from apex_tpu.parallel.overlap import (  # noqa: F401  (fused SP forms)
    all_gather_matmul, matmul_reduce_scatter)
from apex_tpu.transformer import parallel_state as ps
from apex_tpu._compat import axis_size as _axis_size


# -- copy_to: identity / psum ------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_tensor_model_parallel_region(x, axis_name: str = ps.TENSOR_AXIS):
    return x


def _copy_fwd(x, axis_name):
    return x, None


def _copy_bwd(axis_name, _, dy):
    _mon.collective("psum", axis_name, dy)
    return (jax.lax.psum(dy, axis_name),)


copy_to_tensor_model_parallel_region.defvjp(_copy_fwd, _copy_bwd)


# -- reduce_from: psum / identity -------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_from_tensor_model_parallel_region(x, axis_name: str = ps.TENSOR_AXIS):
    _mon.collective("psum", axis_name, x)
    return jax.lax.psum(x, axis_name)


def _reduce_fwd(x, axis_name):
    _mon.collective("psum", axis_name, x)
    return jax.lax.psum(x, axis_name), None


def _reduce_bwd(axis_name, _, dy):
    return (dy,)


reduce_from_tensor_model_parallel_region.defvjp(_reduce_fwd, _reduce_bwd)


# -- scatter_to: local split / all-gather -----------------------------------

def _local_chunk(x, axis_name, dim=-1):
    world = _axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    size = x.shape[dim] // world
    return jax.lax.dynamic_slice_in_dim(x, rank * size, size, axis=dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def scatter_to_tensor_model_parallel_region(x, axis_name: str = ps.TENSOR_AXIS, dim: int = -1):
    return _local_chunk(x, axis_name, dim)


def _scatter_fwd(x, axis_name, dim):
    return _local_chunk(x, axis_name, dim), None


def _scatter_bwd(axis_name, dim, _, dy):
    _mon.collective("all_gather", axis_name, dy)
    return (jax.lax.all_gather(dy, axis_name, axis=dim if dim >= 0 else dy.ndim + dim, tiled=True),)


scatter_to_tensor_model_parallel_region.defvjp(_scatter_fwd, _scatter_bwd)


# -- gather_from: all-gather / local split ----------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def gather_from_tensor_model_parallel_region(x, axis_name: str = ps.TENSOR_AXIS, dim: int = -1):
    _mon.collective("all_gather", axis_name, x)
    return jax.lax.all_gather(x, axis_name, axis=dim if dim >= 0 else x.ndim + dim, tiled=True)


def _gather_fwd(x, axis_name, dim):
    return gather_from_tensor_model_parallel_region(x, axis_name, dim), None


def _gather_bwd(axis_name, dim, _, dy):
    return (_local_chunk(dy, axis_name, dim),)


gather_from_tensor_model_parallel_region.defvjp(_gather_fwd, _gather_bwd)


# -- sequence-parallel variants (default dim 0 = sequence, the Megatron
#    [s, b, h] convention; pass dim=1 for batch-first [b, s, h] models) --

def scatter_to_sequence_parallel_region(x, axis_name: str = ps.TENSOR_AXIS,
                                        dim: int = 0):
    return scatter_to_tensor_model_parallel_region(x, axis_name, dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def gather_from_sequence_parallel_region(x, axis_name: str = ps.TENSOR_AXIS,
                                         dim: int = 0):
    """fwd all-gather along the sequence ``dim``; bwd REDUCE-SCATTER —
    under SP every rank's cotangent w.r.t. the gathered sequence is a
    partial sum (e.g. ``dy @ W_shard^T`` in a column-parallel backward),
    so the backward must sum across ranks while re-sharding (Megatron's
    ``_GatherFromSequenceParallelRegion`` with
    ``tensor_parallel_output_grad=True``). A plain local chunk here
    silently drops (tp-1)/tp of the gradient."""
    _mon.collective("all_gather", axis_name, x)
    return jax.lax.all_gather(x, axis_name, axis=dim, tiled=True)


def _sp_gather_fwd(x, axis_name, dim):
    _mon.collective("all_gather", axis_name, x)
    return jax.lax.all_gather(x, axis_name, axis=dim, tiled=True), None


def _sp_gather_bwd(axis_name, dim, _, dy):
    _mon.collective("psum_scatter", axis_name, dy)
    return (jax.lax.psum_scatter(dy, axis_name, scatter_dimension=dim,
                                 tiled=True),)


gather_from_sequence_parallel_region.defvjp(_sp_gather_fwd, _sp_gather_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def reduce_scatter_to_sequence_parallel_region(
        x, axis_name: str = ps.TENSOR_AXIS, dim: int = 0):
    """fwd reduce-scatter along ``dim``, bwd all-gather — the Megatron-SP
    "g" in the sequence-parallel MLP/attention sandwich."""
    _mon.collective("psum_scatter", axis_name, x)
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=dim,
                                tiled=True)


def _rs_fwd(x, axis_name, dim):
    _mon.collective("psum_scatter", axis_name, x)
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=dim,
                                tiled=True), None


def _rs_bwd(axis_name, dim, _, dy):
    _mon.collective("all_gather", axis_name, dy)
    return (jax.lax.all_gather(dy, axis_name, axis=dim, tiled=True),)


reduce_scatter_to_sequence_parallel_region.defvjp(_rs_fwd, _rs_bwd)


def allreduce_sequence_parallel_gradients(grads, is_sp_partial,
                                          axis_name: str = ps.TENSOR_AXIS):
    """psum the gradients of logically-replicated params whose grads are
    per-rank partials under sequence parallelism (layernorm scales/biases
    and post-reduce-scatter biases see only the local token shard) — the
    Megatron ``allreduce_sequence_parallel_gradients`` analog.

    ``is_sp_partial(path_tuple, leaf) -> bool`` selects the leaves; the
    path entries are plain strings (dict keys, attribute names, or
    sequence indices).
    """
    def _name(p):
        for attr in ("key", "name", "idx"):
            if hasattr(p, attr):
                return str(getattr(p, attr))
        return str(p)

    def fix(path, leaf):
        if is_sp_partial(tuple(_name(p) for p in path), leaf):
            return ps.psum_if_bound(leaf, axis_name)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, grads)
