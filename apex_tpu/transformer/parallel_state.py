"""Model-parallel state: the DP×PP×TP (×CP×EP) grid as one jax Mesh.

Reference: ``apex/transformer/parallel_state.py:53-322`` —
``initialize_model_parallel(tp, pp, vpp)`` carves the NCCL world into
data/tensor/pipeline/embedding process groups and stores them in module
globals, with rank/world-size getters for each.

TPU-native translation: the grid IS a ``jax.sharding.Mesh`` with named
axes ``("data", "pipeline", "tensor")`` (+ optional ``context`` for
sequence/ring parallelism and ``expert`` for MoE). "Groups" are mesh axes;
"ranks" are ``lax.axis_index`` inside shard_map/jit (traced) and plain
coordinates outside. The embedding group (first+last PP stage,
``parallel_state.py:124-133``) becomes an ``axis_index_groups`` helper for
collectives restricted to those stages.

Axis order note: ("data", "pipeline", "tensor") puts tensor-parallel
neighbours innermost so TP collectives ride the fastest ICI links and DP
gradient reduction crosses the slower dimension — same locality policy as
the reference's "tp ranks contiguous" group construction
(``parallel_state.py:95-122``).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh
from apex_tpu._compat import axis_size as _traced_axis_size

# Canonical axis names
DATA_AXIS = "data"
PIPELINE_AXIS = "pipeline"
TENSOR_AXIS = "tensor"
CONTEXT_AXIS = "context"   # sequence/ring-attention parallelism (new, §5 gap)
EXPERT_AXIS = "expert"     # MoE expert parallelism (new)

_MESH: Optional[Mesh] = None
_VIRTUAL_PIPELINE_WORLD_SIZE: Optional[int] = None
_VIRTUAL_PIPELINE_RANK: Optional[int] = None
_PIPELINE_SPLIT_RANK: Optional[int] = None


def initialize_model_parallel(
    tensor_model_parallel_size_: int = 1,
    pipeline_model_parallel_size_: int = 1,
    virtual_pipeline_model_parallel_size_: Optional[int] = None,
    pipeline_model_parallel_split_rank_: Optional[int] = None,
    context_parallel_size_: int = 1,
    expert_parallel_size_: int = 1,
    *,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build and install the global mesh.

    Mirrors ``initialize_model_parallel`` (``parallel_state.py:53-156``):
    world must factor as dp·pp·tp(·cp·ep); virtual-pipeline state is
    recorded for the interleaved schedule. Returns the Mesh (also kept as
    module state for the getters).
    """
    global _MESH, _VIRTUAL_PIPELINE_WORLD_SIZE, _VIRTUAL_PIPELINE_RANK, _PIPELINE_SPLIT_RANK
    devs = list(devices if devices is not None else jax.devices())
    world = len(devs)
    tp = tensor_model_parallel_size_
    pp = pipeline_model_parallel_size_
    cp = context_parallel_size_
    ep = expert_parallel_size_
    denom = tp * pp * cp * ep
    if world % denom != 0:
        raise RuntimeError(
            f"world size ({world}) is not divisible by tp ({tp}) x pp ({pp})"
            f" x cp ({cp}) x ep ({ep})")
    dp = world // denom

    if virtual_pipeline_model_parallel_size_ is not None:
        if pp < 2:
            # parallel_state.py:84-88: interleaved schedule needs pp >= 2
            raise RuntimeError(
                "pipeline-model-parallel size should be greater than 1 with "
                "interleaved schedule")
        _VIRTUAL_PIPELINE_WORLD_SIZE = virtual_pipeline_model_parallel_size_
        _VIRTUAL_PIPELINE_RANK = 0
    else:
        _VIRTUAL_PIPELINE_WORLD_SIZE = None
        _VIRTUAL_PIPELINE_RANK = None
    _PIPELINE_SPLIT_RANK = pipeline_model_parallel_split_rank_

    shape = [dp, pp, tp]
    names = [DATA_AXIS, PIPELINE_AXIS, TENSOR_AXIS]
    if cp > 1:
        shape.insert(1, cp)
        names.insert(1, CONTEXT_AXIS)
    if ep > 1:
        shape.insert(1, ep)
        names.insert(1, EXPERT_AXIS)
    arr = np.array(_ring_ordered(devs, tp)).reshape(shape)
    _MESH = Mesh(arr, tuple(names))
    return _MESH


def _ici_neighbours(a, b) -> bool:
    """Two chips one ICI hop apart: their ``coords`` differ by one in
    exactly one place."""
    diff = [abs(p - q) for p, q in zip(a.coords, b.coords)]
    return sum(diff) == 1


def _ring_ordered(devs: list, tp: int) -> list:
    """``devs`` with each tensor group — ``tp`` consecutive devices, as
    the reshape above cuts them — reordered so that consecutive tensor
    ranks, and the last with the first, are ICI neighbours: the
    sequence-parallel rings (``parallel/overlap.py``) hop ``j -> j +- 1``,
    and a hop between chips that are not neighbours crosses two links
    that another pair is using. A v5e 2x2 enumerates row-major over
    ``coords``, (0,0) (1,0) (0,1) (1,1), so ranks 1 -> 2 and 3 -> 0 of
    the enumeration order are diagonal; the cycle is 0, 1, 3, 2.

    The order follows from the devices: a group keeps its members and its
    first device, and takes the first cycle (else the first path) through
    its members' neighbour graph in enumeration order; devices without
    ``coords`` (CPU), a group of one or two, a group with no such path and
    a group too large to search (the walk below is exhaustive) keep the
    order they came in."""
    if not 3 <= tp <= 16 or not all(hasattr(d, "coords") for d in devs):
        return devs

    def walk(path, rest, closed):
        if not rest:
            return path if not closed or _ici_neighbours(
                path[-1], path[0]) else None
        for d in rest:
            if _ici_neighbours(path[-1], d):
                found = walk(path + [d], [r for r in rest if r is not d],
                             closed)
                if found:
                    return found
        return None

    out = []
    for g in range(0, len(devs), tp):
        group = devs[g:g + tp]
        out += (walk(group[:1], group[1:], True)
                or walk(group[:1], group[1:], False) or group)
    return out


def model_parallel_is_initialized() -> bool:
    """``parallel_state.py:159-166``."""
    return _MESH is not None


def get_mesh() -> Mesh:
    if _MESH is None:
        raise RuntimeError("model parallel mesh is not initialized")
    return _MESH


def destroy_model_parallel():
    """``parallel_state.py:(end) destroy_model_parallel``."""
    global _MESH, _VIRTUAL_PIPELINE_WORLD_SIZE, _VIRTUAL_PIPELINE_RANK, _PIPELINE_SPLIT_RANK
    _MESH = None
    _VIRTUAL_PIPELINE_WORLD_SIZE = None
    _VIRTUAL_PIPELINE_RANK = None
    _PIPELINE_SPLIT_RANK = None


def _axis_size(name: str) -> int:
    if _MESH is None or name not in _MESH.axis_names:
        return 1
    return _MESH.shape[name]


# -- world sizes (host-side, static) ---------------------------------------

def get_tensor_model_parallel_world_size() -> int:
    """``parallel_state.py:214-219``."""
    return _axis_size(TENSOR_AXIS)


def get_pipeline_model_parallel_world_size() -> int:
    return _axis_size(PIPELINE_AXIS)


def get_data_parallel_world_size() -> int:
    return _axis_size(DATA_AXIS)


def get_context_parallel_world_size() -> int:
    return _axis_size(CONTEXT_AXIS)


def get_expert_parallel_world_size() -> int:
    return _axis_size(EXPERT_AXIS)


# -- ranks: traced inside shard_map, 0 outside ------------------------------

def _axis_rank(name: str):
    try:
        return jax.lax.axis_index(name)
    except NameError:
        return 0


def psum_if_bound(x, axis_name: str):
    """``lax.psum`` when ``axis_name`` is bound (inside ``shard_map``),
    identity otherwise — outside shard_map arrays carry *global* values, so
    the unreduced value is already the full reduction (tp=1 / GSPMD use)."""
    try:
        return jax.lax.psum(x, axis_name)
    except NameError:
        return x


def pmax_if_bound(x, axis_name: str):
    try:
        return jax.lax.pmax(x, axis_name)
    except NameError:
        return x


def sequence_parallel_active(flag: bool) -> bool:
    """Megatron-SP is in effect only when requested AND tp > 1."""
    return bool(flag) and get_tensor_model_parallel_world_size() > 1


def axis_size_if_bound(axis_name) -> int:
    """Size of ``axis_name`` inside shard_map, 1 when unbound/None.

    Reads the *traced axis env* (the compat ``axis_size``), not the
    static ``_MESH`` lookup ``_axis_size`` above: callers may be inside a
    shard_map over a mesh that was never installed as the global, and
    outside any shard_map the axis is unbound (NameError -> 1) even when
    a global mesh with that axis exists."""
    if axis_name is None:
        return 1
    try:
        return _traced_axis_size(axis_name)
    except NameError:
        return 1


def get_tensor_model_parallel_rank():
    """Inside shard_map: traced index on the tensor axis
    (``parallel_state.py:252-258`` analog). Outside: 0."""
    return _axis_rank(TENSOR_AXIS)


def get_pipeline_model_parallel_rank():
    return _axis_rank(PIPELINE_AXIS)


def get_data_parallel_rank():
    return _axis_rank(DATA_AXIS)


def get_context_parallel_rank():
    return _axis_rank(CONTEXT_AXIS)


# -- pipeline stage predicates (static, per-stage — used when building the
#    per-stage module list; parallel_state.py:260-322) ----------------------

def is_pipeline_first_stage(stage: int = 0, ignore_virtual: bool = False) -> bool:
    if not ignore_virtual and _VIRTUAL_PIPELINE_WORLD_SIZE is not None:
        if _VIRTUAL_PIPELINE_RANK != 0:
            return False
    return stage == 0


def is_pipeline_last_stage(stage: int, ignore_virtual: bool = False) -> bool:
    if not ignore_virtual and _VIRTUAL_PIPELINE_WORLD_SIZE is not None:
        if _VIRTUAL_PIPELINE_RANK != _VIRTUAL_PIPELINE_WORLD_SIZE - 1:
            return False
    return stage == get_pipeline_model_parallel_world_size() - 1


def get_virtual_pipeline_model_parallel_world_size():
    return _VIRTUAL_PIPELINE_WORLD_SIZE


def get_virtual_pipeline_model_parallel_rank():
    return _VIRTUAL_PIPELINE_RANK


def set_virtual_pipeline_model_parallel_rank(rank: int):
    global _VIRTUAL_PIPELINE_RANK
    _VIRTUAL_PIPELINE_RANK = rank


def get_pipeline_model_parallel_split_rank():
    return _PIPELINE_SPLIT_RANK


def get_embedding_axis_index_groups():
    """Groups pairing first and last pipeline stage for tied-embedding grad
    reduction (``parallel_state.py:124-133`` embedding group). Returns
    ``axis_index_groups`` for a psum over the pipeline axis, or None when
    pp == 1."""
    pp = get_pipeline_model_parallel_world_size()
    if pp == 1:
        return None
    if pp == 2:
        return [[0, 1]]
    # only first+last participate; middle stages form singleton groups
    groups = [[0, pp - 1]] + [[i] for i in range(1, pp - 1)]
    return groups
