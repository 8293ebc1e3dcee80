"""Grouped matmul: rows sorted by group, each group against its own weight.

``out[r] = x[r] @ w[group of r]`` for rows laid out in tiles of
``block_m``: a group's rows start at a tile boundary and every tile belongs
to ONE group (``tile_group``), so groups may be uneven or empty and the
kernel never looks at a row's group. :func:`tile_layout` builds that layout
from per-group counts; :mod:`apex_tpu.transformer.moe_dropless` is its
user.

The Pallas kernel's grid is ``(m tiles, n tiles)`` with the whole ``K`` in
a block (no accumulator): a step is one ``[block_m, K] @ [K, block_n]`` on
the MXU with the weight block picked by the scalar-prefetched
``tile_group``. Decode is weight-streaming-bound (a handful of rows a
group), so what matters is that a group's weight is read once a column
block and that blocks are large; ``K * block_n`` of bf16 is 7 MB at K =
7168, hence the raised VMEM limit. The number of tiles is static (the
worst case of the counts); tiles past ``tiles_used`` map every operand to
the block of the last used step, so they move nothing, and compute
nothing: their output rows are never read.

``impl="reference"`` is a plain einsum over a one-hot of each row's group:
the off-TPU path and the tests' baseline. ``jax.lax.ragged_dot`` over the
same layout was tried on the chip and not kept: 1.7-2.4x slower at decode's
and a prompt's shapes, and its cost grows with the static row bound where
the kernel skips unused tiles (PERF.md, PR 26).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu._compat import tpu_compiler_params
from apex_tpu.monitor import profile as _prof

IMPLS = ("kernel", "reference")

#: double-buffered [K, block_n] weight blocks at K = 7168 need ~15 MB, over
#: Mosaic's default 16 MB scope with the x and out blocks beside them
_VMEM_LIMIT = 64 * 1024 * 1024


def _resolve_interpret(interpret):
    # the one rule of the Pallas ops (looked up at call time: the compile
    # tests steer it there)
    from apex_tpu.ops.flash_attention import _resolve_interpret as rule
    return rule(interpret)


def tile_layout(counts, block_m: int, max_rows: int):
    """Where sorted rows go when every group starts at a tile boundary.

    ``counts`` int32 ``[g]``: rows of each group (any may be 0), at most
    ``max_rows`` in all. Returns ``(starts [g], tile_group [tiles],
    tiles_used [])``: the first padded row of each group, the group that
    owns each tile (tiles past ``tiles_used`` repeat the last used tile's
    group) and the number of tiles that hold rows. ``tiles`` is static:
    ``num_tiles(g, block_m, max_rows)``."""
    g = counts.shape[0]
    tiles = num_tiles(g, block_m, max_rows)
    per = -(-counts // block_m)                       # tiles of each group
    ends = jnp.cumsum(per)
    starts = (ends - per) * block_m
    used = ends[-1]
    t = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32),
                    jnp.maximum(used - 1, 0))
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, t, side="right"), g - 1).astype(jnp.int32)
    return starts.astype(jnp.int32), tile_group, used.astype(jnp.int32)


def num_tiles(groups: int, block_m: int, max_rows: int) -> int:
    """Tiles that hold ``max_rows`` rows however they fall into
    ``groups`` groups: every non-empty group may waste part of a tile."""
    return -(-max_rows // block_m) + min(groups, max_rows)


def _kernel(tg_ref, used_ref, x_ref, w_ref, o_ref):
    del tg_ref

    @pl.when(pl.program_id(0) < used_ref[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def grouped_matmul(x, w, tile_group, tiles_used, *, block_m: int,
                   impl: str = "kernel", interpret: Optional[bool] = None):
    """``x`` ``[tiles * block_m, K]`` in the tile layout, ``w`` ``[g, K,
    N]``: ``[tiles * block_m, N]`` in ``x.dtype``. Rows of tiles past
    ``tiles_used`` are undefined."""
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    m, k = x.shape
    g, kw, n = w.shape
    tiles = tile_group.shape[0]
    if kw != k or m != tiles * block_m:
        raise ValueError(f"x {x.shape} / w {w.shape} / {tiles} tiles of "
                         f"{block_m} rows do not fit together")
    if impl == "reference":
        onehot = jax.nn.one_hot(jnp.repeat(tile_group, block_m), g,
                                dtype=x.dtype)
        return jnp.einsum("mk,mg,gkn->mn", x, onehot, w,
                          preferred_element_type=jnp.float32
                          ).astype(x.dtype)
    bn = _block_n(k, n)

    def step(i, used):
        # past the used tiles: stay on the last used step's blocks
        return jnp.minimum(i, jnp.maximum(used[0] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles, n // bn),
        in_specs=[
            pl.BlockSpec((block_m, k),
                         lambda i, j, tg, used: (step(i, used), 0)),
            pl.BlockSpec((1, k, bn),
                         lambda i, j, tg, used: (
                             tg[i], 0, jnp.where(i < used[0], j,
                                                 n // bn - 1))),
        ],
        out_specs=pl.BlockSpec(
            (block_m, bn),
            lambda i, j, tg, used: (step(i, used),
                                    jnp.where(i < used[0], j, n // bn - 1))),
    )
    with _prof.scope("moe_grouped_matmul"):
        return pl.pallas_call(
            _kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
            compiler_params=tpu_compiler_params(
                vmem_limit_bytes=_VMEM_LIMIT,
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=_resolve_interpret(interpret),
        )(tile_group, jnp.reshape(tiles_used, (1,)).astype(jnp.int32), x, w)


def _block_n(k: int, n: int, budget: int = 8 * 1024 * 1024,
                     itemsize: int = 2) -> int:
    """The widest column block (a multiple of 128 that divides ``n``)
    whose ``[K, block_n]`` weight block stays under ``budget`` bytes."""
    best = None
    for bn in range(128, n + 1, 128):
        if n % bn == 0 and k * bn * itemsize <= budget:
            best = bn
    return best or n
