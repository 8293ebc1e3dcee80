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

**Backward** (the kernel path is a ``jax.custom_vjp``; a program that never
differentiates it traces the forward kernel alone). Both cotangents walk
the forward's tile layout, nothing is sorted again:

- ``dx = dy @ w[group]^T``: the forward's kernel with the weight block
  contracted over its columns (``transpose_w``: ``[block_m, N] x [block_k,
  N]^T``, no transposed copy of the weights), under the same scope
  ``apx:moe_grouped_matmul``. Its rows past ``tiles_used`` are undefined,
  as the forward's are: whoever laid the rows out reads back the rows it
  wrote (``moe_dropless._take_rows`` gathers its cotangent).
- ``dw[g] = sum over the tiles of g of x_tile^T @ dy_tile``: a kernel of its
  own (``apx:moe_grouped_matmul_dw``), grid ``(n blocks, m tiles)`` with the
  tiles innermost: a float32 ``[K, block_n]`` accumulator is zeroed at a
  group's first tile and written to the group's block of ``dw`` at its last
  (a group's tiles are consecutive), tiles past ``tiles_used`` are skipped
  as in the forward, and a group without a row keeps the zeros ``dw``
  starts from (the output aliases a zero array that is never read).
  ``tile_group`` and ``tiles_used`` carry no gradient.

``impl="reference"`` is a plain einsum over a one-hot of each row's group:
the off-TPU path and the tests' baseline. ``jax.lax.ragged_dot`` over the
same layout was tried on the chip and not kept: 1.7-2.4x slower at decode's
and a prompt's shapes, and its cost grows with the static row bound where
the kernel skips unused tiles (PERF.md, PR 26).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu import _compat
from apex_tpu._compat import tpu_compiler_params
from apex_tpu.monitor import profile as _prof

IMPLS = ("kernel", "reference")

#: double-buffered [K, block_n] weight blocks at K = 7168 need ~15 MB, over
#: Mosaic's default 16 MB scope with the x and out blocks beside them
_VMEM_LIMIT = 64 * 1024 * 1024


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


def _kernel(tg_ref, used_ref, x_ref, w_ref, o_ref, *, transpose_w=False):
    del tg_ref

    @pl.when(pl.program_id(0) < used_ref[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0],
            (((1,), (1 if transpose_w else 0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _dw_kernel(tg_ref, used_ref, x_ref, dy_ref, _, o_ref, acc):
    i, used = pl.program_id(1), used_ref[0]
    last_tile = pl.num_programs(1) - 1

    @pl.when(i < used)
    def _():
        g = tg_ref[i]
        first = (i == 0) | (tg_ref[jnp.maximum(i - 1, 0)] != g)
        last = (i == used - 1) | (tg_ref[jnp.minimum(i + 1, last_tile)] != g)
        prod = jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [K, block_n]

        @pl.when(first)
        def _():
            acc[...] = prod

        @pl.when(jnp.logical_not(first))
        def _():
            acc[...] += prod

        @pl.when(last)
        def _():
            o_ref[0] = acc[...].astype(o_ref.dtype)

    # no tile holds a row: the block the clamped index names is written
    # back all the same, so it has to hold the zeros it stands for
    @pl.when((used == 0) & (i == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def grouped_matmul(x, w, tile_group, tiles_used, *, block_m: int,
                   impl: str = "kernel", interpret: Optional[bool] = None):
    """``x`` ``[tiles * block_m, K]`` in the tile layout, ``w`` ``[g, K,
    N]``: ``[tiles * block_m, N]`` in ``x.dtype``. Rows of tiles past
    ``tiles_used`` are undefined. Differentiable in ``x`` and ``w`` (module
    doc: the cotangent of ``x`` is undefined in those rows too, a group
    without a row gets a zero ``dw``)."""
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
    used = jnp.reshape(tiles_used, (1,)).astype(jnp.int32)
    return _grouped_matmul(x, w, tile_group, used, block_m,
                           _compat.resolve_interpret(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped_matmul(x, w, tile_group, used, block_m, interpret):
    return _call(x, w, tile_group, used, block_m, interpret)


def _vjp_fwd(x, w, tile_group, used, block_m, interpret):
    return (_call(x, w, tile_group, used, block_m, interpret),
            (x, w, tile_group, used))


def _vjp_bwd(block_m, interpret, res, dy):
    x, w, tile_group, used = res
    dx = _call(dy, w, tile_group, used, block_m, interpret,
               transpose_w=True)
    dw = _call_dw(x, dy, w, tile_group, used, block_m, interpret)
    return dx.astype(x.dtype), dw, None, None


_grouped_matmul.defvjp(_vjp_fwd, _vjp_bwd)


def last_used(i, used):
    """Tile ``i``, or past the used tiles the last used one: a step there
    stays on the blocks it had, and moves nothing."""
    return jnp.minimum(i, jnp.maximum(used[0] - 1, 0))


def _call(x, w, tile_group, used, block_m, interpret, transpose_w=False):
    """``x @ w[group]`` (``[m, K] -> [m, N]``), or with ``transpose_w``
    ``x @ w[group]^T`` (``[m, N] -> [m, K]``)."""
    m = x.shape[0]
    g, k, n = w.shape
    tiles = tile_group.shape[0]
    if transpose_w:
        bo = _block_n(n, k)                 # a block of w's ROWS, whole N
        n_out, w_block = k, (1, bo, n)
    else:
        bo = _block_n(k, n)                 # a block of w's columns, whole K
        n_out, w_block = n, (1, k, bo)
    last = n_out // bo - 1
    step = last_used

    def col(i, j, used):
        return jnp.where(i < used[0], j, last)

    def w_map(i, j, tg, used):
        c = col(i, j, used)
        return (tg[i], c, 0) if transpose_w else (tg[i], 0, c)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles, n_out // bo),
        in_specs=[
            pl.BlockSpec((block_m, x.shape[1]),
                         lambda i, j, tg, used: (step(i, used), 0)),
            pl.BlockSpec(w_block, w_map),
        ],
        out_specs=pl.BlockSpec(
            (block_m, bo),
            lambda i, j, tg, used: (step(i, used), col(i, j, used))),
    )
    with _prof.scope("moe_grouped_matmul"):
        return pl.pallas_call(
            functools.partial(_kernel, transpose_w=transpose_w),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, n_out), x.dtype),
            compiler_params=tpu_compiler_params(
                vmem_limit_bytes=_VMEM_LIMIT,
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(tile_group, used, x, w)


def _call_dw(x, dy, w, tile_group, used, block_m, interpret):
    """``dw [g, K, N]`` in ``w.dtype`` (module doc)."""
    g, k, n = w.shape
    tiles = tile_group.shape[0]
    bn = _block_n(k, n, itemsize=4)         # the float32 accumulator's
    step = last_used

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // bn, tiles),
        in_specs=[
            pl.BlockSpec((block_m, k),
                         lambda j, i, tg, used: (step(i, used), 0)),
            pl.BlockSpec((block_m, bn),
                         lambda j, i, tg, used: (step(i, used), j)),
            pl.BlockSpec(memory_space=pl.ANY),      # the zeros dw starts as
        ],
        out_specs=pl.BlockSpec(
            (1, k, bn), lambda j, i, tg, used: (tg[step(i, used)], 0, j)),
        scratch_shapes=[pltpu.VMEM((k, bn), jnp.float32)],
    )
    with _prof.scope("moe_grouped_matmul_dw"):
        return pl.pallas_call(
            _dw_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((g, k, n), w.dtype),
            input_output_aliases={4: 0},
            compiler_params=tpu_compiler_params(
                vmem_limit_bytes=_VMEM_LIMIT,
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(tile_group, used, x, dy, jnp.zeros((g, k, n), w.dtype))


def _block_n(k: int, n: int, budget: int = 8 * 1024 * 1024,
                     itemsize: int = 2) -> int:
    """The widest column block (a multiple of 128 that divides ``n``)
    whose ``[K, block_n]`` weight block stays under ``budget`` bytes."""
    best = None
    for bn in range(128, n + 1, 128):
        if n % bn == 0 and k * bn * itemsize <= budget:
            best = bn
    return best or n
