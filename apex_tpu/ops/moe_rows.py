"""Row movement between token order and the grouped matmul's tile layout.

The expert layer (:mod:`apex_tpu.transformer.moe_dropless`) sorts its
(token, choice) assignments by expert into tiles of ``block_m`` rows
(``ops.grouped_matmul.tile_layout``). The buffers are sized for the worst
case; a step fills ``tiles_used`` of the tiles. ``jnp.take`` builds every
row of such a buffer, dead or live. The kernels here move the live rows
only, in the two directions the layer needs:

- :func:`sorted_rows`: ``out[r] = x[src[r]]`` for the rows of the first
  ``tiles_used`` tiles, one program a tile, the others skipped as
  ``grouped_matmul`` skips them (clamped index maps and ``pl.when``: a dead
  tile is neither a DMA nor a write; its rows stay undefined). With
  ``scale`` and ``dot_with`` it is the combine's cotangent in one pass:
  ``out[r] = scale[r] * x[src[r]]`` (float32 product, rounded once) and
  ``dot[r] = <x[src[r]], dot_with[r]>`` in float32.
- :func:`token_rows`: ``out[t] = sum_c wm[t, c] * ys[idx[t, c]]`` over the
  choices with ``idx[t, c] >= 0``, one program a block of tokens, a row
  fetched only where it exists (a program walks the list of its block's
  live choices, :func:`_live_slots`), summed in float32 in ascending ``c``
  and cast once. The ``[t, k, h]`` array is never built.

**Rows travel as 32-bit words.** A DMA addresses whole tiles of an array's
last two dimensions (8 rows of ``[n, h]``), so a row that is to be fetched
alone lies in a layout of its own: :func:`open_tiles` (a kernel over the
used tiles of the sorted side; :func:`open_rows` is the same over every row
of the token-order side) writes row ``r`` as ``S`` sublanes of 128
uint32 words at ``[r * S, (r + 1) * S)`` of a ``[n * S, 128]`` array, word
``(s, lane)`` holding columns ``s * 128 + lane`` (low half) and ``h / 2 +
s * 128 + lane`` (high half); ``S`` is ``h / 256`` rounded up to whole
tiles of 8 sublanes. A row is then one contiguous DMA, a group of them is
in flight on one semaphore, and the kernels read a sublane of every fetched
row with one strided load, so that halves of words are split and joined
lane by lane and nothing is shuffled: a bf16 tile ``[block_m, h]`` IS
``[block_m / 2, h]`` uint32 with rows ``2j`` (low) and ``2j + 1`` (high) in
a word, which is why even and odd output rows are fetched into two halves
of the buffer. ``h`` is a multiple of 256 and the rows are bf16 (:func:`fits`).

Each kernel call is a ``jax.jit`` function, and each kernel's body loops
over a row's column blocks (``lax.fori_loop`` with 128-aligned dynamic lane
slices) where it could be unrolled: a program with per-block recomputation
still lowers a jitted call once a context (forward, recomputed, transposed),
so a body's size is paid several times a program, in ``setup_s`` (PERF.md
section 6, PR 47).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu import _compat
from apex_tpu._compat import tpu_compiler_params
from apex_tpu.monitor import profile as _prof
from apex_tpu.ops.grouped_matmul import last_used

#: tokens a program of :func:`token_rows` sums: ``k`` rows each in flight
TOKEN_BLOCK = 32
#: row DMAs a loop iteration starts (Mosaic unrolls no ``fori_loop`` partly)
_UNROLL = 8
#: a tile, its buffer of fetched rows and the pipeline's second copies are
#: ~8 MB; under Mosaic's default 16 MB the weighted kernel compiles in 14 s,
#: with room in 1
_VMEM_LIMIT = 64 * 1024 * 1024
_U32 = jnp.uint32
_HI = np.uint32(0xFFFF0000)


def fits(h: int, dtype) -> bool:
    """Whether rows ``[.., h]`` of ``dtype`` can travel as words: bf16 (the
    sums and products read a half word as the high half of a float32), two
    128-lane column blocks a word."""
    return h % 256 == 0 and jnp.dtype(dtype) == jnp.bfloat16


def _row_sublanes(h: int) -> int:
    """``S``: the sublanes of 128 words a row holds, ``h / 256`` of them
    filled."""
    return -(-(h // 256) // 8) * 8


def _f32(word_half):
    """A bf16 in the HIGH half of each word (low half zero) as float32."""
    return pltpu.bitcast(word_half, jnp.float32)


def _bf16_bits(x):
    """float32 ``x`` rounded to bf16 (nearest even), in the HIGH half of
    its words."""
    b = pltpu.bitcast(x, _U32)
    return (b + 0x7FFF + ((b >> 16) & 1)) & _HI


# -- sorted tiles -> rows that can be fetched alone ---------------------------

def _lanes(s, first=0):
    """The 128 lanes of column block ``s`` after column ``first``."""
    return pl.ds(pl.multiple_of(first + s * 128, 128), 128)


def _open_tiles_kernel(used_ref, x_ref, o_ref, *, s_rows):
    @pl.when(pl.program_id(0) < used_ref[0])
    def _():
        hm, h = x_ref.shape[0] // 2, x_ref.shape[1]

        def part(s, carry):
            lo = pltpu.bitcast(x_ref[:, _lanes(s)], _U32)
            hi = pltpu.bitcast(x_ref[:, _lanes(s, h // 2)], _U32)
            o_ref[pl.ds(s, hm, stride=2 * s_rows), :] = \
                (lo & 0xFFFF) | (hi << 16)                  # rows 2j
            o_ref[pl.ds(s_rows + s, hm, stride=2 * s_rows), :] = \
                (lo >> 16) | (hi & _HI)                     # rows 2j + 1
            return carry

        jax.lax.fori_loop(0, h // 256, part, 0)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def _open_tiles_call(ys, used, *, block_m, interpret):
    rows, h = ys.shape
    s_rows = _row_sublanes(h)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // block_m,),
        in_specs=[pl.BlockSpec((block_m, h),
                               lambda i, used: (last_used(i, used), 0))],
        out_specs=pl.BlockSpec((block_m * s_rows, 128),
                               lambda i, used: (last_used(i, used), 0)),
    )
    with _prof.scope("moe_rows_open"):
        return pl.pallas_call(
            functools.partial(_open_tiles_kernel, s_rows=s_rows),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows * s_rows, 128), _U32),
            compiler_params=tpu_compiler_params(
                vmem_limit_bytes=_VMEM_LIMIT,
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(used, ys)


def open_tiles(ys, tiles_used, *, block_m: int,
               interpret: Optional[bool] = None):
    """The rows of the first ``tiles_used`` tiles of ``ys`` ``[tiles *
    block_m, h]`` (bf16) as ``[tiles * block_m * S, 128]`` uint32, a row a
    contiguous run of ``S`` sublanes (module doc); the other rows stay
    undefined."""
    used = jnp.reshape(tiles_used, (1,)).astype(jnp.int32)
    return _open_tiles_call(ys, used, block_m=block_m,
                            interpret=_compat.resolve_interpret(interpret))


def open_rows(x, *, interpret: Optional[bool] = None):
    """:func:`open_tiles` of every row of ``x`` ``[n, h]``, ``n`` a multiple
    of 16."""
    n = x.shape[0]
    block = next(b for b in (256, 128, 64, 32, 16) if n % b == 0)
    return open_tiles(x, n // block, block_m=block, interpret=interpret)


# -- token order -> sorted order ----------------------------------------------

def _start_row(rows_ref, buf, sem, s_rows, row, slot):
    """Start the DMA of opened row ``row`` into the buffer's ``slot``."""
    at = pl.multiple_of(row * s_rows, s_rows)
    to = pl.multiple_of(slot * s_rows, s_rows)
    pltpu.make_async_copy(rows_ref.at[pl.ds(at, s_rows)],
                          buf.at[pl.ds(to, s_rows)], sem).start()


def _wait(n, rows_ref, buf, sem, s_rows):
    """Wait for ``n`` row DMAs on ``sem``, a row's bytes a wait."""
    def wait(i, carry):
        pltpu.make_async_copy(rows_ref.at[pl.ds(0, s_rows)],
                              buf.at[pl.ds(0, s_rows)], sem).wait()
        return carry

    jax.lax.fori_loop(0, n, wait, 0)


def _sorted_kernel(used_ref, src_ref, *refs, s_rows, weighted):
    if weighted:
        w_ref, ys_ref, rows_ref, o_ref, dot_ref, buf, sem = refs
    else:
        rows_ref, o_ref, buf, sem = refs

    @pl.when(pl.program_id(0) < used_ref[0])
    def _():
        hm, h = o_ref.shape[0] // 2, o_ref.shape[1]

        # rows 2j to the first half of the buffer, rows 2j + 1 to the second:
        # a word of the output holds one of each
        def start(g, carry):
            for u in range(_UNROLL):
                i = g * _UNROLL + u
                _start_row(rows_ref, buf, sem, s_rows, src_ref[0, 0, i],
                           (u % 2) * hm + i // 2)
            return carry

        jax.lax.fori_loop(0, 2 * hm // _UNROLL, start, 0)
        _wait(2 * hm, rows_ref, buf, sem, s_rows)

        def put(at, words):                     # [hm, 128] words, two rows each
            o_ref[:, at] = pltpu.bitcast(words, o_ref.dtype)

        def part(s, dots):
            even = buf[pl.ds(s, hm, stride=s_rows), :]
            odd = buf[pl.ds(hm * s_rows + s, hm, stride=s_rows), :]
            cols = _lanes(s), _lanes(s, h // 2)
            if not weighted:
                put(cols[0], (even & 0xFFFF) | (odd << 16))
                put(cols[1], (even >> 16) | (odd & _HI))
                return dots
            w_even, w_odd = w_ref[:, 0:1], w_ref[:, 1:2]
            dot_even, dot_odd = dots
            halves = ((even << 16, odd << 16), (even & _HI, odd & _HI))
            for at, (e, o) in zip(cols, halves):
                e, o = _f32(e), _f32(o)
                y = pltpu.bitcast(ys_ref[:, at], _U32)
                dot_even += e * _f32(y << 16)
                dot_odd += o * _f32(y & _HI)
                put(at, (_bf16_bits(e * w_even) >> 16)
                    | _bf16_bits(o * w_odd))
            return dot_even, dot_odd

        zeros = jnp.zeros((hm, 128), jnp.float32)
        dots = jax.lax.fori_loop(0, h // 256, part,
                                 (zeros, zeros) if weighted else 0)
        if weighted:
            dot_ref[:, 0:1] = dots[0].sum(-1, keepdims=True)
            dot_ref[:, 1:2] = dots[1].sum(-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("h", "dtype", "block_m",
                                             "interpret"))
def _sorted_call(rows, src, used, scale, dot_with, *, h, dtype, block_m,
                 interpret):
    weighted = scale is not None
    n = src.shape[0]
    tiles, hm = n // block_m, block_m // 2
    s_rows = _row_sublanes(h)

    in_specs = [pl.BlockSpec((1, 1, block_m), lambda i, u: (last_used(i, u), 0, 0),
                             memory_space=pltpu.SMEM)]
    operands = [src.reshape(tiles, 1, block_m)]
    out_specs = pl.BlockSpec((block_m, h), lambda i, u: (last_used(i, u), 0))
    out_shape = jax.ShapeDtypeStruct((n, h), dtype)
    if weighted:
        pair = pl.BlockSpec((hm, 2), lambda i, u: (last_used(i, u), 0))
        in_specs += [pair, pl.BlockSpec((block_m, h),
                                        lambda i, u: (last_used(i, u), 0))]
        operands += [scale.astype(jnp.float32).reshape(n // 2, 2), dot_with]
        out_specs = [out_specs, pair]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((n // 2, 2), jnp.float32)]
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(tiles,), in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((block_m * s_rows, 128), _U32),
                        pltpu.SemaphoreType.DMA(())])
    with _prof.scope("moe_rows_sorted"):
        out = pl.pallas_call(
            functools.partial(_sorted_kernel, s_rows=s_rows,
                              weighted=weighted),
            grid_spec=grid_spec, out_shape=out_shape,
            compiler_params=tpu_compiler_params(
                vmem_limit_bytes=_VMEM_LIMIT,
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(used, *operands, rows)
    return (out[0], out[1].reshape(n)) if weighted else out


def sorted_rows(x, src, tiles_used, *, block_m: int, scale=None,
                dot_with=None, interpret: Optional[bool] = None):
    """``x[src]`` in the tile layout: ``x`` ``[t, h]``, ``src`` int32
    ``[tiles * block_m]`` (every entry a row of ``x``), only the rows of the
    first ``tiles_used`` tiles defined. With ``scale`` (float32 ``[tiles *
    block_m]``) and ``dot_with`` (``[tiles * block_m, h]``), both or neither:
    ``(scale[r] * x[src[r]], <x[src[r]], dot_with[r]>)``, the second in
    float32 ``[tiles * block_m]``."""
    if (scale is None) != (dot_with is None):
        raise ValueError("scale and dot_with come together")
    used = jnp.reshape(tiles_used, (1,)).astype(jnp.int32)
    interpret = _compat.resolve_interpret(interpret)
    return _sorted_call(open_rows(x, interpret=interpret),
                        src.astype(jnp.int32), used, scale, dot_with,
                        h=x.shape[1], dtype=jnp.dtype(x.dtype),
                        block_m=block_m, interpret=interpret)


# -- sorted order -> token order ----------------------------------------------

def _token_kernel(live_ref, key_ref, wm_ref, rows_ref, o_ref, buf, sem, *,
                  s_rows, slot_bits):
    tb, h = o_ref.shape
    k = wm_ref.shape[1]
    live = live_ref[pl.program_id(0)]

    def start(i, carry):
        key = key_ref[0, 0, i]
        _start_row(rows_ref, buf, sem, s_rows, key >> slot_bits,
                   key & ((1 << slot_bits) - 1))
        return carry

    jax.lax.fori_loop(0, live, start, 0)
    _wait(live, rows_ref, buf, sem, s_rows)
    # choice c of every token of the block: a slot that fetched nothing holds
    # whatever was there, so it is selected away
    ws = [wm_ref[:, c:c + 1] for c in range(k)]
    on = [w != 0 for w in ws]

    def part(s, carry):
        lo = jnp.zeros((tb, 128), jnp.float32)
        hi = jnp.zeros((tb, 128), jnp.float32)
        for c in range(k):
            words = buf[pl.ds(c * tb * s_rows + s, tb, stride=s_rows), :]
            lo += jnp.where(on[c], ws[c] * _f32(words << 16), 0.0)
            hi += jnp.where(on[c], ws[c] * _f32(words & _HI), 0.0)
        o_ref[:, _lanes(s)] = lo.astype(o_ref.dtype)
        o_ref[:, _lanes(s, h // 2)] = hi.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, h // 256, part, 0)


def _live_slots(idx, tb):
    """``(live [blocks], keys [blocks, 1, tb * k], slot_bits)``: for every
    block of ``tb`` tokens the number of its choices that have a row, and
    those first in ``keys`` as ``row << slot_bits | slot``. A block's slots
    are choice-major (the tokens of one choice lie a constant stride apart,
    which one strided load reads). The list is made by comparing and
    summing, ``[blocks, slots, slots]`` fused: XLA's scatter, gather and
    sort take a millisecond for what this does in a tenth."""
    t, k = idx.shape
    n = tb * k
    slot_bits = max(1, (n - 1).bit_length())
    rows = idx.reshape(t // tb, tb, k).transpose(0, 2, 1).reshape(t // tb, n)
    ok = rows >= 0
    upto = jnp.cumsum(ok.astype(jnp.int32), axis=1)         # inclusive
    at = jnp.arange(n, dtype=jnp.int32)
    # the slot of a block's i-th live choice: the slots with fewer than
    # i + 1 live choices up to and with them come before it
    nth = (upto[:, :, None] <= at[None, None, :]).sum(1, dtype=jnp.int32)
    key = jnp.where(ok, (rows << slot_bits) | at, 0)
    keys = jnp.where(at[None, None, :] == nth[:, :, None],
                     key[:, None, :], 0).sum(2, dtype=jnp.int32)
    return upto[:, -1], keys.reshape(t // tb, 1, n), slot_bits


@functools.partial(jax.jit, static_argnames=("h", "out_dtype", "interpret"))
def _token_call(rows, idx, wm, *, h, out_dtype, interpret):
    t, k = idx.shape
    tb = TOKEN_BLOCK
    s_rows = _row_sublanes(h)
    live, keys, slot_bits = _live_slots(idx, tb)
    if (rows.shape[0] // s_rows) >> (31 - slot_bits):
        raise ValueError(f"{rows.shape[0] // s_rows} rows and {tb * k} "
                         "slots a block do not fit one int32 key")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t // tb,),
        in_specs=[pl.BlockSpec((1, 1, tb * k), lambda i, live: (i, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((tb, k), lambda i, live: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tb, h), lambda i, live: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tb * k * s_rows, 128), _U32),
                        pltpu.SemaphoreType.DMA(())])
    with _prof.scope("moe_rows_tokens"):
        return pl.pallas_call(
            functools.partial(_token_kernel, s_rows=s_rows,
                              slot_bits=slot_bits),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((t, h), out_dtype),
            compiler_params=tpu_compiler_params(
                vmem_limit_bytes=_VMEM_LIMIT,
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(live, keys, wm, rows)


def token_rows(ys, idx, wm, tiles_used, *, block_m: int, out_dtype=None,
               interpret: Optional[bool] = None):
    """``out[t] = sum_c wm[t, c] * ys[idx[t, c]]`` over the choices with
    ``idx[t, c] >= 0`` (those rows lie in the first ``tiles_used`` tiles of
    ``ys`` ``[tiles * block_m, h]``), float32 sums in ascending ``c``, cast
    to ``out_dtype`` (``ys.dtype``). ``idx`` int32 ``[t, k]``, ``wm``
    float32 ``[t, k]`` (a choice whose weight is 0 adds nothing, whatever
    its row holds); ``t`` a multiple of :data:`TOKEN_BLOCK`."""
    interpret = _compat.resolve_interpret(interpret)
    rows = open_tiles(ys, tiles_used, block_m=block_m, interpret=interpret)
    return _token_call(rows, idx.astype(jnp.int32), wm.astype(jnp.float32),
                       h=ys.shape[1],
                       out_dtype=jnp.dtype(out_dtype or ys.dtype),
                       interpret=interpret)
