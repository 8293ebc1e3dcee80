"""The paged KV pool's kernels (the serve path): decode attention through a
block table over a preallocated page pool, its XLA reference, and the two
in-place writes that fill the pool.

The pool's layout contract, shared with ``apex_tpu.serve.cache``, is the
comment below. The prefills call the training forward
(``apex_tpu.ops.flash_attention``) from the serve side; nothing a train step
imports loads this module (``tests/test_layering.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu import _compat

# the masked score: finite and not -inf, so that a row with no live key (an
# inactive slot) has s - max = 0 and not NaN before its mask zeroes it
_NEG_INF = -1e30

# ---------------------------------------------------------------------------
# Paged KV pool (the serve path): one query token per sequence reading K/V
# through a block table over a preallocated page pool, and the in-place
# writes that fill the pool.
# ---------------------------------------------------------------------------
#
# Layout contract (shared with apex_tpu.serve.cache):
#   q            [b, kv_heads, group, d]   (group = q_heads // kv_heads; GQA.
#                                           MHA is group == 1)
#   kv pages     [kv_heads, num_pages, page_size, 2*d]
#                                          (ONE layer's pool: a token's K in
#                                           lanes 0:d, its V in d:2d)
#   block_tables [b, pages_per_seq] int32  (pool page ids; page 0 is the
#                                           null page — entries past the
#                                           sequence length point there and
#                                           are masked by seq_lens)
#   seq_lens     [b] int32                 (0 = inactive slot: zero output)
#   k/v_scales   [kv_heads, num_pages] f32 (fp8-KV mode: the per-page
#                                           quantize multiplier of
#                                           amp.fp8 — dequant divides it
#                                           back out in-kernel)
#
# Why K and V share a row: at d = 64 a [.., page_size, 64] array gets a
# device layout with the page slots in the lanes, which no Pallas call
# accepts, so every program that touched the pool copied it in and out
# (PERF.md, PR 24). With 2*d >= 128 in the lanes the layout XLA picks IS
# the row-major one the kernels below are held to, and a donated pool is
# updated where it lies.
#
# The decode kernel is one program a SEQUENCE (grid ``(b, head_blocks)``,
# head_blocks > 1 only where one page of every kv head, twice, would not
# fit ``_DECODE_BUFFER_BYTES`` of VMEM). The pool stays in HBM
# (``pl.ANY``); the program reads its row's length, walks that row's
# ``ceil(seq_len / page_size)`` live pages in a ``fori_loop`` and copies
# each, ``pool[:, table[j]]`` = one page of ALL kv heads, into one of two
# VMEM buffers, page j+1 (or the next row's first page) in flight while
# page j is computed. A slot past the sequence's end is never a step and
# never a DMA. Per page the heads' scores lie side by side in the lanes
# of one [page_size, heads] block, so the online softmax runs once a page
# for all heads, and both products stream the PAGE through the MXU
# against a small held operand (the queries; the probabilities). There is
# no backward: decode is inference-only.
#
# The page size is fixed when the pool is allocated, so resolution
# (explicit > tuned cache > heuristic, the fwd/bwd policy) happens in
# ``serve.cache.resolve_page_size`` at pool construction rather than per
# call. It is the unit of allocation, of one DMA and of one step of the
# walk.
#
# The two writes (``paged_kv_write_rows`` for a decode step,
# ``paged_kv_write_pages`` for a prompt) alias the pool to their output
# and move only the rows they touch: a tile of the pool is copied to
# VMEM, the new rows are merged in, and the tile is copied back. A
# prompt's pages go one after another. A decode step's rows go a GROUP a
# program, the group's reads in flight together and then its writes (one
# tile's two DMAs alone are latency: ~0.9 us a row where its bytes need
# 0.16, PERF.md PR 39); two rows of one tile (a speculative verify
# window, the masked rows on the null page) are merged into one buffer
# that is written once, so they never race. An XLA scatter or
# dynamic_update_slice computes the same pool but makes XLA lay the pool
# out for the update ([kv, 1, 1, 2d]: heads next to the lanes) and copy
# it there and back.


def _split_pages(kv_pages):
    d = kv_pages.shape[-1] // 2
    return kv_pages[..., :d], kv_pages[..., d:]


def paged_attention_reference(q, kv_pages, block_tables, seq_lens,
                              *, scale=None, k_scales=None, v_scales=None):
    """Pure-XLA paged decode attention — the parity baseline and the
    off-TPU serving path (gathers pages through the block table; O(b *
    pages_per_seq * page_size) memory, fine at decode's one-query
    shapes)."""
    kv_heads, _, page_size, _ = kv_pages.shape
    b, _, _, d = q.shape
    m = block_tables.shape[1]
    scale = d ** -0.5 if scale is None else scale
    # [kv, b, m, bs, 2d] -> [b, kv, m*bs, 2d]
    kv = jnp.take(kv_pages, block_tables, axis=1).transpose(1, 0, 2, 3, 4)
    kv = kv.astype(jnp.float32).reshape(b, kv_heads, m * page_size, 2 * d)
    k, v = _split_pages(kv)
    if k_scales is not None:
        ks = jnp.take(k_scales, block_tables, axis=1).transpose(1, 0, 2)
        k = k / jnp.repeat(ks, page_size, axis=2)[..., None]
    if v_scales is not None:
        vs = jnp.take(v_scales, block_tables, axis=1).transpose(1, 0, 2)
        v = v / jnp.repeat(vs, page_size, axis=2)[..., None]
    s = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32), k) * scale
    pos = jnp.arange(m * page_size, dtype=jnp.int32)
    live = pos[None, :] < seq_lens[:, None]              # [b, m*bs]
    s = jnp.where(live[:, None, None, :], s, _NEG_INF)
    mx = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), _NEG_INF)
    p = jnp.exp(s - mx)
    p = jnp.where(live[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bksd->bkgd", p, v) / jnp.where(l > 0, l, 1.0)
    return out.astype(q.dtype)


#: VMEM a decode program's two page buffers may take together. A program
#: takes every kv head of a sequence where one page of all of them, twice,
#: fits (16 heads x 128 rows x 128 lanes of bf16, twice: 1 MB), and
#: otherwise the largest divisor of ``kv_heads`` that does.
_DECODE_BUFFER_BYTES = 8 * 1024 * 1024


def _paged_decode_kernel(*refs, scale, page_size, group, fp8, pages_per_seq,
                         per_head=False):
    it = iter(refs)
    bt_ref = next(it)                       # scalar prefetch: [b*m] int32
    sl_ref = next(it)                       # scalar prefetch: [b] int32
    ks_ref = next(it) if fp8 else None      # SMEM [kv, num_pages] f32
    vs_ref = next(it) if fp8 else None
    q_ref, pool_ref, o_ref, buf, sem, acc_scr, slot_ref = it

    hb, width = buf.shape[1], buf.shape[3]  # kv heads a program, 2*d
    d, rows = acc_scr.shape                 # rows = hb * group query heads
    n_hb = pool_ref.shape[0] // hb          # head blocks: 1 where VMEM allows
    bi, hj = pl.program_id(0), pl.program_id(1)
    here = bi * n_hb + hj                   # programs run in this order
    last = pl.num_programs(0) * n_hb - 1

    def live_pages(b):
        return pl.cdiv(sl_ref[b], page_size)

    def fetch(p, j, slot):
        # page j of program p: one page of ``hb`` kv heads into a buffer.
        # ``per_head``: the table has a row a (sequence, head block), a
        # list of pages CHOSEN for it (``ops.sparse_attention``)
        b, h = p // n_hb, p % n_hb
        row = p if per_head else b
        return pltpu.make_async_copy(
            pool_ref.at[pl.ds(h * hb, hb), bt_ref[row * pages_per_seq + j]],
            buf.at[slot], sem.at[slot])

    seq_len = sl_ref[bi]
    n_live = live_pages(bi)
    following = jnp.minimum(here + 1, last)

    # the buffer of this program's first page is carried from program to
    # program: the one before, if it walked any page, started that copy
    # during its own last page
    @pl.when(here == 0)
    def _():
        slot_ref[0] = 0
    first = slot_ref[0]
    fetched = (here > 0) & (live_pages(jnp.maximum(here - 1, 0) // n_hb) > 0)

    @pl.when((n_live > 0) & jnp.logical_not(fetched))
    def _():
        fetch(here, 0, first).start()

    # a cached row is K | V. The pool's own dtype feeds the MXU; 8-bit
    # pages are widened to the query's (e4m3 is exact in bf16)
    cdt = q_ref.dtype if fp8 else buf.dtype
    q = q_ref[0, 0].astype(cdt)             # [rows, 2d] = [q | 0], or [rows, d]
    head = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1) // group
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def page_step(j, carry):
        m_prev, l_prev = carry              # [1, rows]: heads in the lanes
        slot = (first + j) % 2

        @pl.when(j + 1 < n_live)
        def _():
            fetch(here, j + 1, 1 - slot).start()

        @pl.when((j + 1 == n_live) & (here < last)
                 & (live_pages(following // n_hb) > 0))
        def _():
            fetch(following, 0, 1 - slot).start()

        fetch(here, j, slot).wait()
        page = bt_ref[bi * pages_per_seq + j]

        # scores with the QUERIES held in the MXU and the page streamed
        # through it: [page_size, 2d] x [2d, rows], of which a head keeps
        # its own columns. (Holding the page instead loads a 128 x 128
        # tile into the MXU for ``group`` streamed rows: 2.2 us a page of
        # 16 heads against 0.9, PERF.md PR 28.)
        s = jnp.zeros((page_size, rows), jnp.float32)
        for h in range(hb):
            k = buf[slot, h][:, :q.shape[1]].astype(cdt)
            s = jnp.where(head == h, jax.lax.dot_general(
                k, q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32), s)
        factor = scale
        if fp8:
            # dequant: stored pages are clip(x * page_scale); the scale
            # guards in amp.fp8.compute_scale keep every stored scale
            # finite and positive, so the divides are safe
            factor = jnp.full((1, rows), scale, jnp.float32)
            for h in range(hb):
                factor = jnp.where(
                    head == h, scale / ks_ref[hj * hb + h, page], factor)
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0)
        # a walked page holds at least one live row, so every column's
        # maximum is a real score and the dead rows' exp is an exact 0
        s = jnp.where(pos < seq_len, s * factor, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=0, keepdims=True)

        # values the same way round: V^T [d, page_size] streamed against
        # the probabilities [page_size, rows] held, so the accumulator is
        # [d, rows] and ``alpha`` scales it as it lies. (The whole row is
        # transposed and V^T taken as its last sublanes: slicing the V
        # lanes off first read 4% slower on the chip.)
        p = p.astype(cdt)
        pv = jnp.zeros_like(acc_scr)
        for h in range(hb):
            v_t = buf[slot, h].astype(cdt).T[width - d:]
            out = jax.lax.dot_general(
                v_t, p, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if fp8:
                out = out / vs_ref[hj * hb + h, page]
            pv = jnp.where(head == h, out, pv)
        acc_scr[...] = acc_scr[...] * alpha + pv
        return m_new, l_new

    # a dead slot is never a step and never a DMA; an inactive row
    # (seq_len 0) walks nothing and writes zeros
    _, l = jax.lax.fori_loop(
        0, n_live, page_step,
        (jnp.full((1, rows), _NEG_INF, jnp.float32),
         jnp.zeros((1, rows), jnp.float32)))
    slot_ref[0] = (first + n_live) % 2
    o_ref[0, 0] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)
                   ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "hb", "interpret",
                                             "per_head"))
def _paged_decode_call(q, kv_pages, block_tables, seq_lens, k_scales,
                       v_scales, *, scale, hb, interpret, per_head=False):
    """The kernel call with ``hb`` kv heads a program. Jitted on its own:
    a decode program makes this call once a layer on the same shapes, and
    so traces and lowers the kernel (unrolled over the heads: ~0.2 s a
    call on a host core) once, not once a layer. ``per_head``:
    ``block_tables`` is ``[b * kv_heads / hb, m]``, a list of pages a
    (sequence, head block), walked in the order given; ``seq_lens`` [b]
    then counts the rows of that list's pages that are live, and the call
    runs under the scope ``sparse_decode_attention`` (a walk of chosen
    pages is another kernel to a roofline's reader than a walk of all)."""
    b, kv_heads, group, d = q.shape
    _, _, page_size, width = kv_pages.shape
    fp8 = k_scales is not None
    n_hb, rows = kv_heads // hb, hb * group
    # the score product runs over a whole cached row where K is not a
    # lane tile of its own: the query becomes [q | 0]
    q_lanes = d if d % 128 == 0 else width
    q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, q_lanes - d)))
    q = q.reshape(b, n_hb, rows, q_lanes)

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, page_size=page_size,
        group=group, fp8=fp8, pages_per_seq=block_tables.shape[1],
        per_head=per_head)

    in_specs = []
    operands = []
    if fp8:
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM),
                     pl.BlockSpec(memory_space=pltpu.SMEM)]
        operands += [k_scales, v_scales]
    in_specs += [
        pl.BlockSpec((1, 1, rows, q_lanes),
                     lambda bi, hj, bt, sl: (bi, hj, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    operands += [q, kv_pages]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_hb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, d, rows),
                               lambda bi, hj, bt, sl: (bi, hj, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, hb, page_size, width),
                                   kv_pages.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((d, rows), jnp.float32),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    from apex_tpu.monitor import profile as _prof
    with _prof.scope("sparse_decode_attention" if per_head
                     else "paged_decode_attention"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, n_hb, d, rows), q.dtype),
            # in order: a program waits for a copy the one before started
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(block_tables.reshape(-1).astype(jnp.int32),
          seq_lens.astype(jnp.int32), *operands)
    # [b, n_hb, d, hb * group] -> [b, kv_heads, group, d]
    return out.transpose(0, 1, 3, 2).reshape(b, kv_heads, group, d)


def paged_decode_attention(q, kv_pages, block_tables, seq_lens, *,
                           scale: Optional[float] = None,
                           k_scales=None, v_scales=None,
                           interpret: Optional[bool] = None):
    """Paged single-query (decode) attention, GQA-aware. Returns
    ``[b, kv_heads, group, d]`` in ``q.dtype``.

    See the layout contract above. ``k_scales``/``v_scales`` arm the
    fp8-KV mode: pages hold e4m3 values quantized per page with the
    amp.fp8 codec and the kernel dequantizes in-VMEM — the pool in HBM
    stays 1 byte/element. Scales ride in SMEM (4 B per page per head).

    Off-TPU the kernel runs in Pallas interpret mode (same contract as
    :func:`flash_attention`); ``apex_tpu.serve`` uses
    :func:`paged_attention_reference` there instead, which is faster
    under XLA CPU.
    """
    _, kv_heads, _, d = q.shape
    kvp, _, page_size, width = kv_pages.shape
    if (kvp, width) != (kv_heads, 2 * d):
        raise ValueError(
            f"kv_pages {kv_pages.shape} does not match q {q.shape}: want "
            f"[kv_heads={kv_heads}, num_pages, page_size, 2*d={2 * d}]")
    if (k_scales is None) != (v_scales is None):
        raise ValueError("fp8-KV mode needs BOTH k_scales and v_scales")
    if page_size % 8:
        # a page is a VMEM buffer's sublane extent; the tune menu and
        # serve.cache's heuristic are both 8-aligned, but an explicit
        # page_size can reach here unrounded — fail with the contract
        # rather than a Mosaic tiling error
        raise ValueError(
            f"page_size {page_size} must be a multiple of 8 (the Pallas "
            f"sublane tile); use the reference path for odd pools")
    # kv heads a program: all of them where their page, twice, fits
    page_bytes = page_size * width * kv_pages.dtype.itemsize
    hb = max(h for h in range(1, kv_heads + 1) if kv_heads % h == 0
             and (h == 1 or 2 * h * page_bytes <= _DECODE_BUFFER_BYTES))
    return _paged_decode_call(
        q, kv_pages, block_tables, seq_lens, k_scales, v_scales,
        scale=float(d ** -0.5 if scale is None else scale), hb=hb,
        interpret=_compat.resolve_interpret(interpret))


def _merge_rows(tile_ref, buf, sem, src, lo, hi):
    """Rows ``lo <= r < hi`` of the pool tile ``tile_ref`` (HBM,
    [kv, rows, 2d]) become ``src`` ([kv, rows or 1, 2d]), the others
    stay: read the tile, merge, write it back, and wait — the next
    tile may be this one."""
    read = pltpu.make_async_copy(tile_ref, buf, sem)
    read.start()
    read.wait()
    row = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 1)
    buf[...] = jnp.where((row >= lo) & (row < hi), src, buf[...])
    write = pltpu.make_async_copy(buf, tile_ref, sem)
    write.start()
    write.wait()


def _write_rows_kernel(page_ref, slot_ref, rows_ref, _, pool_ref, buf, sem,
                       *, tile, n_rows):
    # one program moves a GROUP of rows: every tile read in flight at
    # once, the rows merged in, every tile write in flight at once
    group = buf.shape[0]
    first = pl.program_id(0) * group
    # the last group may be short: its missing rows repeat the last real
    # one (a tile that is read again and neither merged nor written)
    here = [jnp.minimum(first + i, n_rows - 1) for i in range(group)]
    page = [page_ref[r] for r in here]
    slot = [slot_ref[r] for r in here]
    base = [pl.multiple_of(s // tile * tile, tile) for s in slot]

    def copy(i, read):
        hbm = pool_ref.at[:, page[i], pl.ds(base[i], tile), :]
        src, dst = (hbm, buf.at[i]) if read else (buf.at[i], hbm)
        return pltpu.make_async_copy(src, dst, sem.at[i])

    for i in range(group):
        copy(i, True).start()
    # rows of one tile (the masked rows on the null page; two tokens of
    # one sequence) are merged into ONE buffer, that of the first of them,
    # and only that one is written: G^2/2 scalar compares, under the reads
    at = [p * pool_ref.shape[2] + b for p, b in zip(page, base)]
    owner = []
    for i in range(group):
        o = jnp.int32(i)
        for j in reversed(range(i)):
            o = jnp.where(at[j] == at[i], j, o)
        owner.append(o)
    for i in range(group):
        copy(i, True).wait()
    for i in range(group):
        def merge(i=i):
            dst = buf.at[owner[i]]
            row = jax.lax.broadcasted_iota(jnp.int32, dst.shape, 1)
            dst[...] = jnp.where(row == slot[i] - base[i], rows_ref[i],
                                 dst[...])
        if n_rows % group:
            pl.when(first + i < n_rows)(merge)
        else:
            merge()
    for i in range(group):
        pl.when(owner[i] == i)(copy(i, False).start)
    for i in range(group):
        pl.when(owner[i] == i)(copy(i, False).wait)


def _write_pages_kernel(table_ref, len_ref, rows_ref, _, pool_ref, buf, sem,
                        *, page_size, n_rows):
    # phase 0: a page's live rows to the sequence's page; phase 1: the
    # rows past the prompt's end to the null page, as the scatter does
    j, phase = pl.program_id(0), pl.program_id(1)
    here = jnp.minimum(page_size, n_rows - j * page_size)
    live = jnp.clip(len_ref[0] - j * page_size, 0, here)
    lo = jnp.where(phase == 0, 0, live)
    hi = jnp.where(phase == 0, live, here)
    page = jnp.where(phase == 0, table_ref[j], 0)

    @pl.when(lo < hi)
    def _():
        _merge_rows(pool_ref.at[:, page], buf, sem, rows_ref[...], lo, hi)


def _kv_write_call(kernel, grid, prefetch, rows, rows_spec, kv_pages,
                   scratch_shapes, interpret):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[rows_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch_shapes,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype),
        # operands: two prefetched scalars, the rows, the pool
        input_output_aliases={3: 0},
        interpret=_compat.resolve_interpret(interpret),
    )(*prefetch, rows, kv_pages)


#: rows a program of the token write unrolls over at most: its scalar
#: compares grow with the square, and so does the time a host takes to
#: trace and lower it (32 rows: 17.0 against 18.1 us a leaf of 64 rows on
#: the chip, and seconds more of a serve cell's set-up; PERF.md, PR 39)
_WRITE_GROUP_ROWS = 16


def _write_group(b, kv_heads, tile, width, itemsize):
    """Rows a program of :func:`paged_kv_write_rows` moves together: as
    many as ``_DECODE_BUFFER_BYTES`` of VMEM hold (a tile each, and a row
    each in the two buffers of the rows' pipeline, padded to a tile's
    sublanes), at most ``_WRITE_GROUP_ROWS``, spread evenly over the
    groups ``b`` rows then need."""
    row_bytes = 3 * kv_heads * tile * width * itemsize
    most = max(1, min(_WRITE_GROUP_ROWS, _DECODE_BUFFER_BYTES // row_bytes))
    return pl.cdiv(b, pl.cdiv(b, most))


def paged_kv_write_rows(kv_pages, page_ids, slots, rows, *,
                        interpret: Optional[bool] = None):
    """One token a batch row into a layer's pool, in place:
    ``kv_pages[:, page_ids[i], slots[i]] = rows[i]`` for every ``i`` in
    order. ``rows``: [b, kv_heads, 2*d] in the pool's dtype; masked
    rows carry page 0. Returns the pool (aliased to the operand).

    A program moves a group of ``G`` rows (:func:`_write_group`: from the
    tile's bytes, a VMEM budget and ``b``; the grid is ``ceil(b / G)``):
    it starts the ``G`` reads of the rows' tiles (a tile: every kv head's
    smallest row group the DMA engine addresses), waits for them, merges
    each row into its tile, starts the writes and waits for them, so a
    tile's two DMAs wait beside the group's and not alone. Rows of a group
    that share a tile are found by comparing ``(page, tile)`` with the
    earlier rows of the group and merged, in order, into the FIRST one's
    buffer, which alone is written back: a later write never undoes an
    earlier row. Groups run one after another, so rows of two groups that
    share a tile do not meet."""
    return _write_rows_call(kv_pages, page_ids, slots, rows,
                            interpret=_compat.resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _write_rows_call(kv_pages, page_ids, slots, rows, *, interpret):
    """The token write's kernel call. Jitted on its own, like the decode
    kernel's: a decode program makes this call once a layer on the same
    shapes, and so traces and lowers the kernel (unrolled over the group:
    ~0.3 s a call at 16 rows) once, not once a layer. A cached trace does
    not see its caller's scope, so the write's is named here too."""
    kv_heads, _, page_size, width = kv_pages.shape
    # the smallest row group the DMA engine addresses in the pool's
    # dtype: 8 sublanes of 32-bit words, each holding 4/itemsize rows
    itemsize = jnp.dtype(kv_pages.dtype).itemsize
    tile = 32 // itemsize
    if page_size % tile:
        tile = page_size
    b = rows.shape[0]
    group = _write_group(b, kv_heads, tile, width, itemsize)
    kernel = functools.partial(_write_rows_kernel, tile=tile, n_rows=b)
    spec = pl.BlockSpec((group, kv_heads, 1, width),
                        lambda g, pg, sl: (g, 0, 0, 0))
    scratch = [pltpu.VMEM((group, kv_heads, tile, width), kv_pages.dtype),
               pltpu.SemaphoreType.DMA((group,))]
    from apex_tpu.monitor import profile as _prof
    with _prof.scope("kv_write"):
        return _kv_write_call(
            kernel, (pl.cdiv(b, group),),
            (page_ids.astype(jnp.int32), slots.astype(jnp.int32)),
            rows[:, :, None, :], spec, kv_pages, scratch, interpret)


def paged_kv_write_pages(kv_pages, block_table, length, rows, *,
                         interpret: Optional[bool] = None):
    """A (padded) prompt's rows into a layer's pool, in place, page by
    page: position ``p < length`` goes to page ``block_table[p //
    page_size]``, slot ``p % page_size``; the positions past ``length``
    go to the null page at their slot. ``rows``: [S, kv_heads, 2*d] in
    the pool's dtype."""
    kv_heads, _, page_size, width = kv_pages.shape
    n_rows = rows.shape[0]
    n_pages = -(-n_rows // page_size)
    rows = jnp.pad(rows.transpose(1, 0, 2),
                   ((0, 0), (0, n_pages * page_size - n_rows), (0, 0)))
    kernel = functools.partial(_write_pages_kernel, page_size=page_size,
                               n_rows=n_rows)
    spec = pl.BlockSpec((kv_heads, page_size, width),
                        lambda j, phase, bt, ln: (0, j, 0))
    return _kv_write_call(
        kernel, (n_pages, 2),
        (block_table.astype(jnp.int32),
         jnp.reshape(length, (1,)).astype(jnp.int32)),
        rows, spec, kv_pages,
        [pltpu.VMEM((kv_heads, page_size, width), kv_pages.dtype),
         pltpu.SemaphoreType.DMA(())], interpret)
