"""Flash attention: Pallas TPU kernels, forward AND backward, with
in-kernel dropout and additive bias.

Reference targets (SURVEY §2.2):
- ``fmhalib`` (``apex/contrib/csrc/fmha/fmha_api.cpp:67-110`` fwd with
  p_dropout plumbing, ``:232-319`` bwd): fused MHA for packed
  variable-length sequences (cu_seqlens), seqlen ≤ 512, sm80 only;
- ``fast_multihead_attn`` (``apex/contrib/csrc/multihead_attn/*``): fused
  QKV GEMM + batched score GEMM + softmax + dropout + out-projection,
  incl. additive-mask variants.

TPU design: one flash-attention kernel family with online softmax covers
both — no seqlen cap, with **segment ids** replacing cu_seqlens for packed
varlen batches (equal-length padding-free packing, the TPU-friendly
layout), causal masking for decoder use, an optional **additive bias**
(broadcastable [b|1, h|1, sq, sk] — the additive attn-mask of the fast MHA
variants), and **in-kernel dropout** driven by a counter-based hash RNG
(murmur3 finalizer over (seed, b, h, q_pos, k_pos) — see
``_keep_from_positions``), mask regenerated identically in the backward so
no dropout mask is ever materialized in HBM.

Memory: the backward is two Pallas kernels (dk/dv with k-blocks outer and
dq with q-blocks outer), each recomputing p = exp(s - lse) blockwise from
the saved (q, k, v, out, lse) — O(s) residual memory, O(s^2) flops, the
flash-attention-2 decomposition. No [sq, sk] matrix is ever materialized
outside VMEM scratch.

Shapes: q [b, h, sq, d]; k, v [b, h, sk, d]; segment_ids int32 [b, sq]
([b, sk] for kv if lengths differ). fp32 accumulation throughout.

Default block sizes, tuned on a v5e chip (b8 h16 d64 bf16): the forward
and backward get INDEPENDENT defaults (r5 retune — the r3 single
default conflated the two phases). Forward: 1024 everywhere (256-blocks
are ~1.9x slower — per-program overhead; 2048-blocks exceed VMEM) —
even causal, where one [1024, 1024] block per s=1024 sequence beats two
512-blocks (1.33 vs 1.72 ms fwd-only): a grid step costs more than the
live-block skip saves. A PLAIN causal call (no segments, bias, dropout
or padding) skips the fully-masked half inside the program instead, in
strips of query rows that each stop at their own diagonal (``Strips``
below), forward and fused backward, and its backward then takes
1024-blocks too. Backward otherwise: causal s=1024 keeps two
512-aligned k blocks — measured 1.17 ms vs 1.29 ms fused-at-1024 and
1.66 ms two-kernel (the fused single-pass kernel runs at any n_kb since
r5; the 512 choice is purely the faster measurement); s >= 2048 uses
1024-blocks. When bias AND
dropout are both active both defaults drop to (512, 512): the extra
[block_q, block_k] fp32 bias block plus the keep mask push the 1024
config over VMEM on hardware (verified at d=128 s=2048: bias-only ok,
dropout-only ok, both fail). Blocks clamp to the sequence length for
small shapes. Per-pass VPU attribution at the GPT bench shape (measured
r5, fwd): the two MXU dots + per-program overhead are 1.24 ms of the
1.74 ms call; max-tracking 0.15 ms, exp 0.05 ms, causal mask+where
0.02 ms, acc rescale 0.17 ms — i.e. the kernel is program-count bound,
not exp-bound (exp costs the same as mul on the v5e VPU).

The paged decode kernel and the KV pool's writes (the serve path) are in
``apex_tpu.ops.paged_attention``.
"""

from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu import _compat

_NEG_INF = -1e30

# Fused single-pass backward runs while its per-(b,h) dk/dv accumulators
# (2x [sk, d] fp32 scratch + the dk/dv output blocks in their own dtype)
# leave room under Mosaic's 16 MB scoped-VMEM limit next to
# the ~10 MB of block operands and p/ds transients; beyond it the
# two-kernel flash-attention-2 decomposition takes over (~2x the
# p-recompute and q/k/v/do reads, but O(block) VMEM). Measured v5e
# b4 h16 d64 s2048 causal bf16 fwd+bwd: 8.6 ms fused vs 9.7 ms
# two-kernel; single-k-block shapes ALSO run fused since the r5
# deferred-scale/ds-reuse kernel (b32 h12 s512 d64: 3.43 -> 3.16 ms —
# the r3 n_kb >= 2 gate no longer held). The gate also counts
# bias/dropout block bytes; a bias-active shape that passes it (bf16
# d64 s2048 at 256-blocks: 1.84 MB) was verified on hardware — compiles
# under the Mosaic scoped-VMEM limit and matches the reference backward.
_FUSED_BWD_MAX_KV_BYTES = 2 * 1024 * 1024


# ---------------------------------------------------------------------------
# Reference (unfused) implementation — the parity baseline, and the O(s^2)
# fallback for tiny shapes.
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, *, causal=False, segment_ids_q=None,
                  segment_ids_kv=None, scale=None, bias=None):
    d = q.shape[-1]
    scale = d ** -0.5 if scale is None else scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    sq, sk = s.shape[-2], s.shape[-1]
    if causal:
        cm = jnp.arange(sk)[None, :] > jnp.arange(sq)[:, None] + (sk - sq)
        s = jnp.where(cm, _NEG_INF, s)
    if segment_ids_q is not None:
        sid_kv = segment_ids_q if segment_ids_kv is None else segment_ids_kv
        seg = ((segment_ids_q[:, None, :, None] == sid_kv[:, None, None, :])
               & (segment_ids_q >= 0)[:, None, :, None])
        s = jnp.where(seg, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if segment_ids_q is not None:
        # fully-masked (padding, id<0) rows: zeros, not uniform attention
        p = jnp.where(seg.any(axis=-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Shared in-kernel helpers
# ---------------------------------------------------------------------------

def _block_mask(qi, kb, block_q, block_k, causal, causal_offset,
                sq_ref, skv_ref, window=None):
    """[block_q, block_k] validity mask for block (qi, kb), or None when
    nothing masks (not causal, no segments) — skipping the two where()
    passes and the iota/compare construction saves real VPU time in the
    exp-bound d=64 regime (~6% of a BERT-base step). The unmasked case
    is only reachable with unpadded operands: ``_pad_operands`` installs
    segment ids whenever it pads."""
    if not causal and sq_ref is None:
        return None
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        # offset aligns the (original, pre-padding) sequence ends
        mask &= k_pos <= q_pos + causal_offset
    if window is not None:
        # the band's lower edge: a query sees its last ``window`` keys
        mask &= k_pos > q_pos + causal_offset - window
    if sq_ref is not None:
        sid_q = sq_ref[0]                             # [block_q, 1]
        sid_k = skv_ref[0]                            # [1, block_k]
        # negative ids are padding: they match nothing, not even each other
        mask &= (sid_q == sid_k) & (sid_q >= 0)
    return mask


def _fmix32(h):
    """murmur3 finalizer: full-avalanche 32-bit mix (public constants)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _keep_from_positions(seed, bi, hi, q_pos, k_pos, dropout_rate):
    """Counter-based dropout keep mask from *global* positions.

    A pure integer hash of (seed, batch, head, q_pos, k_pos) — no PRNG
    state, so forward and both backward kernels regenerate the identical
    mask without ever storing it (the reference stores philox offsets for
    the same purpose, ``apex/contrib/csrc/fmha/fmha_api.cpp:101``), the
    mask is independent of block-size choices, and the scheme runs
    identically on TPU hardware, in interpret mode, and in plain XLA
    (which is how the tests verify exact parity).
    """
    base = _fmix32(jnp.uint32(seed)
                   ^ (jnp.uint32(bi) * jnp.uint32(0x9E3779B1))
                   ^ (jnp.uint32(hi) * jnp.uint32(0xB5297A4D)))
    h = (q_pos.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ k_pos.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
         ^ base)
    bits = _fmix32(h)
    threshold = jnp.uint32(min(int(dropout_rate * 4294967296.0), 4294967295))
    return bits >= threshold


def dropout_keep_reference(seed, b, h, sq, sk, dropout_rate):
    """[b, h, sq, sk] keep mask exactly as the kernels generate it —
    test/debug helper (pure XLA)."""
    q_pos = jnp.arange(sq, dtype=jnp.int32)[:, None]
    k_pos = jnp.arange(sk, dtype=jnp.int32)[None, :]
    masks = jnp.stack([
        jnp.stack([_keep_from_positions(seed, bi, hi, q_pos, k_pos,
                                        dropout_rate)
                   for hi in range(h)])
        for bi in range(b)])
    return masks


def _dropout_keep(seed_ref, bi, hi, qi, kb, block_q, block_k, dropout_rate):
    """In-kernel keep mask for block (qi, kb) of grid cell (bi, hi)."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return _keep_from_positions(seed_ref[0], bi, hi, q_pos, k_pos,
                                dropout_rate)


def _causal_block_live(qi, kb, block_q, block_k, causal_offset):
    """Whether block (qi, kb) has any unmasked position under causal."""
    return kb * block_k <= qi * block_q + (block_q - 1) + causal_offset


def _causal_block_full(qi, kb, block_q, block_k, causal_offset):
    """Whether block (qi, kb) is FULLY live under causal (no masked
    entry): the last k position must be visible to the first q row.
    Fully-live blocks skip mask construction entirely — the iota pair,
    compare, and two where() passes are ~4 of the ~9 VPU passes over the
    [block_q, block_k] tile, and for causal grids roughly half the live
    blocks are full (s=1024 @ 512-blocks: 1 of 3; s=4096 @ 1024-blocks:
    6 of 10), so this is the main VPU-time lever at d=64 (measured: exp
    costs the same as mul on the v5e VPU — the kernel is pass-count
    bound, not transcendental-bound)."""
    return (kb + 1) * block_k - 1 <= qi * block_q + causal_offset


def _window_block_live(qi, kb, block_q, block_k, causal_offset, window):
    """Whether block (qi, kb) reaches into the band from below: its last
    key is seen by the block's first query row."""
    return (kb + 1) * block_k - 1 > qi * block_q + causal_offset - window


def _window_block_full(qi, kb, block_q, block_k, causal_offset, window):
    """Whether no entry of block (qi, kb) lies below the band: its first
    key is seen by the block's last query row."""
    return kb * block_k > qi * block_q + block_q - 1 + causal_offset - window


# ---------------------------------------------------------------------------
# The band: a sliding window, and key/value heads shared by a group
# ---------------------------------------------------------------------------
#
# A call with ``window`` (a query sees its last ``window`` keys) or with
# fewer key/value heads than query heads walks a grid of its own: the inner
# dimension counts only the blocks a q block (forward, dq) or a k block
# (dk/dv) can see, ``_Band.inner`` of them, and the block's real index is
# computed from the outer one (``_band_kb``, ``_band_qi``), clamped in the
# index maps, so that a block outside the band or above the diagonal is
# neither a step that multiplies nor a DMA (the clamped index repeats the
# last block's). Blocks the band's edges cross are masked
# (``_block_mask``), blocks inside run mask-free. The key/value BlockSpecs
# index ``h // group``: K and V are read as they are, never repeated. dK
# and dV of a key/value head are summed over its group INSIDE the dk/dv
# kernel's grid: its inner dimension walks the group's query heads one
# after another over the same resident K/V block and one float32
# accumulator. These calls always take the two-kernel backward. A grouped
# call that is not causal walks every block (its band is the square). A
# call without either runs the grids above, unchanged.

#: ``inner``: the inner grid extent; ``blocks``: how many blocks the inner
#: index ranges over (k blocks for the q-outer grids, q blocks for dk/dv's)
_Band = collections.namedtuple("_Band", "inner blocks causal")


def _band_kb(qi, t, band, block_q, block_k, causal_offset):
    """The k block of inner step ``t`` for q block ``qi``: the band's
    blocks end at the one the q block's diagonal crosses. Negative: none."""
    hi = band.blocks - 1
    if band.causal:
        hi = jnp.minimum(
            (qi * block_q + block_q - 1 + causal_offset) // block_k, hi)
    return hi - (band.inner - 1) + t


def _band_qi(kb, u, band, block_q, block_k, causal_offset):
    """The q block of inner step ``u`` for k block ``kb``: the band's
    blocks start at the first q block that sees the k block. Past the last
    q block: none."""
    if not band.causal:
        return u
    return jnp.maximum((kb * block_k - causal_offset) // block_q, 0) + u


def _band_plan(causal, sq_p, sk_p, block_q, block_k, causal_offset, window):
    """``(_Band of the q-outer grids, _Band of the k-outer grid, live
    blocks)`` for padded lengths: the inner extents are the most blocks any
    outer block sees; causal with ``window=None`` is the triangle."""
    n_qb, n_kb = sq_p // block_q, sk_p // block_k
    if not causal:
        return (_Band(n_kb, n_kb, False), _Band(n_qb, n_qb, False),
                n_qb * n_kb)

    def rows_of(qi):            # visible key range of a q block
        lo = 0 if window is None else \
            qi * block_q + causal_offset - window + 1
        hi = qi * block_q + block_q - 1 + causal_offset
        return max(lo, 0) // block_k, min(hi // block_k, n_kb - 1)

    def cols_of(kb):            # q blocks that see a k block
        lo = max((kb * block_k - causal_offset) // block_q, 0)
        hi = n_qb - 1 if window is None else min(
            (kb * block_k + block_k - 1 - causal_offset + window - 1)
            // block_q, n_qb - 1)
        return lo, hi

    spans = [rows_of(qi) for qi in range(n_qb)]
    live = sum(hi - lo + 1 for lo, hi in spans if hi >= lo)
    k_inner = max(max(hi - lo + 1 for lo, hi in spans), 1)
    q_inner = max(max(hi - lo + 1 for lo, hi in map(cols_of, range(n_kb))),
                  1)
    return _Band(k_inner, n_kb, True), _Band(q_inner, n_qb, True), live


def _dispatch_causal(compute, causal, use_segments, qi, kb, block_q,
                     block_k, causal_offset, skip_dead=True, strips=None,
                     window=None, alive=None):
    """Run ``compute(masked: bool)`` under the right predication — shared
    by all four kernels. Causal without segments splits live blocks into
    fully-live (mask-free, see ``_causal_block_full``; bit-identical
    since where(True, s, _) is the identity) and diagonal (mask built
    and applied); causal with segments predicates on liveness only; all
    other shapes run unconditionally, masked iff segments are present.

    ``skip_dead=False`` (the single-k-block FORWARD): dead causal blocks
    must still run the masked compute — the n_kb==1 specialization
    writes o/lse inside ``compute``, so a skipped block would leave its
    output block uninitialized (VMEM garbage on hardware). The mask +
    dead-row guard turn those rows into zeros/-1e30 lse, matching the
    carry path's initialized-scratch behavior.

    ``strips`` (a :class:`_StripPlan`; plain causal only): the block the
    diagonal crosses runs ``compute(masked, strip)`` once a strip of
    query rows, each against the keys up to its own diagonal.

    ``alive`` (the banded grids): whether this inner step is a block at
    all; ``window`` adds the band's lower edge to what is live and to what
    is full."""
    if alive is not None:
        args = (qi, kb, block_q, block_k, causal_offset)
        live, full = alive, True
        if causal:
            live &= _causal_block_live(*args)
            full = _causal_block_full(*args)
        if window is not None:
            live &= _window_block_live(*args, window)
            full &= _window_block_full(*args, window)
        if use_segments or not causal:
            pl.when(live)(lambda: compute(use_segments))
        else:
            pl.when(live & full)(lambda: compute(False))
            pl.when(live & jnp.logical_not(full))(lambda: compute(True))
    elif strips is not None:
        rel = qi * block_q + causal_offset - kb * block_k
        if strips.any_full:
            pl.when(rel >= block_k - 1)(lambda: compute(False))

        @pl.when(rel == strips.rel)
        def _diagonal():
            for strip in _strips_of(strips, block_q, block_k,
                                    keep_dead=not skip_dead):
                compute(strip.masked, strip)
    elif causal and not use_segments:
        full = _causal_block_full(qi, kb, block_q, block_k, causal_offset)
        pl.when(full)(lambda: compute(False))
        rest = jnp.logical_not(full)
        if skip_dead:
            rest &= _causal_block_live(qi, kb, block_q, block_k,
                                       causal_offset)
        pl.when(rest)(lambda: compute(True))
    elif causal:
        if skip_dead:
            live = _causal_block_live(qi, kb, block_q, block_k,
                                      causal_offset)
            pl.when(live)(lambda: compute(True))
        else:
            compute(True)
    else:
        compute(use_segments)


# ---------------------------------------------------------------------------
# Strips: the causal triangle inside a program
# ---------------------------------------------------------------------------
#
# A program that holds the block the diagonal crosses (at s = 1,024 THE
# block: one [1024, 1024] program a (batch, head)) used to multiply the
# whole square and mask half of it away. It now walks strips of ``r``
# query rows; strip ``i`` multiplies against the keys up to its own
# diagonal only (static slices of the refs), so the dead rectangle above
# it is never touched: no more VMEM, no more grid steps. Measured on a v5e
# (b8 h16 s1024 d64 bf16, ms a call; PERF.md, PR 44 and PR 45): forward
# 0.533 -> 0.444 at r = 512 (0.469 at 256); fused backward 1.219 (two
# 512-blocks) -> 0.915 at block 1,024, r = 256 (1.040 at 512). Tiling
# the KEYS as well inside a program lost at every tile shape, and
# 512-blocks with strips gain nothing (the grid step is what costs). The
# two matmuls are 0.39 of the 0.53 ms and removing the exp changes
# nothing: the MXU, half filled at d = 64, binds, so products not
# computed are the lever.
#
# ``r`` follows from the direction, the block and ``d``; no argument sets
# it. A call that is not causal, or carries segments (padding installs
# them), a bias or dropout, or whose diagonal crosses its blocks at more
# than one place, has no plan and runs the whole-block program.

_Strip = collections.namedtuple("_Strip", "rows cols shift masked")
_StripPlan = collections.namedtuple("_StripPlan", "rows rel any_full")


def _strip_rows(direction, block_q, d):
    """Query rows a strip: 512 forward and 256 backward (half that past
    d = 128, where a strip's products are as long again), halved until
    it divides the block into two strips or more; ``block_q`` = none."""
    r = (512 if d <= 128 else 256) // (1 if direction == "fwd" else 2)
    while r >= 128 and (block_q % r or r >= block_q):
        r //= 2
    return r if r >= 128 else block_q


def _block_rels(sq, sk, block_q, block_k):
    """``rel`` of every block of the grid: row ``j`` of block (qi, kb)
    sees the block's columns ``<= j + rel`` under causal."""
    return [qi * block_q + (sk - sq) - kb * block_k
            for qi in range(sq // block_q) for kb in range(sk // block_k)]


def _strip_plan(direction, causal, plain, sq, sk, block_q, block_k, d):
    """The :class:`_StripPlan` of a call, or None where it runs whole
    blocks. ``plain``: no segments, no bias, no dropout. ``sq``, ``sk``
    unpadded: a length its block does not divide is padded, and padding
    installs segments."""
    block_q, block_k = min(block_q, sq), min(block_k, sk)
    if not (causal and plain) or sq % block_q or sk % block_k \
            or block_k % 128:
        return None
    r = _strip_rows(direction, block_q, d)
    if r >= block_q:
        return None
    rels = _block_rels(sq, sk, block_q, block_k)
    # the forward's single k block writes its outputs inside the compute,
    # dead rows too, so its dead blocks are the diagonal's business
    keep_dead = direction == "fwd" and sk == block_k
    diagonal = {rel for rel in rels if rel < block_k - 1
                and (keep_dead or rel > -block_q)}
    if len(diagonal) != 1:
        return None
    return _StripPlan(r, diagonal.pop(),
                      any(rel >= block_k - 1 for rel in rels))


def _strips_of(plan, block_q, block_k, keep_dead=False):
    """The strips of the diagonal's block, top to bottom. Row ``j`` of a
    strip sees columns ``<= j + shift``; ``cols`` ends at the strip's
    last row's diagonal. A strip that sees nothing is left out, or kept
    on one lane tile of masked columns (``keep_dead``: the caller has to
    write its rows)."""
    r = plan.rows
    out = []
    for i in range(block_q // r):
        shift = plan.rel + i * r
        n = min(max(shift + r, 0), block_k)
        if n == 0:
            if not keep_dead:
                continue
            n = 128
        out.append(_Strip(pl.ds(i * r, r), pl.ds(0, n), shift,
                          masked=shift < n - 1))
    return out


def _tile_mask(masked, strip, *block_mask_args):
    """The validity mask of what a ``compute(masked, strip)`` multiplies:
    None, the strip's own diagonal, or ``_block_mask`` of the whole
    block."""
    if not masked:
        return None
    if strip is None:
        return _block_mask(*block_mask_args)
    r, n = strip.rows.size, strip.cols.size
    row = jax.lax.broadcasted_iota(jnp.int32, (r, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (r, n), 1)
    return col <= row + strip.shift


def _tiles(plan, causal, sq, sk, block_q, block_k, skip_dead=True):
    """(computed, square): the 128 x 128 tiles of scores a (batch, head)
    of the call multiplies, and those of its whole ``sq`` x ``sk`` sheet.
    Whole blocks but for the dead ones a causal grid skips and, under a
    plan, the diagonal's strips."""
    unit = 128.0 * 128.0
    computed = 0
    for rel in _block_rels(sq, sk, block_q, block_k):
        if plan is not None and rel == plan.rel:
            computed += sum(s.rows.size * s.cols.size for s in _strips_of(
                plan, block_q, block_k, keep_dead=not skip_dead))
        elif not (causal and skip_dead and rel <= -block_q):
            computed += block_q * block_k
    return computed / unit, sq * sk / unit


def _count_tiles(direction, batch_heads, plan, causal, sq, sk, block_q,
                 block_k, skip_dead=True):
    """``flash/tiles_computed`` and ``flash/tiles_square`` by direction,
    once a trace of the kernel's call: their ratio says how much of the
    square a call multiplies (0.75 forward and 0.625 backward at s =
    1,024; 1.0 where nothing can be skipped)."""
    from apex_tpu.monitor import hooks as _mon
    computed, square = _tiles(plan, causal, sq, sk, block_q, block_k,
                              skip_dead)
    _mon.counter("flash/tiles_computed", batch_heads * computed,
                 direction=direction)
    _mon.counter("flash/tiles_square", batch_heads * square,
                 direction=direction)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, block_q, block_k, use_segments,
                use_bias, dropout_rate, causal_offset, single_kb=False,
                strips=None, window=None, band=None):
    it = iter(refs)
    sq_ref = next(it) if use_segments else None
    skv_ref = next(it) if use_segments else None
    bias_ref = next(it) if use_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = it

    bi, hi, qi, kb = (pl.program_id(0), pl.program_id(1),
                      pl.program_id(2), pl.program_id(3))
    n_kb = pl.num_programs(3)
    step, alive = kb, None          # the inner step IS the k block, or:
    if band is not None:
        kb = _band_kb(qi, step, band, block_q, block_k, causal_offset)
        alive = kb >= 0

    if not single_kb:
        @pl.when(step == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute(masked, strip=None):
        # the whole block, or one strip of its query rows against the keys
        # up to that strip's diagonal (``_dispatch_causal``)
        rows = slice(None) if strip is None else strip.rows
        cols = slice(None) if strip is None else strip.cols
        # operands stay in their native dtype: the MXU multiplies bf16
        # pairs exactly and accumulates fp32 (preferred_element_type), so
        # upcasting first changes nothing numerically but forces Mosaic's
        # multi-pass fp32 matmul (~3x slower)
        q = q_ref[0, 0, rows]                            # [block_q, d]
        k = k_ref[0, 0, cols]                            # [block_k, d]
        v = v_ref[0, 0, cols]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if scale != 1.0:
            s = s * scale
        if use_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)

        mask = _tile_mask(masked, strip, qi, kb, block_q, block_k, causal,
                          causal_offset, sq_ref, skv_ref, window)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)

        if single_kb:
            # n_kb == 1 specialization (r5): every row sees its FULL key
            # range in this one block, so the online-softmax carry —
            # m/l scratch round trips, alpha rescale, acc_scr
            # init/mul/readback — is pure overhead. Compute the exact
            # softmax and write the outputs directly.
            # floor at _NEG_INF like the carry path's m_prev init: an
            # all -inf additive-bias row otherwise gives m = -inf and
            # s - m = NaN (the old path returned a zero row)
            m = jnp.maximum(jnp.max(s, axis=1, keepdims=True), _NEG_INF)
            p = jnp.exp(s - m)
            if mask is not None and (use_segments or use_bias
                                     or causal_offset < 0):
                p = jnp.where(mask, p, 0.0)      # dead-row guard (below)
            l = jnp.sum(p, axis=1, keepdims=True)
            if dropout_rate > 0.0:
                keep = _dropout_keep(seed_ref, bi, hi, qi, kb, block_q,
                                     block_k, dropout_rate)
                p = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
            acc = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            safe_l = jnp.where(l > 0, l, 1.0)
            o_ref[0, 0, rows] = (acc / safe_l).astype(o_ref.dtype)
            lse_ref[0, 0, 0, rows] = jnp.reshape(m + jnp.log(safe_l),
                                                 (q.shape[0],))
            return

        m_prev = m_scr[rows]                              # [block_q, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        if mask is not None and (use_segments or use_bias
                                 or causal_offset < 0):
            # guard rows whose row max is the masked fill (m_new ==
            # -1e30, so exp(s - m_new) = 1, not 0): segment padding
            # rows, sq > sk rows with no visible k, or a -inf additive
            # bias row pushing every live score below -1e30 can produce
            # them — under plain causal with sq <= sk and no bias,
            # k position 0 is live for every row from the first
            # (kb == 0) block on, so m_new is finite and masked entries
            # underflow to an exact 0 without the where() pass
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[rows] + jnp.sum(p, axis=1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _dropout_keep(seed_ref, bi, hi, qi, kb, block_q, block_k,
                                 dropout_rate)
            # dropout applies to the normalized p; l (the normalizer) uses
            # the undropped sum, so scale only the accumulated numerator
            p = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
        # p rounds to the v dtype for the MXU (flash-attention-2 practice;
        # fp32 v inputs keep an exact fp32 product)
        acc_scr[rows] = acc_scr[rows] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[rows] = m_new
        l_scr[rows] = l_new

    _dispatch_causal(_compute, causal, use_segments, qi, kb, block_q,
                     block_k, causal_offset, skip_dead=not single_kb,
                     strips=strips, window=window, alive=alive)

    if not single_kb:
        @pl.when(step == n_kb - 1)
        def _finish():
            l = l_scr[:]
            safe_l = jnp.where(l > 0, l, 1.0)
            o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
            # lse is [b, h, 1, sq] (sequence on the lane dim: a
            # [..., sq, 1] layout pads the trailing unit dim to 128
            # lanes — 128x memory and DMA traffic); the [block_q, 1]
            # scratch relayouts to lanes here, once per q-block
            lse_ref[0, 0, 0] = jnp.reshape(m_scr[:] + jnp.log(safe_l),
                                           (block_q,))


def _pad_operands(q, k, v, segment_ids_q, segment_ids_kv, bias, do,
                  block_q, block_k):
    """Pad seq dims to block multiples; padded positions get segment id -1."""
    b, _, sq, _ = q.shape
    sk = k.shape[2]
    pad_q = -sq % block_q
    pad_k = -sk % block_k
    if pad_q or pad_k:
        if segment_ids_q is None:
            segment_ids_q = jnp.zeros((b, sq), jnp.int32)
            segment_ids_kv = jnp.zeros((b, sk), jnp.int32)
        elif segment_ids_kv is None:
            segment_ids_kv = segment_ids_q
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        segment_ids_q = jnp.pad(segment_ids_q, ((0, 0), (0, pad_q)),
                                constant_values=-1)
        segment_ids_kv = jnp.pad(segment_ids_kv, ((0, 0), (0, pad_k)),
                                 constant_values=-1)
        if bias is not None:
            bias = jnp.pad(bias, ((0, 0), (0, 0), (0, pad_q), (0, pad_k)))
        if do is not None:
            do = jnp.pad(do, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    elif segment_ids_q is not None and segment_ids_kv is None:
        segment_ids_kv = segment_ids_q
    return q, k, v, segment_ids_q, segment_ids_kv, bias, do, pad_q, pad_k


def _grid_block(g, dim):
    return dim(*g) if callable(dim) else g[dim]


def _seg_specs(block_q, block_k, qdim, kdim):
    """BlockSpecs for the [b, sq, 1] / [b, 1, sk] segment-id layouts.

    ``qdim``/``kdim``: which grid dim indexes q-blocks / k-blocks, or (the
    banded grids) a function of the grid indices that gives the block.
    """
    def qmap(*g):
        return (g[0], _grid_block(g, qdim), 0)

    def kmap(*g):
        return (g[0], 0, _grid_block(g, kdim))

    return [pl.BlockSpec((1, block_q, 1), qmap),
            pl.BlockSpec((1, 1, block_k), kmap)]


def _bias_spec(bias, block_q, block_k, qdim, kdim, hdim=1):
    bb, bh = bias.shape[0], bias.shape[1]

    def bmap(*g):
        return (g[0] if bb > 1 else 0, _grid_block(g, hdim) if bh > 1 else 0,
                _grid_block(g, qdim), _grid_block(g, kdim))

    return pl.BlockSpec((1, 1, block_q, block_k), bmap)


# Negative result (measured, v5e): folding the softmax scale into q
# before the kernel (to skip the per-block s*scale VPU pass) changed
# NOTHING — 8.09 vs 7.95 ms/call on the BERT-shape fwd+bwd microbench.
# Mosaic already handles the scalar epilogue efficiently; the kernels
# keep the straightforward `s * scale` (guarded for callers passing 1.0).


# The forward's and the backward's kernel calls are jitted on their own,
# like ``_paged_decode_call`` and ``_write_rows_call`` of
# ``ops/paged_attention.py``: a model makes them
# once a LAYER on the same shapes, and a Pallas call is traced (the kernel
# body, unrolled over its strips) and lowered (its Mosaic module) once a
# call site at every lowering of the program around it, compile cache hit
# or not. Behind ``jax.jit`` the N layers of a program share one trace and
# one lowered function (PERF.md, PR 44: 36 layers x three programs cost
# cell 4 twenty seconds of set-up where the compiler's time had not moved).
# A cached trace does not see its caller's scope, so the scopes that name
# the compiled instructions (``apx_flash_attention_fwd`` / ``_bwd``: a
# device trace tells the directions apart by them) are inside.

@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10, 11, 12, 13))
def _flash_fwd_impl(q, k, v, segment_ids_q, segment_ids_kv, bias, seed,
                    scale, causal, dropout_rate, block_q, block_k, interpret,
                    window=None):
    from apex_tpu.monitor import profile as _prof
    # a window call's instructions carry a name of their own, so that a
    # device trace tells a model's window layers from its full ones
    with _prof.scope("flash_attention_window_fwd" if window
                     else "flash_attention_fwd"):
        return _flash_fwd(q, k, v, segment_ids_q, segment_ids_kv, bias, seed,
                          scale, causal, dropout_rate, block_q, block_k,
                          interpret, window)


def _banded(q, k, window):
    """Whether a call walks the banded grids (``_Band``)."""
    return window is not None or k.shape[1] != q.shape[1]


#: the banded kernels hold [block_q, block_k] float32 scores and their
#: gradient at d = 128 and 1,024-blocks: over Mosaic's 16 MB default scope
_BAND_VMEM_LIMIT = 64 * 1024 * 1024


#: a banded call's default blocks: 1,024 but for the BACKWARD under a window,
#: 512. Measured on a v5e at b2 h32/4 s8192 d128 bf16 (PERF.md, PR 46), ms a
#: call, blocks 256 / 512 / 1,024: window 1,024 forward 11.3 / 6.3 / 4.8,
#: its backward 14.8 / 9.2 / 11.2; no window forward 41.4 / 17.8 / 10.1, its
#: backward 58.7 / 28.6 / 25.6. A grid step costs more than the band's
#: tighter fit saves, except where the two-kernel backward multiplies a
#: window's edge blocks twice
_BAND_BLOCK, _BAND_BLOCK_BWD_WINDOW = 1024, 512


def _band_params():
    from apex_tpu._compat import tpu_compiler_params
    return tpu_compiler_params(vmem_limit_bytes=_BAND_VMEM_LIMIT)


def _count_band_tiles(direction, batch_heads, window, live, sq_p, sk_p,
                      block_q, block_k):
    """``flash/tiles_*`` of a banded call (``_count_tiles``), with
    ``attention=window|full`` beside the direction (``kind`` is the
    recorder's own field): the blocks its grid multiplies, whole, over the
    square's."""
    from apex_tpu.monitor import hooks as _mon
    kind = "window" if window is not None else "full"
    unit = 128.0 * 128.0
    _mon.counter("flash/tiles_computed",
                 batch_heads * live * block_q * block_k / unit,
                 direction=direction, attention=kind)
    _mon.counter("flash/tiles_square", batch_heads * sq_p * sk_p / unit,
                 direction=direction, attention=kind)


def _flash_fwd(q, k, v, segment_ids_q, segment_ids_kv, bias, seed,
               scale, causal, dropout_rate, block_q, block_k, interpret,
               window=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    causal_offset = sk - sq   # aligns the original sequence ends
    banded = _banded(q, k, window)
    strips = None if banded else _strip_plan(
        "fwd", causal, segment_ids_q is None and bias is None
        and dropout_rate == 0.0, sq, sk, block_q, block_k, d)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    (q, k, v, segment_ids_q, segment_ids_kv, bias, _, pad_q, pad_k
     ) = _pad_operands(q, k, v, segment_ids_q, segment_ids_kv, bias, None,
                       block_q, block_k)
    sq_p, sk_p = sq + pad_q, sk + pad_k
    use_segments = segment_ids_q is not None
    use_bias = bias is not None

    grid = (b, h, sq_p // block_q, sk_p // block_k)
    single_kb = sk_p // block_k == 1 and not banded
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, use_segments=use_segments, use_bias=use_bias,
        dropout_rate=dropout_rate, causal_offset=causal_offset,
        single_kb=single_kb, strips=strips)
    kdim, kv_map, params = 3, (lambda b_, h_, qi, ki: (b_, h_, ki, 0)), {}
    if banded:
        band, _, live = _band_plan(causal, sq_p, sk_p, block_q, block_k,
                                   causal_offset, window)
        kernel = functools.partial(kernel, window=window, band=band)
        grid = grid[:3] + (band.inner,)
        params = dict(compiler_params=_band_params())
        _count_band_tiles("fwd", b * h, window, live, sq_p, sk_p, block_q,
                          block_k)
        group = h // k.shape[1]

        def kdim(*g):       # the k block of a (q block, inner step)
            return jnp.maximum(_band_kb(g[2], g[3], band, block_q, block_k,
                                        causal_offset), 0)

        def kv_map(*g):
            return g[0], g[1] // group, kdim(*g), 0
    else:
        _count_tiles("fwd", b * h, strips, causal, sq_p, sk_p, block_q,
                     block_k, skip_dead=not single_kb)

    # Mosaic requires the last two block dims to be (8k, 128k) or equal to
    # the array dims — trailing-singleton layouts (b, sq, 1) / (b, 1, sk)
    # tile the 1D id vectors with no broadcast cost.
    in_specs = []
    operands = []
    if use_segments:
        in_specs += _seg_specs(block_q, block_k, qdim=2, kdim=kdim)
        operands += [segment_ids_q[:, :, None], segment_ids_kv[:, None, :]]
    if use_bias:
        in_specs += [_bias_spec(bias, block_q, block_k, qdim=2, kdim=kdim)]
        operands += [bias]
    if dropout_rate > 0.0:
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)]
        operands += [seed]
    in_specs += [
        pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        pl.BlockSpec((1, 1, block_k, d), kv_map),
        pl.BlockSpec((1, 1, block_k, d), kv_map),
    ]
    operands += [q, k, v]

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b_, h_, qi, ki: (b_, h_, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, sq_p), jnp.float32),
        ],
        scratch_shapes=(
            # minimal-tile dummies when single_kb: the specialization
            # never touches the carry scratch, and (block_q, d) fp32
            # would waste ~256 KB of the VMEM the block defaults are
            # budgeted against (measured perf-neutral)
            [pltpu.VMEM((8, 128), jnp.float32)] * 3
            if single_kb else [
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ]),
        interpret=interpret,
        **params,
    )(*operands)
    return out[:, :, :sq], lse[:, :, 0, :sq]


# ---------------------------------------------------------------------------
# Pallas backward kernels (flash-attention-2 decomposition)
# ---------------------------------------------------------------------------

def _recompute_p(q_ref, k_ref, lse_ref, bias_ref, mask, scale, guard,
                 rows=slice(None), cols=slice(None)):
    """p = exp(s - lse), zeroed where masked. [block_q, block_k], or the
    strip ``rows`` x ``cols`` of it.
    ``mask=None`` = fully live (a non-masking shape, or a fully-live
    causal block — see ``_causal_block_full``), so the where() passes are
    skipped. ``guard``: whether rows with lse == -1e30 (segment padding)
    or +inf blowups (sq > sk fully-masked rows) can exist — when False
    (plain causal, sq <= sk) the post-exp where() is skipped too: masked
    entries have s = -1e30 and finite lse, so exp underflows to exact 0."""
    q = q_ref[0, 0, rows]          # native dtype: bf16 MXU path (see fwd)
    k = k_ref[0, 0, cols]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0].astype(jnp.float32)
    lse_col = lse_ref[0, 0, 0, rows][:, None]    # [block_q, 1] (relayout)
    if mask is None:
        return jnp.exp(s - lse_col)
    s = jnp.where(mask, s, _NEG_INF)
    p = jnp.exp(s - lse_col)
    if guard:
        p = jnp.where(mask, p, 0.0)
    return p


def _p_dp_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
             seed_ref, mask, scale, dropout_rate,
             bi, hi, qi, kb, block_q, block_k, guard,
             rows=slice(None), cols=slice(None)):
    """Shared backward-block math: recompute p, form dp and ds, of the
    block or of its strip ``rows`` x ``cols``.

    Returns ``(p_drop, do, ds)``. The dropout-backward rule lives ONLY
    here: ``ds`` multiplies the UNdropped ``p`` while ``dp`` is
    masked-and-rescaled, and ``p_drop`` (masked+rescaled) feeds dv.

    NOTE: ``ds`` is returned UNSCALED — callers multiply the softmax
    scale into the [*, d] dk/dq accumulators at their finish step
    instead of paying a [block_q, block_k] multiply per block pair
    (block_k/d = 8x fewer elements, and the fp32 post-dot multiply is
    numerically at least as good as scaling ds before its bf16 cast).
    """
    p = _recompute_p(q_ref, k_ref, lse_ref, bias_ref, mask, scale, guard,
                     rows, cols)
    do = do_ref[0, 0, rows]                               # [block_q, d]
    dp = jax.lax.dot_general(
        do, v_ref[0, 0, cols], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if dropout_rate > 0.0:
        keep = _dropout_keep(seed_ref, bi, hi, qi, kb, block_q, block_k,
                             dropout_rate)
        inv = 1.0 / (1.0 - dropout_rate)
        p_drop = jnp.where(keep, p, 0.0) * inv
        dp = jnp.where(keep, dp, 0.0) * inv
    else:
        p_drop = p
    ds = p * (dp - delta_ref[0, 0, 0, rows][:, None])
    return p_drop, do, ds


def _dkdv_kernel(*refs, scale, causal, block_q, block_k, use_segments,
                 use_bias, dropout_rate, causal_offset, window=None,
                 band=None, group=1):
    it = iter(refs)
    sq_ref = next(it) if use_segments else None
    skv_ref = next(it) if use_segments else None
    bias_ref = next(it) if use_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dk_ref, dv_ref, dk_scr, dv_scr) = it

    bi, hi, kb, qi = (pl.program_id(0), pl.program_id(1),
                      pl.program_id(2), pl.program_id(3))
    n_qb = pl.num_programs(3)
    step, alive = qi, None          # the inner step IS the q block, or:
    if band is not None:
        # the group's query heads one after another, each over the band's
        # q blocks; ``hi`` the query head (the dropout mask is keyed by it)
        hi = hi * group + step // band.inner
        qi = _band_qi(kb, step % band.inner, band, block_q, block_k,
                      causal_offset)
        alive = qi < band.blocks
    guard = use_segments or use_bias or causal_offset < 0

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute(masked):
        mask = (_block_mask(qi, kb, block_q, block_k, causal, causal_offset,
                            sq_ref, skv_ref, window) if masked else None)
        p_drop, do, ds = _p_dp_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
            seed_ref, mask, scale, dropout_rate, bi, hi, qi, kb,
            block_q, block_k, guard)
        # dv += p_drop^T @ do : [block_k, d]
        dv_scr[:] += jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dk += ds^T @ q : [block_k, d] (softmax scale applied at finish)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0, 0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _dispatch_causal(_compute, causal, use_segments, qi, kb, block_q,
                     block_k, causal_offset, window=window, alive=alive)

    @pl.when(step == n_qb - 1)
    def _finish():
        dk = dk_scr[:] * scale if scale != 1.0 else dk_scr[:]
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(*refs, scale, causal, block_q, block_k, use_segments,
                      use_bias, dropout_rate, causal_offset, strips=None):
    """Single-pass backward: dq accumulated per q-block (resident across
    the inner k loop) while dk/dv accumulate into full-[sk, d] fp32 VMEM
    scratch for the whole (b, h) cell. Recomputes p = exp(s - lse) ONCE
    per block pair — the two-kernel decomposition pays that recompute
    (and a full read of q/k/v/do) twice. Used when the [sk, d] scratch
    fits VMEM; the two-kernel path remains for longer sequences."""
    it = iter(refs)
    sq_ref = next(it) if use_segments else None
    skv_ref = next(it) if use_segments else None
    bias_ref = next(it) if use_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr) = it

    bi, hi, qi, kb = (pl.program_id(0), pl.program_id(1),
                      pl.program_id(2), pl.program_id(3))
    n_qb, n_kb = pl.num_programs(2), pl.num_programs(3)
    guard = use_segments or use_bias or causal_offset < 0

    @pl.when((qi == 0) & (kb == 0))
    def _init_kv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(kb == 0)
    def _init_q():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute(masked, strip=None):
        # the whole block, or one strip of its query rows against the keys
        # up to that strip's diagonal (``_dispatch_causal``)
        rows = slice(None) if strip is None else strip.rows
        cols = slice(None) if strip is None else strip.cols
        mask = _tile_mask(masked, strip, qi, kb, block_q, block_k, causal,
                          causal_offset, sq_ref, skv_ref)
        p_drop, do, ds = _p_dp_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
            seed_ref, mask, scale, dropout_rate, bi, hi, qi, kb,
            block_q, block_k, guard, rows, cols)
        kv = pl.ds(kb * block_k,
                   block_k if strip is None else strip.cols.size)
        dv_scr[kv, :] += jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # ds rounds to the operand dtype ONCE and feeds both the dk and
        # dq dots (q/k share a dtype on every real path); softmax scale
        # applies at the [*, d] finish, not per [block_q, block_k] block
        dsc = ds.astype(q_ref.dtype)
        dk_scr[kv, :] += jax.lax.dot_general(
            dsc, q_ref[0, 0, rows], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_scr[rows] += jax.lax.dot_general(
            dsc.astype(k_ref.dtype), k_ref[0, 0, cols],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    _dispatch_causal(_compute, causal, use_segments, qi, kb, block_q,
                     block_k, causal_offset, strips=strips)

    @pl.when(kb == n_kb - 1)
    def _finish_q():
        dq = dq_scr[...] * scale if scale != 1.0 else dq_scr[...]
        dq_ref[0, 0] = dq.astype(dq_ref.dtype)

    @pl.when((qi == n_qb - 1) & (kb == n_kb - 1))
    def _finish_kv():
        dk = dk_scr[...] * scale if scale != 1.0 else dk_scr[...]
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(*refs, scale, causal, block_q, block_k, use_segments,
               use_bias, dropout_rate, causal_offset, window=None, band=None):
    it = iter(refs)
    sq_ref = next(it) if use_segments else None
    skv_ref = next(it) if use_segments else None
    bias_ref = next(it) if use_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = it

    bi, hi, qi, kb = (pl.program_id(0), pl.program_id(1),
                      pl.program_id(2), pl.program_id(3))
    n_kb = pl.num_programs(3)
    step, alive = kb, None          # as in ``_fwd_kernel``
    if band is not None:
        kb = _band_kb(qi, step, band, block_q, block_k, causal_offset)
        alive = kb >= 0
    guard = use_segments or use_bias or causal_offset < 0

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute(masked):
        mask = (_block_mask(qi, kb, block_q, block_k, causal, causal_offset,
                            sq_ref, skv_ref, window) if masked else None)
        _, _, ds = _p_dp_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
            seed_ref, mask, scale, dropout_rate, bi, hi, qi, kb,
            block_q, block_k, guard)
        # dq += ds @ k : [block_q, d] (softmax scale applied at finish)
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _dispatch_causal(_compute, causal, use_segments, qi, kb, block_q,
                     block_k, causal_offset, window=window, alive=alive)

    @pl.when(step == n_kb - 1)
    def _finish():
        dq = dq_scr[:] * scale if scale != 1.0 else dq_scr[:]
        dq_ref[0, 0] = dq.astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "dropout_rate", "block_q", "block_k", "interpret",
    "window"))
def _flash_bwd_impl(res, do, *, scale, causal, dropout_rate, block_q,
                    block_k, interpret, window=None):
    from apex_tpu.monitor import profile as _prof
    with _prof.scope("flash_attention_window_bwd" if window
                     else "flash_attention_bwd"):
        return _flash_bwd(res, do, scale=scale, causal=causal,
                          dropout_rate=dropout_rate, block_q=block_q,
                          block_k=block_k, interpret=interpret,
                          window=window)


def _bwd_fused(sk_p, d, k_dtype, v_dtype, use_bias, dropout_rate, block_q,
               block_k):
    """Whether the backward runs as the fused single-pass kernel: its
    [sk, d] dk/dv accumulators (fp32 scratch pair + the output blocks in
    their own dtype) fit the scoped-VMEM budget. r5 re-measure: the old
    n_kb >= 2 gate (single-block fused had measured slightly slower in
    r3) no longer holds with the deferred-scale/ds-reuse kernel — fused
    wins at every single-k-block shape tried (b32 h12 s512 d64: 3.43 ->
    3.16 ms; b8 h16 s512 d64: 1.61 -> 1.25; b4 h16 s512 d128: 0.93 ->
    0.91)."""
    kv_bytes = sk_p * d * (8 + jnp.dtype(k_dtype).itemsize
                           + jnp.dtype(v_dtype).itemsize)
    # bias rides as an extra [block_q, block_k] fp32 operand block and
    # dropout regenerates a same-shape keep mask in VMEM; the 2 MB cap
    # was measured without either, so count them against the same gate
    # (at the default 1024 blocks this routes bias/dropout shapes to the
    # two-kernel path, which keeps O(block) VMEM)
    if use_bias:
        kv_bytes += 4 * block_q * block_k
    if dropout_rate > 0.0:
        kv_bytes += 4 * block_q * block_k
    return kv_bytes <= _FUSED_BWD_MAX_KV_BYTES


def _bwd_strip_plan(causal, plain, sq, sk, block_q, block_k, d, k_dtype,
                    v_dtype):
    """The backward's :class:`_StripPlan`: the fused kernel's alone (the
    two kernels of a longer sequence run whole blocks)."""
    plan = _strip_plan("bwd", causal, plain, sq, sk, block_q, block_k, d)
    if plan is not None and not _bwd_fused(sk, d, k_dtype, v_dtype, False,
                                           0.0, block_q, block_k):
        return None
    return plan


def _flash_bwd(res, do, *, scale, causal, dropout_rate, block_q, block_k,
               interpret, window=None):
    q, k, v, out, lse, sid_q, sid_kv, bias, seed = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    causal_offset = sk - sq
    banded = _banded(q, k, window)
    strips = None if banded else _bwd_strip_plan(
        causal, sid_q is None and bias is None and dropout_rate == 0.0,
        sq, sk, block_q, block_k, d, k.dtype, v.dtype)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)

    # delta = rowsum(do * o) — the softmax-Jacobian contraction term.
    # Both row vectors ride as [b, h, 1, sq] (sequence on lanes): a
    # [..., sq, 1] layout would pad the unit dim to 128 lanes.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]              # [b, h, 1, sq]
    lse4 = lse[:, :, None, :]                            # [b, h, 1, sq]

    (q_p, k_p, v_p, sid_q, sid_kv, bias, do_p, pad_q, pad_k
     ) = _pad_operands(q, k, v, sid_q, sid_kv, bias, do, block_q, block_k)
    if pad_q:
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, 0), (0, pad_q)))
        lse4 = jnp.pad(lse4, ((0, 0), (0, 0), (0, 0), (0, pad_q)))
    sq_p, sk_p = sq + pad_q, sk + pad_k
    use_segments = sid_q is not None
    use_bias = bias is not None
    n_qb, n_kb = sq_p // block_q, sk_p // block_k
    interp = interpret

    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, use_segments=use_segments,
                  use_bias=use_bias, dropout_rate=dropout_rate,
                  causal_offset=causal_offset)

    def extra(qdim, kdim, hdim=1):
        specs, ops = [], []
        if use_segments:
            specs += _seg_specs(block_q, block_k, qdim=qdim, kdim=kdim)
            ops += [sid_q[:, :, None], sid_kv[:, None, :]]
        if use_bias:
            specs += [_bias_spec(bias, block_q, block_k, qdim=qdim, kdim=kdim,
                                 hdim=hdim)]
            ops += [bias]
        if dropout_rate > 0.0:
            specs += [pl.BlockSpec(memory_space=pltpu.SMEM)]
            ops += [seed]
        return specs, ops

    def qspec(qdim):
        return pl.BlockSpec((1, 1, block_q, d),
                            lambda *g, _q=qdim: (g[0], g[1], g[_q], 0))

    def kspec(kdim):
        return pl.BlockSpec((1, 1, block_k, d),
                            lambda *g, _k=kdim: (g[0], g[1], g[_k], 0))

    def rowspec(qdim):
        return pl.BlockSpec((1, 1, 1, block_q),
                            lambda *g, _q=qdim: (g[0], g[1], 0, g[_q]))

    if banded:
        return _flash_bwd_banded(
            (q_p, k_p, v_p, do_p, lse4, delta), extra, common, window,
            (sq, sk), interp)
    _count_tiles("bwd", b * h, strips, causal, sq_p, sk_p, block_q, block_k)
    # --- fused single-pass backward when the [sk, d] dk/dv accumulators
    # fit the scoped-VMEM budget
    if _bwd_fused(sk_p, d, k.dtype, v.dtype, use_bias, dropout_rate,
                  block_q, block_k):
        especs, eops = extra(qdim=2, kdim=3)
        kvspec = pl.BlockSpec((1, 1, sk_p, d), lambda *g: (g[0], g[1], 0, 0))
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, strips=strips, **common),
            grid=(b, h, n_qb, n_kb),
            in_specs=especs + [qspec(2), kspec(3), kspec(3), qspec(2),
                               rowspec(2), rowspec(2)],
            out_specs=[qspec(2), kvspec, kvspec],
            out_shape=[jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
                       jax.ShapeDtypeStruct((b, h, sk_p, d), k.dtype),
                       jax.ShapeDtypeStruct((b, h, sk_p, d), v.dtype)],
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                            pltpu.VMEM((sk_p, d), jnp.float32),
                            pltpu.VMEM((sk_p, d), jnp.float32)],
            interpret=interp,
        )(*eops, q_p, k_p, v_p, do_p, lse4, delta)
        return dq[:, :, :sq], dk[:, :, :sk], dv[:, :, :sk]

    # --- dk/dv: grid (b, h, kb, qi), k-block resident, q streamed
    especs, eops = extra(qdim=3, kdim=2)
    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, **common),
        grid=(b, h, n_kb, n_qb),
        in_specs=especs + [qspec(3), kspec(2), kspec(2), qspec(3),
                           rowspec(3), rowspec(3)],
        out_specs=[kspec(2), kspec(2)],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, sk_p, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interp,
    )(*eops, q_p, k_p, v_p, do_p, lse4, delta)

    # --- dq: grid (b, h, qi, kb), q-block resident, k streamed
    especs, eops = extra(qdim=2, kdim=3)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(b, h, n_qb, n_kb),
        in_specs=especs + [qspec(2), kspec(3), kspec(3), qspec(2),
                           rowspec(2), rowspec(2)],
        out_specs=qspec(2),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interp,
    )(*eops, q_p, k_p, v_p, do_p, lse4, delta)

    return dq[:, :, :sq], dk[:, :, :sk], dv[:, :, :sk]


def _flash_bwd_banded(operands, extra, common, window, lengths, interpret):
    """The two-kernel backward over the banded grids (``_Band``): dk/dv a
    KEY/VALUE head, its inner dimension the group's query heads times the
    q blocks that see the k block; dq a query head over the k blocks its q
    block sees. ``operands``: padded q, k, v, do and the lse and delta
    rows; ``extra`` and ``common`` as in ``_flash_bwd``."""
    q_p, k_p, v_p, do_p, lse4, delta = operands
    b, h, sq_p, d = q_p.shape
    hk, sk_p = k_p.shape[1], k_p.shape[2]
    group = h // hk
    block_q, block_k = common["block_q"], common["block_k"]
    off = common["causal_offset"]
    kband, qband, live = _band_plan(common["causal"], sq_p, sk_p, block_q,
                                    block_k, off, window)
    _count_band_tiles("bwd", b * h, window, live, sq_p, sk_p, block_q,
                      block_k)
    common = dict(common, window=window)

    # --- dk/dv: grid (b, kv head, kb, group x band), k block resident
    def head_of(*g):
        return g[1] * group + g[3] // qband.inner

    def qi_of(*g):
        return jnp.minimum(_band_qi(g[2], g[3] % qband.inner, qband, block_q,
                                    block_k, off), qband.blocks - 1)

    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda *g: (g[0], head_of(*g), qi_of(*g), 0))
    row_spec = pl.BlockSpec((1, 1, 1, block_q),
                            lambda *g: (g[0], head_of(*g), 0, qi_of(*g)))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda *g: (g[0], g[1], g[2], 0))
    especs, eops = extra(qdim=qi_of, kdim=2, hdim=head_of)
    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, band=qband, group=group, **common),
        grid=(b, hk, sk_p // block_k, group * qband.inner),
        in_specs=especs + [q_spec, kv_spec, kv_spec, q_spec, row_spec,
                           row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k_p.shape, k_p.dtype),
                   jax.ShapeDtypeStruct(v_p.shape, v_p.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret, compiler_params=_band_params(),
    )(*eops, q_p, k_p, v_p, do_p, lse4, delta)

    # --- dq: grid (b, h, qi, band), q block resident
    def kb_of(*g):
        return jnp.maximum(_band_kb(g[2], g[3], kband, block_q, block_k,
                                    off), 0)

    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda *g: (g[0], g[1], g[2], 0))
    row_spec = pl.BlockSpec((1, 1, 1, block_q),
                            lambda *g: (g[0], g[1], 0, g[2]))
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda *g: (g[0], g[1] // group, kb_of(*g), 0))
    especs, eops = extra(qdim=2, kdim=kb_of)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, band=kband, **common),
        grid=(b, h, sq_p // block_q, kband.inner),
        in_specs=especs + [q_spec, kv_spec, kv_spec, q_spec, row_spec,
                           row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q_p.shape, q_p.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret, compiler_params=_band_params(),
    )(*eops, q_p, k_p, v_p, do_p, lse4, delta)
    sq, sk = lengths
    return dq[:, :, :sq], dk[:, :, :sk], dv[:, :, :sk]


# ---------------------------------------------------------------------------
# Reference backward math (parity baseline for the Pallas kernels; O(s^2)
# memory — debug/test only)
# ---------------------------------------------------------------------------

def _bwd_math(res, do, *, scale, causal, dropout_rate=0.0):
    q, k, v, out, lse, sid_q, sid_kv, bias, seed = res
    if dropout_rate > 0.0:
        raise NotImplementedError(
            "_bwd_math is the no-dropout parity baseline; dropout backward "
            "runs only in the Pallas kernels")
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    sq, sk = s.shape[-2], s.shape[-1]
    mask = jnp.ones(s.shape[-2:], jnp.bool_)
    if causal:
        mask &= ~(jnp.arange(sk)[None, :] > jnp.arange(sq)[:, None] + (sk - sq))
    if sid_q is not None:
        if sid_kv is None:
            sid_kv = sid_q
        seg = ((sid_q[:, None, :, None] == sid_kv[:, None, None, :])
               & (sid_q >= 0)[:, None, :, None])
        mask = mask & seg
    # exact softmax via saved lse; explicit zero where masked (a fully
    # masked padding row has lse == _NEG_INF, so exp(s - lse) would be 1)
    p = jnp.where(mask, jnp.exp(jnp.where(mask, s, _NEG_INF) - lse[..., None]),
                  0.0)
    do32 = do.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, do32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", do32, v.astype(jnp.float32))
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1, keepdims=True)
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14, 15))
def _flash_attention(q, k, v, segment_ids_q, segment_ids_kv, bias, seed,
                     causal, scale, dropout_rate, block_q, block_k,
                     block_q_bwd, block_k_bwd, interpret, window=None):
    out, _ = _fa_fwd(q, k, v, segment_ids_q, segment_ids_kv, bias, seed,
                     causal, scale, dropout_rate, block_q, block_k,
                     block_q_bwd, block_k_bwd, interpret, window)
    return out


#: the forward kernel's two results as ``jax.ad_checkpoint.checkpoint_name``
#: tags them in the ``custom_vjp``'s forward rule (see :func:`flash_attention`)
FLASH_OUT = "flash_attention_out"
FLASH_LSE = "flash_attention_lse"


def _fa_fwd(q, k, v, sid_q, sid_kv, bias, seed, causal, scale, dropout_rate,
            block_q, block_k, block_q_bwd, block_k_bwd, interpret,
            window=None):
    scale_v = q.shape[-1] ** -0.5 if scale is None else scale
    out, lse = _flash_fwd_impl(q, k, v, sid_q, sid_kv, bias, seed,
                               float(scale_v), causal, dropout_rate, block_q,
                               block_k, _compat.resolve_interpret(interpret),
                               window)
    # outside the jitted call: a names policy sees them; identities otherwise
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, out, lse, sid_q, sid_kv, bias, seed)


def _fa_bwd(causal, scale, dropout_rate, block_q, block_k,
            block_q_bwd, block_k_bwd, interpret, window, res, do):
    q = res[0]
    bias = res[7]
    scale_v = q.shape[-1] ** -0.5 if scale is None else scale
    dq, dk, dv = _flash_bwd_impl(
        res, do, scale=float(scale_v), causal=causal,
        dropout_rate=dropout_rate, block_q=block_q_bwd,
        block_k=block_k_bwd, interpret=_compat.resolve_interpret(interpret),
        window=window)
    # bias is an additive attention mask — non-differentiable by contract
    # (matches apex, where masks are inputs, never parameters); a real dbias
    # would require materializing [sq, sk] and is deliberately not offered.
    dbias = None if bias is None else jnp.zeros_like(bias)
    dseed = None
    return dq, dk, dv, None, None, dbias, dseed


_flash_attention.defvjp(_fa_fwd, _fa_bwd)


from apex_tpu.amp.policy import half_function  # noqa: E402  (amp has no ops imports; placed here to keep kernel code import-light)


@half_function
def flash_attention(q, k, v, segment_ids_q=None, segment_ids_kv=None,
                    causal: bool = False, scale: Optional[float] = None,
                    bias=None, dropout_rate: float = 0.0,
                    dropout_seed=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    block_q_bwd: Optional[int] = None,
                    block_k_bwd: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    autotune: Optional[str] = None,
                    window: Optional[int] = None):
    """Fused attention. Returns [b, h, sq, d].

    Under differentiation the forward kernel's output and its log-sum-exp
    carry the names :data:`FLASH_OUT` (``"flash_attention_out"``) and
    :data:`FLASH_LSE` (``"flash_attention_lse"``): a block under
    ``jax.checkpoint`` that keeps these two
    (``policy=jax.checkpoint_policies.save_only_these_names(FLASH_OUT,
    FLASH_LSE)``) does not run the forward kernel again in its backward.
    Without such a policy the names are identities and lower to nothing.

    ``k``, ``v`` ``[b, hk, sk, d]`` with ``hk`` = ``h`` or a divisor of it
    (grouped-query attention: query head ``i`` reads key/value head ``i //
    (h // hk)``; the kernels index it, K and V are never repeated, and dK
    and dV come back ``[b, hk, sk, d]``, summed over each group in float32
    inside the backward kernel). ``h`` not a multiple of ``hk`` is a
    ``ValueError``.

    ``window``: a sliding window (``causal=True`` only, ``sq <= sk``): a
    query sees its last ``window`` keys, itself included (``i - window < j
    <= i`` at equal lengths). Blocks wholly outside the band are neither
    computed nor fetched, blocks its edges cross are masked. ``window >=
    sk`` is plain causal attention, bit for bit. A window or a grouped call
    walks grids of its own (``_Band``), takes the two-kernel backward and
    blocks of 1,024 (512 in a window call's backward) where none are passed
    (the tuned-block cache is not consulted); its instructions are named
    ``apx_flash_attention_window_fwd`` / ``_bwd`` under a window.

    ``segment_ids_*``: packed-varlen support (FMHA cu_seqlens analog) —
    tokens attend only within equal *non-negative* segment ids; negative
    ids are padding: they match nothing (not even each other), attend
    nothing, and produce zero output rows. Sequence lengths need not be
    multiples of the block sizes (inputs are padded internally).

    ``bias``: additive attention bias, broadcastable ``[b|1, h|1, sq, sk]``
    (the additive attn-mask of the fast-MHA variants). Non-differentiable.

    ``dropout_rate``/``dropout_seed``: in-kernel attention dropout via a
    counter-based hash RNG; the mask is regenerated (never stored) in the
    backward. ``dropout_seed`` is an int32 scalar (python int or array);
    pass a fresh value per training step. Ignored when
    ``dropout_rate == 0``.

    ``block_q``/``block_k`` tile the FORWARD kernel;
    ``block_q_bwd``/``block_k_bwd`` tile the backward kernels and default
    to the phase-tuned values (module docstring).

    ``autotune``: block-resolution policy for knobs left at ``None`` —
    ``"cache"`` (default; also via ``$APEX_TPU_AUTOTUNE``) consults the
    persistent per-device tuned-block cache
    (``python -m apex_tpu.ops tune``, docs/perf.md §autotuning) and
    falls back to the heuristic defaults on a miss; ``"off"`` skips the
    lookup entirely (bit-for-bit the heuristic defaults); ``"online"``
    sweeps-and-caches on first miss. Explicitly-passed blocks always
    win. The forward and backward resolve INDEPENDENTLY: a cache that
    holds backward blocks retires the inheritance warning below.

    .. warning:: explicitly-passed forward blocks silently govern the
       backward too: when you set ``block_q``/``block_k`` but not
       ``block_q_bwd``/``block_k_bwd``, the backward inherits your
       forward tiling verbatim (back-compat: callers tuned before the
       phases split expect one consistent tiling) and the phase-tuned
       backward defaults — measurably faster on causal shapes that
       carry segments or dropout, e.g. 1.17 ms vs 1.29 ms at b8 h16
       s1024 d64 — are NOT applied. To get
       the tuned backward while pinning the forward, pass
       ``block_q_bwd=None``-equivalent explicitly:
       ``flash_attention(..., block_q=1024, block_k=1024,
       block_q_bwd=512, block_k_bwd=512)`` (or whatever the module
       docstring's phase table says for your shape), or let the tuned
       cache supply them — a backward cache hit takes precedence over
       the inheritance, silently. A one-time ``UserWarning`` flags the
       inheritance so the behavior is never silent otherwise.
    """
    if dropout_rate >= 1.0 or dropout_rate < 0.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got {dropout_rate}")
    if q.shape[1] % k.shape[1] or k.shape[1] != v.shape[1]:
        raise ValueError(
            f"{q.shape[1]} query heads are not a multiple of {k.shape[1]} "
            f"key / {v.shape[1]} value heads")
    if window is not None:
        if not causal or window < 1 or q.shape[2] > k.shape[2]:
            raise ValueError(
                f"window={window} needs causal=True, window >= 1 and "
                f"sq <= sk (got causal={causal}, sq={q.shape[2]}, "
                f"sk={k.shape[2]})")
        if window >= k.shape[2]:
            window = None                   # every key is inside the band
    if _banded(q, k, window):
        # the banded grids: the blocks passed, or their defaults; no lookup
        # (explicit forward blocks govern the backward too, as below)
        both = bias is not None and dropout_rate > 0.0
        bwd = block_q or block_k or (
            _BAND_BLOCK_BWD_WINDOW if window is not None or both
            else _BAND_BLOCK)
        fwd = 512 if both else _BAND_BLOCK
        block_q, block_k = block_q or fwd, block_k or fwd
        block_q_bwd, block_k_bwd = block_q_bwd or bwd, block_k_bwd or bwd
    explicit_fwd_blocks = block_q is not None or block_k is not None
    if (block_q is None and block_k is None) or \
            (block_q_bwd is None and block_k_bwd is None):
        from apex_tpu.tune import runtime as _tune_rt
        policy = _tune_rt.resolve_policy(autotune)
        if policy != "off":
            shape = {"b": q.shape[0], "h": q.shape[1], "sq": q.shape[2],
                     "sk": k.shape[2], "d": q.shape[3],
                     "itemsize": q.dtype.itemsize}
            flags = {"causal": causal, "bias": bias is not None,
                     "dropout": dropout_rate > 0.0,
                     "segments": segment_ids_q is not None}
            interp = _compat.resolve_interpret(interpret)
            if block_q is None and block_k is None:
                cfg = _tune_rt.resolve("flash_attention_fwd", shape,
                                       q.dtype.name, flags, policy=policy,
                                       interpret=interp)
                if cfg is not None:
                    block_q, block_k = cfg["block_q"], cfg["block_k"]
            if block_q_bwd is None and block_k_bwd is None:
                cfg = _tune_rt.resolve("flash_attention_bwd", shape,
                                       q.dtype.name, flags, policy=policy,
                                       interpret=interp)
                if cfg is not None:
                    # a cache-resolved backward retires the
                    # forward-blocks-govern-backward inheritance: with
                    # both bwd blocks set here the warning branch below
                    # is never entered, so it neither fires nor
                    # consumes its once-key (tested)
                    block_q_bwd = cfg["block_q"]
                    block_k_bwd = cfg["block_k"]
    elif autotune is not None:
        # fully-pinned call sites still get policy-string validation
        from apex_tpu.tune import runtime as _tune_rt
        _tune_rt.resolve_policy(autotune)
    if block_q is None or block_k is None:
        # bias + dropout together exceed VMEM at 1024 blocks (see module
        # docstring); everything else is fastest at 1024 in the FORWARD,
        # including causal shapes: a grid step costs more than it skips
        # (measured b8 h16 s1024 d64 fwd-only: 1.33 ms @ (1024,1024) vs
        # 1.72 ms @ (512,512)), and the fully-masked half of a
        # [1024, 1024] diagonal block is skipped INSIDE the program, a
        # strip of query rows at a time, where the call is plain causal
        # (``_strip_plan``: 0.533 -> 0.444 ms a call)
        default = 512 if (bias is not None and dropout_rate > 0.0) else 1024
        block_q = block_q or default
        block_k = block_k or default
    if block_q_bwd is None or block_k_bwd is None:
        if explicit_fwd_blocks:
            # back-compat: explicit caller blocks govern both phases —
            # loudly, once: the caller tuned the forward and would
            # otherwise lose the phase-tuned backward tiling unseen. Called
            # directly from this frame so warn_inert_once's stacklevel
            # attributes the warning to the user's call site. A caller
            # who passed ONE bwd block has found the bwd knobs — the
            # silent-inheritance hazard is gone, so no warning (and the
            # "were not passed" text would be wrong for them).
            if block_q_bwd is None and block_k_bwd is None:
                from apex_tpu.utils.parity import warn_inert_once
                warn_inert_once(
                    f"flash_attention: explicit forward blocks (block_q="
                    f"{block_q}, block_k={block_k}) also govern the "
                    "BACKWARD kernels because block_q_bwd/block_k_bwd "
                    "were not passed; the phase-tuned backward defaults "
                    "are not applied. Pass block_q_bwd/block_k_bwd "
                    "explicitly to tile the backward independently "
                    "(docstring has the tuned values).",
                    key="flash_attention.inherited_bwd_blocks")
            bq_d, bk_d = block_q, block_k
        else:
            bq_d = bk_d = 512 if (bias is not None and dropout_rate > 0.0) \
                else 1024
            if causal and _bwd_strip_plan(
                    True, segment_ids_q is None and bias is None
                    and dropout_rate == 0.0, q.shape[2], k.shape[2], bq_d,
                    bk_d, q.shape[3], k.dtype, v.dtype) is None:
                # a causal BACKWARD that cannot walk strips inside a
                # 1024-block (segments, dropout, a padded length, the
                # two-kernel form) wants two 512-aligned k blocks per
                # sequence at s=1024, one dead block of four skipped:
                # measured 1.17 ms vs 1.29 ms fused @ (1024,1024) whole
                # and 1.66 ms two-kernel (b8 h16 d64) — the fused kernel
                # runs at any n_kb (r5), this is purely the faster tiling;
                # s >= 2048 keeps 1024 blocks. With strips the 1024-block
                # wins: 0.915 ms (PERF.md, PR 44)
                bq_d = bk_d = min(bq_d, max(512, (q.shape[2] // 2)
                                            // 512 * 512))
        block_q_bwd = block_q_bwd or bq_d
        block_k_bwd = block_k_bwd or bk_d
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        seed = jnp.asarray(dropout_seed, jnp.int32).reshape((1,))
    else:
        seed = jnp.zeros((1,), jnp.int32)
    if bias is not None:
        b, h, sq, sk = q.shape[0], q.shape[1], q.shape[2], k.shape[2]
        if (bias.ndim != 4 or bias.shape[0] not in (1, b)
                or bias.shape[1] not in (1, h)
                or bias.shape[2] != sq or bias.shape[3] != sk):
            raise ValueError(
                f"bias must broadcast to [{b}, {h}, {sq}, {sk}], got "
                f"{bias.shape}")
    # profile scope (monitor.profile): the kernel call (fwd + its
    # custom-vjp backward) attributed as one module; metadata-only
    from apex_tpu.monitor import profile as _prof
    with _prof.scope("flash_attention"):
        return _flash_attention(q, k, v, segment_ids_q, segment_ids_kv,
                                bias, seed, causal, scale,
                                float(dropout_rate), block_q, block_k,
                                block_q_bwd, block_k_bwd, interpret, window)
