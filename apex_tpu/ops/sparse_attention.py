"""Block-sparse attention over a paged K|V pool (InfLLM-v2, one level):
compressed keys, the choice of blocks, and attention over the chosen ones.

A query at position ``t`` (context ``t + 1`` tokens) attends, a K|V head:

- ``t + 1 <= dense_len``: every token up to itself (causal softmax);
- else the tokens up to itself of ``topk`` BLOCKS of ``block_size`` tokens:
  the first ``init_blocks``, the blocks the last ``window_size`` tokens lie
  in, and the best-scoring others. A block's score is the maximum over the
  compressed keys that overlap it of ``sum over the group's query heads of
  softmax_j(q . kbar_j * scale)``, the softmax over the compressed keys that
  are COMPLETE in the query's context; ``kbar_j = mean(k[stride * j : stride
  * j + kernel_size])``. One choice a K|V head (the group shares it).

Leaving the other blocks out is the mathematics, not an approximation of it.

**The compressed-key cache.** ``block_size / kernel_stride`` compressed keys
START in a block; with ``page_size == block_size`` they are kept a page: the
leaf ``[num_pages * per_block, kv_heads * d]`` holds key ``j`` at row
``page_of(stride * j) * per_block + j % per_block``, so the sequence's block
table finds them and a page's keys are one contiguous slab. A key is written
once, when its last token is (``kernel_size`` tokens after its first), as
the mean of the rows THE POOL holds (the cache's dtype): a chunk's and a
decode step's agree to the bit. An incomplete key's row is never read.

**Decode** walks the chosen pages with the paged decode kernel of
``ops.paged_attention`` given a list of pages a (sequence, K|V head): the
chosen blocks in ascending order, so that only the last, the query's own,
is partly live. A row in the dense regime lists all its pages.
**Prefill** computes the same choice a query token and attends under it as
a mask over the keys of the context (a flash product over every block:
at 16k keys that is 0.27 TFLOP a layer-chunk beside 0.58 of matmuls).

Every function has a plain-XLA form; the two attentions take ``impl`` /
``attention_impl`` like the paged kernels. Scopes: ``apx:sparse_select``
around compression, scoring and the choice; the decode kernel is
``apx_sparse_decode_attention`` in a device trace.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu import _compat
from apex_tpu.monitor import profile as _prof
from apex_tpu.ops.flash_attention import flash_attention
from apex_tpu.ops.paged_attention import (
    _NEG_INF, _paged_decode_call, paged_attention_reference)

_BIG = 1e9


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    """The selection's static sizes (MiniCPM4's ``sparse_config`` names)."""

    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    window_size: int = 2048
    init_blocks: int = 1
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self):
        if self.block_size % self.kernel_stride \
                or self.kernel_size % self.kernel_stride:
            raise ValueError("block_size and kernel_size must be multiples "
                             "of kernel_stride")
        if self.dense_len < self.topk * self.block_size:
            # else a context just past dense_len has fewer blocks than topk
            raise ValueError("dense_len must cover topk blocks")

    @property
    def per_block(self) -> int:
        """Compressed keys that start in a block."""
        return self.block_size // self.kernel_stride

    @property
    def overlap(self) -> int:
        """Strides a compressed key spans."""
        return self.kernel_size // self.kernel_stride

    def table_width(self, pages_per_seq: int) -> int:
        """Entries of a decode row's page list: the chosen blocks of a
        sparse row, every page of a dense one."""
        return min(max(self.topk, -(-self.dense_len // self.block_size)),
                   pages_per_seq)


# -- compressed keys -------------------------------------------------------------

def compress(k_rows, spec: SparseSpec):
    """Means over windows of ``kernel_size`` rows at ``kernel_stride``:
    ``k_rows`` ``[n, ...]`` -> float32 ``[n // stride - overlap + 1, ...]``
    (``n`` a multiple of the stride)."""
    n = k_rows.shape[0]
    st = spec.kernel_stride
    part = k_rows.astype(jnp.float32).reshape(
        (n // st, st) + k_rows.shape[1:]).sum(1)
    m = n // st - spec.overlap + 1
    return sum(part[e:e + m] for e in range(spec.overlap)) / spec.kernel_size


def gather_pages(pool, pages):
    """``pool[:, pages]`` for int32 ``pages`` ``[n]``: ``[kv, n, page_size,
    width]``. As a gather of whole pages from the pool seen as ``[kv *
    num_pages, page_size, width]``, which is the pool as it lies: indexing
    the pages of every head at once makes XLA lay the whole pool out anew
    for the gather (heads next to the lanes) and copy it there."""
    kv, num_pages = pool.shape[:2]
    flat = pool.reshape((kv * num_pages,) + pool.shape[2:])
    return flat[jnp.arange(kv)[:, None] * num_pages + pages[None, :]]


def gather_rows(pool, pages, slots):
    """``pool[:, pages, slots]`` for index arrays of one shape ``[...]``:
    ``[kv, ..., width]``, as a gather of rows from the pool seen as ``[kv *
    num_pages * page_size, width]`` (see :func:`gather_pages`)."""
    kv, num_pages, page_size, width = pool.shape
    flat = pool.reshape(kv * num_pages * page_size, width)
    head = jnp.arange(kv).reshape((kv,) + (1,) * pages.ndim) * num_pages
    return flat[(head + pages[None]) * page_size + slots[None]]


def key_row(block_table, j, spec: SparseSpec):
    """Rows of the compressed-key leaf that hold the keys ``j``: of one
    sequence (``block_table`` ``[m]``, ``j`` ``[n]``) or one key a sequence
    (``[b, m]``, ``[b]``)."""
    blk = j // spec.per_block
    page = block_table[blk] if block_table.ndim == 1 else \
        jnp.take_along_axis(block_table, blk[:, None], axis=1)[:, 0]
    return page * spec.per_block + j % spec.per_block


def write_chunk_keys(ckeys, pool, block_table, start, n_live, k_chunk,
                     spec: SparseSpec):
    """The compressed keys that become complete inside a chunk of ``C``
    rows at ``start`` (a multiple of the block size): ``k_chunk`` ``[C, kv,
    d]`` as the pool holds it. Reads the ``kernel_size - stride`` rows
    before the chunk from ``pool``."""
    C, kv, d = k_chunk.shape
    st, B = spec.kernel_stride, spec.block_size
    back = spec.kernel_size - st
    pad = -C % st
    prev_page = block_table[jnp.maximum(start // B - 1, 0)]
    prev = gather_pages(pool, prev_page[None])[:, 0, B - back:, :d] \
        .transpose(1, 0, 2)
    ext = jnp.concatenate(
        [prev.astype(k_chunk.dtype), k_chunk,
         jnp.zeros((pad, kv, d), k_chunk.dtype)], 0)
    keys = compress(ext, spec).astype(ckeys.dtype)       # [(C + pad) / st]
    j = start // st - (spec.overlap - 1) + jnp.arange(keys.shape[0])
    done = (j >= 0) & (j * st + spec.kernel_size <= start + n_live)
    rows = jnp.where(done, key_row(block_table, jnp.maximum(j, 0), spec), 0)
    return ckeys.at[rows].set(keys.reshape(keys.shape[0], kv * d))


def write_token_keys(ckeys, pool, block_tables, positions, active,
                     spec: SparseSpec):
    """A decode step's: row ``b`` wrote position ``positions[b]``; where
    that completes a compressed key, its mean over the pool's last
    ``kernel_size`` rows is written."""
    kv, _, B, width = pool.shape
    d = width // 2
    ks, st = spec.kernel_size, spec.kernel_stride
    n = positions + 1
    done = active & (n % st == 0) & (n >= ks)
    r = jnp.maximum(n[:, None] - ks + jnp.arange(ks)[None, :], 0)   # [b, ks]
    pages = jnp.take_along_axis(block_tables, r // B, axis=1)
    rows_k = gather_rows(pool, pages, r % B)[..., :d]    # [kv, b, ks, d]
    keys = rows_k.astype(jnp.float32).mean(2).transpose(1, 0, 2)
    j = jnp.maximum(n - ks, 0) // st
    rows = jnp.where(done, key_row(block_tables, j, spec), 0)
    return ckeys.at[rows].set(
        keys.reshape(keys.shape[0], kv * d).astype(ckeys.dtype))


def gather_keys(ckeys, block_table, kv_heads: int, spec: SparseSpec):
    """A sequence's compressed keys through its block table ``[..., m]``:
    ``[..., m * per_block, kv, d]`` (a page's keys are one slab)."""
    per = spec.per_block
    slabs = ckeys.reshape(ckeys.shape[0] // per, per * ckeys.shape[1])
    got = jnp.take(slabs, block_table, axis=0)           # [..., m, per*kv*d]
    return got.reshape(block_table.shape[:-1]
                       + (block_table.shape[-1] * per, kv_heads, -1))


# -- the choice ------------------------------------------------------------------

def block_scores(q, ck, positions, n_blocks: int, spec: SparseSpec,
                 scale: float):
    """``q`` ``[T, kv, g, d]``; ``ck`` ``[T, nk, kv, d]`` (a row's own
    keys) or ``[nk, kv, d]`` (one sequence's, shared by the rows);
    ``positions`` ``[T]``. Returns float32 ``[T, kv, n_blocks]``: a block's
    score, -1 where no complete key overlaps it."""
    eq = "tkgd,tnkd->tkgn" if ck.ndim == 4 else "tkgd,nkd->tkgn"
    s = jnp.einsum(eq, q, ck.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    nk = s.shape[-1]
    j = jnp.arange(nk, dtype=jnp.int32)
    valid = (j[None, :] * spec.kernel_stride + spec.kernel_size
             <= positions[:, None] + 1)[:, None, None, :]   # [T, 1, 1, nk]
    s = jnp.where(valid, s, -_BIG)
    p = jnp.where(valid, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    key = jnp.where(valid[:, :, 0], p.sum(2), -1.0)          # [T, kv, nk]
    per, want = spec.per_block, n_blocks * spec.per_block
    key = jnp.pad(key[..., :want], ((0, 0), (0, 0), (0, max(0, want - nk))),
                  constant_values=-1.0)
    main = key.reshape(key.shape[:2] + (n_blocks, per))
    best = main.max(-1)
    for e in range(1, spec.overlap):
        # a key that starts in the block before and reaches into this one
        before = jnp.pad(main[..., :-1, per - e], ((0, 0), (0, 0), (1, 0)),
                         constant_values=-1.0)
        best = jnp.maximum(best, before)
    return best


def _live_forced(positions, n_blocks, spec):
    b = jnp.arange(n_blocks, dtype=jnp.int32)[None, :]
    pos = positions[:, None]
    live = b <= pos // spec.block_size
    first_w = jnp.maximum(pos - spec.window_size + 1, 0) // spec.block_size
    return live, live & ((b < spec.init_blocks) | (b >= first_w))


def choose_blocks(scores, positions, spec: SparseSpec):
    """The ``topk`` blocks of each (row, K|V head), ascending: int32 ``[T,
    kv, min(topk, n_blocks)]``. Means something where the row is sparse
    (``positions + 1 > dense_len``)."""
    n_blocks = scores.shape[-1]
    live, forced = _live_forced(positions, n_blocks, spec)
    ranked = jnp.where(forced[:, None], _BIG,
                       jnp.where(live[:, None], scores, -_BIG))
    _, idx = jax.lax.top_k(ranked, min(spec.topk, n_blocks))
    return jnp.sort(idx.astype(jnp.int32), axis=-1)


def attended_blocks(idx, positions, n_blocks: int, spec: SparseSpec):
    """bool ``[T, kv, n_blocks]``: the blocks a row attends: the chosen
    ones where it is sparse, every live one where it is dense."""
    b = jnp.arange(n_blocks, dtype=jnp.int32)
    chosen = (idx[..., None] == b).any(-2)
    live, _ = _live_forced(positions, n_blocks, spec)
    sparse = positions + 1 > spec.dense_len
    return jnp.where(sparse[:, None, None], chosen, live[:, None, :])


def select(q, ck, positions, n_blocks: int, spec: SparseSpec, scale: float):
    """Scores and choice in one scope: ``(idx, attended)``."""
    with _prof.scope("sparse_select"):
        idx = choose_blocks(
            block_scores(q, ck, positions, n_blocks, spec, scale),
            positions, spec)
        return idx, attended_blocks(idx, positions, n_blocks, spec)


# -- attention over the choice -----------------------------------------------------

def listed_rows(positions, active, spec: SparseSpec, pages_per_seq: int):
    """int32 ``[b]``: how many rows of a decode row's page list are live:
    its whole context where it is dense, ``topk - 1`` whole blocks and its
    own block's part where it is sparse, 0 where it is inactive."""
    B = spec.block_size
    k = min(spec.topk, pages_per_seq)
    sparse = positions + 1 > spec.dense_len
    rows = jnp.where(sparse, (k - 1) * B + positions % B + 1, positions + 1)
    return jnp.where(active, rows, 0).astype(jnp.int32)


def decode_page_lists(idx, block_tables, positions, spec: SparseSpec):
    """What the decode kernel walks: int32 ``[b, kv, W]``, a row's chosen
    pages in ascending block order (a dense row's: all of them), of which
    ``listed_rows`` rows are live."""
    W = spec.table_width(block_tables.shape[1])
    idx = jnp.pad(idx, ((0, 0), (0, 0),
                        (0, max(0, W - idx.shape[-1]))))[..., :W]
    sparse = positions + 1 > spec.dense_len
    blk = jnp.where(sparse[:, None, None], idx,
                    jnp.arange(W, dtype=jnp.int32))
    return jnp.take_along_axis(block_tables[:, None, :], blk, axis=-1)


def sparse_decode_attention(q, kv_pages, pages, rows, *, scale: float,
                            impl: str = "reference",
                            interpret: Optional[bool] = None):
    """One query a row over its listed pages. ``q`` ``[b, kv, g, d]``;
    ``pages`` ``[b, kv, W]`` (``decode_page_lists``); ``rows`` ``[b]``
    (``listed_rows``).
    Returns ``[b, kv, g, d]`` in ``q.dtype``."""
    b, kv, _, _ = q.shape
    if impl == "kernel":
        return _paged_decode_call(
            q, kv_pages, pages.reshape(b * kv, -1), rows, None, None,
            scale=float(scale), hb=1,
            interpret=_compat.resolve_interpret(interpret), per_head=True)
    with _prof.scope("sparse_decode_attention"):
        return jnp.concatenate([
            paged_attention_reference(
                q[:, h:h + 1], kv_pages[h:h + 1], pages[:, h], rows,
                scale=scale) for h in range(kv)], axis=1)


def sparse_prefill_attention(q, k, v, attended, q_pos, spec: SparseSpec, *,
                             scale: float, attention_impl: str = "reference",
                             interpret: Optional[bool] = None,
                             autotune: Optional[str] = None):
    """A chunk's queries over the context's keys under the choice. ``q``
    ``[C, kv, g, d]``; ``k, v`` ``[S, kv, d]`` (positions ``0 .. S - 1``);
    ``attended`` bool ``[C, kv, S / block_size]``; ``q_pos`` ``[C]``.
    Returns ``[C, kv, g, d]`` in ``q.dtype``."""
    C, kv, g, d = q.shape
    S = k.shape[0]
    s_pos = jnp.arange(S, dtype=jnp.int32)
    allowed = jnp.repeat(attended, spec.block_size, axis=-1)[..., :S] \
        & (s_pos[None, None, :] <= q_pos[:, None, None])     # [C, kv, S]
    allowed = allowed.transpose(1, 0, 2)[:, None]            # [kv, 1, C, S]
    qh = q.transpose(1, 2, 0, 3)                             # [kv, g, C, d]
    kh, vh = (jnp.broadcast_to(x.transpose(1, 0, 2)[:, None], (kv, g, S, d))
              for x in (k, v))
    if attention_impl == "flash":
        # a K|V head is the kernel's batch entry, its group the heads: the
        # bias is one [C, S] sheet a K|V head
        bias = jnp.where(allowed, 0.0, _NEG_INF).astype(q.dtype)
        out = flash_attention(qh, kh, vh, bias=bias, scale=scale,
                                 interpret=interpret, autotune=autotune)
    else:
        s = jnp.einsum("kgcd,kgsd->kgcs", qh, kh,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(allowed, s, _NEG_INF), axis=-1)
        out = jnp.einsum("kgcs,kgsd->kgcd", p.astype(q.dtype), vh,
                         preferred_element_type=jnp.float32).astype(q.dtype)
    return out.transpose(2, 0, 1, 3)
