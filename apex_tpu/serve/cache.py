"""Paged KV cache: a preallocated page pool + per-sequence block tables.

The pool is allocated ONCE (``init_cache``) and never reshaped: every
cache mutation writes rows into the fixed arrays, so the decode and
prefill steps donate the pool and update it in place. Layout (the
contract in ``ops.flash_attention``, above its paged kernels):

    pools[layer]      [kv_heads, num_pages, page_size, 2*d]
                      one leaf a layer; a token's K in lanes 0:d, its V
                      in d:2d
                      (a model that caches something else a token gives
                      the lanes of its row as ``row_width``: a latent
                      cache is ``kv_heads=1`` and one padded latent row,
                      ``serve/latent.py``; ``num_layers`` counts the
                      pool's leaves, so a model with two attentions a
                      layer asks for twice its layers)
    k_scale / v_scale [num_layers, kv_heads, num_pages]  f32 (fp8 mode)

Two further kinds of leaf, for a model that asks for them through its
``cache_config`` (``serve/minicpm_sala.py``; none by default, and a state
without them is the pytree it always was):

    states[i]         f32 [state_rows, *state_shape]
                      a FIXED-SIZE state a sequence a recurrent layer,
                      indexed by the sequence's batch row (``state_rows`` =
                      the engine's ``max_batch``), not by pages: it lives
                      and dies with the row, is taken as zero by the
                      sequence's first prefill chunk, and is donated and
                      updated in place like the pool. A preempted sequence
                      loses it with its pages and is recomputed.
    ckeys[layer]      [num_pages * keys_per_page, kv_heads * d]
                      a small cache a pool leaf for what a model derives
                      from a page's rows (compressed keys for a block
                      selection, ``ops/sparse_attention.py``): found through
                      the same block table, freed with the page

One leaf a layer, because a program that updates and reads 2 x L slices
of one stacked array makes XLA copy the whole stack; K beside V,
because 2*d >= 128 in the lanes is what gives the leaf the row-major
device layout the Pallas kernels take as it is (PERF.md, PR 24).

Page 0 is the **null page**: the host allocator never hands it out, and
every masked write (inactive batch slots, prompt padding) is routed to
it — so a write never needs a branch on the row, and nothing ever reads
the null page's contents (block-table entries past a sequence's length
point at it but are masked by ``seq_lens``).

A write is ``impl="kernel"`` (the aliased Pallas writes of
``ops.flash_attention``: on the TPU an XLA scatter lays the pool out
for the update and copies it there and back) or ``impl="reference"``
(the XLA scatter: the off-TPU path and the parity baseline); both
store the same bits.

fp8-KV mode stores e4m3 pages through the :mod:`apex_tpu.amp.fp8` codec
with ONE scale per (layer, head, page), fixed when the page's slot-0
token is written (``compute_scale`` of that token's amax with
``fp8_margin`` powers of two of headroom; later tokens in the page
quantize with the same scale and saturate-clip past it — the e4m3 clip
is the codec's correctness rule). The slot-0 rule is what makes
evict/re-admit bit-exact: a page's scale is a deterministic function of
its first token regardless of whether that token arrived via prefill or
decode, so a recomputed cache is bitwise the original.

Page size resolves **explicit > tuned cache > heuristic** through
``apex_tpu.tune`` (:func:`resolve_page_size` — the ``decode_attention``
sweep of ``python -m apex_tpu.ops tune``), exactly like the flash
fwd/bwd blocks.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.amp import fp8 as fp8_mod
from apex_tpu.monitor import profile as _prof
from apex_tpu.ops.paged_attention import (paged_kv_write_pages,
                                          paged_kv_write_rows)

#: heuristic default page size: big enough that one DMA of the decode
#: kernel's walk moves a page of every kv head in one piece (512 KB at 16
#: heads of 64 in bf16) and a 1k-token context is 8 steps, small enough
#: that the tail the kernel reads and the pool holds for nothing
#: (page_size/2 tokens a sequence on average) stays a few percent at
#: chat lengths
DEFAULT_PAGE_SIZE = 128


def resolve_page_size(*, kv_heads: int, head_dim: int, context_len: int,
                      group: int = 1, dtype=jnp.bfloat16, fp8: bool = False,
                      batch: int = 1, page_size: Optional[int] = None,
                      autotune: Optional[str] = None) -> int:
    """Pool page size: explicit > tuned cache > heuristic (the flash
    fwd/bwd resolution order, via the ``decode_attention`` sweep)."""
    if page_size is not None:
        return int(page_size)
    from apex_tpu.tune import runtime as tune_rt
    policy = tune_rt.resolve_policy(autotune)
    if policy != "off":
        dt = jnp.dtype(dtype)
        shape = {"b": batch, "kv": kv_heads, "group": group,
                 "s": context_len, "d": head_dim, "itemsize": dt.itemsize}
        cfg = tune_rt.resolve("decode_attention", shape, dt.name,
                              {"fp8": bool(fp8)}, policy=policy)
        if cfg is not None:
            return int(cfg["block_kv"])
    # clip to the context like flash blocks clip to the sequence, but
    # keep the 8-sublane alignment the Pallas kernel requires
    return min(DEFAULT_PAGE_SIZE, max(8, -(-context_len // 8) * 8))


@dataclasses.dataclass(frozen=True)
class CacheConfig:
    """Static pool geometry (hashable — rides jit as a static arg)."""

    num_layers: int
    kv_heads: int
    head_dim: int
    num_pages: int                 # INCLUDING the null page 0
    page_size: int
    dtype: Any = jnp.bfloat16      # pool dtype (ignored when fp8)
    fp8: bool = False
    fp8_margin: float = 2.0        # 2**margin headroom over the slot-0 amax
    #: lanes of one token's row in a head's page; None = K beside V
    row_width: Optional[int] = None
    #: recurrent-state leaves, each f32 [state_rows, *state_shape]
    state_leaves: int = 0
    state_rows: int = 0
    state_shape: Tuple[int, ...] = ()
    #: derived rows a page a pool leaf ([.., kv_heads * head_dim] each)
    keys_per_page: int = 0

    def __post_init__(self):
        if self.num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is the "
                             "reserved null page)")
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")

    @property
    def pool_dtype(self):
        return fp8_mod.E4M3 if self.fp8 else jnp.dtype(self.dtype)

    @property
    def width(self) -> int:
        return 2 * self.head_dim if self.row_width is None \
            else self.row_width

    @property
    def usable_pages(self) -> int:
        return self.num_pages - 1

    def pages_for_tokens(self, n: int) -> int:
        return -(-int(n) // self.page_size)

    # -- capacity accounting (host-side ints: the bench/test assertions
    #    about fp8 capacity come from HERE, not from hand-waving) ------

    def bytes_per_page(self) -> int:
        """HBM bytes one pool page costs across its rows as they are held,
        padding included (+ fp8 scales)."""
        elems = self.kv_heads * self.page_size * self.width
        per = elems * jnp.dtype(self.pool_dtype).itemsize
        if self.fp8:
            per += 2 * self.kv_heads * 4          # k_scale + v_scale rows
        per += self.keys_per_page * self.kv_heads * self.head_dim \
            * jnp.dtype(self.pool_dtype).itemsize
        return per * self.num_layers

    def state_bytes(self) -> int:
        """HBM bytes of the recurrent-state leaves (every row, always)."""
        n = self.state_leaves * self.state_rows * 4
        for s in self.state_shape:
            n *= s
        return n

    def pool_bytes(self) -> int:
        """Everything ``init_cache`` allocates: pages (with what is kept a
        page) and recurrent state."""
        return self.bytes_per_page() * self.num_pages + self.state_bytes()

    def pages_in_budget(self, budget_bytes: int) -> int:
        return int(budget_bytes) // self.bytes_per_page()

    def max_concurrent_seqs(self, budget_bytes: int, seq_len: int) -> int:
        """How many ``seq_len``-token sequences fit a pool of
        ``budget_bytes`` (minus the null page)."""
        usable = max(0, self.pages_in_budget(budget_bytes) - 1)
        return usable // self.pages_for_tokens(seq_len)

    def occupancy_bytes(self, pages_in_use: int) -> int:
        """HBM bytes held by ``pages_in_use`` allocated pages — the
        per-step ``serve/pool_bytes_in_use`` gauge the engine records
        (same byte accounting as :meth:`bytes_per_page`, so the
        telemetry and the capacity claims can never drift apart)."""
        return int(pages_in_use) * self.bytes_per_page()


class CacheState(NamedTuple):
    """The device pytree the jitted steps thread and donate."""

    pools: Tuple[jax.Array, ...]   # one [kv, pages, page_size, width] a layer
    k_scale: Optional[jax.Array]   # None outside fp8 mode
    v_scale: Optional[jax.Array]
    states: Tuple[jax.Array, ...] = ()   # f32 [rows, *state_shape] each
    ckeys: Tuple[jax.Array, ...] = ()    # one beside each pool leaf


def init_cache(cfg: CacheConfig) -> CacheState:
    shape = (cfg.kv_heads, cfg.num_pages, cfg.page_size, cfg.width)
    # DISTINCT arrays, here and for the scales — aliased leaves break
    # the donated step (donate-same-buffer-twice)
    pools = tuple(jnp.zeros(shape, cfg.pool_dtype)
                  for _ in range(cfg.num_layers))
    states = tuple(jnp.zeros((cfg.state_rows,) + tuple(cfg.state_shape),
                             jnp.float32) for _ in range(cfg.state_leaves))
    ckeys = tuple(jnp.zeros((cfg.num_pages * cfg.keys_per_page,
                             cfg.kv_heads * cfg.head_dim), cfg.pool_dtype)
                  for _ in range(cfg.num_layers if cfg.keys_per_page else 0))
    if not cfg.fp8:
        return CacheState(pools, None, None, states, ckeys)
    if states or ckeys:
        raise NotImplementedError("fp8 pages with state or derived leaves")
    # scales init to 1.0: finite and positive everywhere, so the
    # kernel's dequant divides are safe even for never-written pages
    sshape = (cfg.num_layers, cfg.kv_heads, cfg.num_pages)
    return CacheState(pools, jnp.ones(sshape, jnp.float32),
                      jnp.ones(sshape, jnp.float32))


def _page_scales(cfg: CacheConfig, x) -> jax.Array:
    """compute_scale over the head dim: ``x`` [..., kv, d] ->
    [..., kv]."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    return fp8_mod.compute_scale(amax, fp8_mod.E4M3_MAX,
                                 margin=cfg.fp8_margin)


WRITE_IMPLS = ("reference", "kernel")


def _check_impl(impl: str):
    if impl not in WRITE_IMPLS:
        raise ValueError(f"impl must be one of {WRITE_IMPLS}, got {impl!r}")


def _with_layer(state: CacheState, layer: int, pool, k_scale,
                v_scale) -> CacheState:
    pools = state.pools[:layer] + (pool,) + state.pools[layer + 1:]
    return state._replace(pools=pools, k_scale=k_scale, v_scale=v_scale)


def with_leaf(state: CacheState, kind: str, i: int, leaf) -> CacheState:
    """``state`` with leaf ``i`` of ``kind`` (``"states"`` | ``"ckeys"``)
    replaced."""
    old = getattr(state, kind)
    return state._replace(**{kind: old[:i] + (leaf,) + old[i + 1:]})


@_prof.scoped("kv_write")
def write_token(cfg: CacheConfig, state: CacheState, layer: int,
                page_ids, slots, k_new, v_new, *, impl: str = "reference",
                interpret: Optional[bool] = None) -> CacheState:
    """Write one decode token per batch slot into layer ``layer``.

    ``page_ids``/``slots``: int32 [b] (masked slots carry page 0);
    ``k_new``/``v_new``: [b, kv_heads, d]. Pure — runs inside the
    donated decode step.

    ``impl="kernel"`` (``ops.paged_attention.paged_kv_write_rows``) moves
    a group of ``G`` rows a program, their tiles' reads in flight together
    and then their writes; ``G`` follows from the tile's bytes, the decode
    kernel's VMEM budget and ``b``, and is nothing a caller sets. Rows of a
    group that share a tile (the masked rows; two tokens of one sequence)
    are merged in order into one buffer that is written once, so every
    row lands, and where two rows name one slot the later one stays.
    """
    _check_impl(impl)
    # NB the scale indexing below mixes the scalar ``layer`` with index
    # arrays: both are "advanced" indices separated by the heads slice,
    # so the broadcast dims land FIRST — gathers/scatters see [b, kv]
    k_t, v_t = k_new, v_new                        # [b, kv, d]
    k_scale = state.k_scale
    v_scale = state.v_scale
    if cfg.fp8:
        first = (slots == 0)[:, None]              # [b, 1]
        cand_k = _page_scales(cfg, k_new)          # [b, kv]
        cand_v = _page_scales(cfg, v_new)
        cur_k = state.k_scale[layer, :, page_ids]  # [b, kv]
        cur_v = state.v_scale[layer, :, page_ids]
        sk = jnp.where(first, cand_k, cur_k)
        sv = jnp.where(first, cand_v, cur_v)
        k_scale = state.k_scale.at[layer, :, page_ids].set(sk)
        v_scale = state.v_scale.at[layer, :, page_ids].set(sv)
        k_t = fp8_mod.quantize(k_t, sk[..., None], fp8_mod.E4M3)
        v_t = fp8_mod.quantize(v_t, sv[..., None], fp8_mod.E4M3)
    else:
        k_t = k_t.astype(cfg.pool_dtype)
        v_t = v_t.astype(cfg.pool_dtype)
    rows = jnp.concatenate([k_t, v_t], axis=-1)    # [b, kv, 2d]
    pool = _store_token_rows(state.pools[layer], page_ids, slots, rows,
                             impl, interpret)
    return _with_layer(state, layer, pool, k_scale, v_scale)


def _store_token_rows(pool, page_ids, slots, rows, impl, interpret):
    if impl == "kernel":
        return paged_kv_write_rows(pool, page_ids, slots, rows,
                                   interpret=interpret)
    # adjacent index arrays stay in place: the update is [kv, b, width]
    return pool.at[:, page_ids, slots].set(rows.transpose(1, 0, 2))


@_prof.scoped("kv_write")
def write_token_rows(cfg: CacheConfig, state: CacheState, layer: int,
                     page_ids, slots, rows, *, impl: str = "reference",
                     interpret: Optional[bool] = None) -> CacheState:
    """:func:`write_token` for a model that builds its own rows:
    ``rows`` ``[b, kv_heads, cfg.width]``, one a batch slot."""
    _check_impl(impl)
    _no_fp8_rows(cfg)
    pool = _store_token_rows(state.pools[layer], page_ids, slots,
                             rows.astype(cfg.pool_dtype), impl, interpret)
    return _with_layer(state, layer, pool, state.k_scale, state.v_scale)


def _no_fp8_rows(cfg: CacheConfig):
    if cfg.fp8:
        raise NotImplementedError(
            "fp8 pages hold a K and a V scale a page: rows of another "
            "kind are not quantized (write_token / write_prompt are)")


@_prof.scoped("kv_write")
def write_prompt(cfg: CacheConfig, state: CacheState, layer: int,
                 block_table, length, k_seq, v_seq, *,
                 impl: str = "reference",
                 interpret: Optional[bool] = None) -> CacheState:
    """Write a whole (padded) prompt's K/V for one sequence.

    ``block_table``: int32 [m] (the sequence's pages); ``length``:
    traced scalar (real prompt length — positions past it route to the
    null page); ``k_seq``/``v_seq``: [S, kv_heads, d] with S static and
    a multiple-free shape (S <= m * page_size).
    """
    _check_impl(impl)
    S = k_seq.shape[0]
    pos = jnp.arange(S, dtype=jnp.int32)
    live = pos < length
    pages = jnp.where(live, block_table[pos // cfg.page_size], 0)
    k_t, v_t = k_seq, v_seq                        # [S, kv, d]
    k_scale = state.k_scale
    v_scale = state.v_scale
    if cfg.fp8:
        # slot-0 rule: one scale write per touched page, from the
        # page's first token (static stride — S and page_size are
        # static), identical to what the decode write would have set
        pos0 = jnp.arange(0, S, cfg.page_size, dtype=jnp.int32)
        pages0 = pages[pos0]                       # masked ones hit null
        sk0 = _page_scales(cfg, k_seq[pos0])       # [m_used, kv]
        sv0 = _page_scales(cfg, v_seq[pos0])
        k_scale = state.k_scale.at[layer, :, pages0].set(sk0)
        v_scale = state.v_scale.at[layer, :, pages0].set(sv0)
        # every position quantizes with ITS page's (new) scale
        sk = k_scale[layer, :, pages]              # [S, kv]
        sv = v_scale[layer, :, pages]
        k_t = fp8_mod.quantize(k_t, sk[..., None], fp8_mod.E4M3)
        v_t = fp8_mod.quantize(v_t, sv[..., None], fp8_mod.E4M3)
    else:
        k_t = k_t.astype(cfg.pool_dtype)
        v_t = v_t.astype(cfg.pool_dtype)
    rows = jnp.concatenate([k_t, v_t], axis=-1)    # [S, kv, 2d]
    pool = _store_prompt_rows(cfg, state.pools[layer], block_table, length,
                              pages, pos, rows, impl, interpret)
    return _with_layer(state, layer, pool, k_scale, v_scale)


def _store_prompt_rows(cfg, pool, block_table, length, pages, pos, rows,
                       impl, interpret):
    if impl == "kernel":
        return paged_kv_write_pages(pool, block_table, length, rows,
                                    interpret=interpret)
    return pool.at[:, pages, pos % cfg.page_size].set(
        rows.transpose(1, 0, 2))


@_prof.scoped("kv_write")
def write_prompt_rows(cfg: CacheConfig, state: CacheState, layer: int,
                      block_table, length, rows, *, impl: str = "reference",
                      interpret: Optional[bool] = None) -> CacheState:
    """:func:`write_prompt` for a model that builds its own rows:
    ``rows`` ``[S, kv_heads, cfg.width]``, one a (padded) position."""
    _check_impl(impl)
    _no_fp8_rows(cfg)
    pos = jnp.arange(rows.shape[0], dtype=jnp.int32)
    pages = jnp.where(pos < length, block_table[pos // cfg.page_size], 0)
    pool = _store_prompt_rows(cfg, state.pools[layer], block_table, length,
                              pages, pos, rows.astype(cfg.pool_dtype), impl,
                              interpret)
    return _with_layer(state, layer, pool, state.k_scale, state.v_scale)
