"""Paged decode attention over a LATENT cache (multi-head latent attention,
the absorbed path).

A token caches one row a layer, shared by every query head::

    latent_pages [1, num_pages, page_size, width]
        lanes 0:r            the normalised latent c_kv (r = kv_lora_rank)
        lanes r:r+rope       the rotated shared key head k_pe
        lanes r+rope:width   zeros (the row is padded to a multiple of 128)
    q            [b, heads, width]   q_lat | q_pe | zeros, per head
    block_tables [b, pages_per_seq] int32; seq_lens [b] int32 (0 = inactive)

Scores are ``q . row`` over the whole row (the padding multiplies zeros by
zeros), values are the first ``r`` lanes OF THE SAME ROW: a page is read
once and serves as keys and as values. The caller has absorbed ``W_kvb``'s
key part into ``q_lat`` and applies its value part to the output
``[b, heads, r]``. MQA-shaped: all heads of a sequence share the page, so a
program is one sequence x a few pages with every head in the sublanes.

The leading ``1`` keeps the leaf in the pool contract of
``ops.flash_attention`` (``[kv_heads, num_pages, page_size, lanes]``), so
the aliased ``paged_kv_write_rows`` / ``paged_kv_write_pages`` update a
latent pool in place as they do a K|V pool.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu import _compat
from apex_tpu.monitor import profile as _prof

_NEG_INF = -1e30


def mla_attention_reference(q, latent_pages, block_tables, seq_lens, *,
                            value_dim: int, scale: float):
    """Pure-XLA baseline and off-TPU path: gathers every sequence's pages
    through its block table. Returns ``[b, heads, value_dim]``."""
    _, _, page_size, width = latent_pages.shape
    b, m = block_tables.shape
    rows = jnp.take(latent_pages[0], block_tables, axis=0)  # [b, m, ps, w]
    rows = rows.reshape(b, m * page_size, width).astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows) * scale
    live = (jnp.arange(m * page_size, dtype=jnp.int32)[None, :]
            < seq_lens[:, None])[:, None, :]
    s = jnp.where(live, s, _NEG_INF)
    p = jnp.where(live, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    out = jnp.einsum("bhs,bsv->bhv", p, rows[..., :value_dim])
    return (out / jnp.where(l > 0, l, 1.0)).astype(q.dtype)


def _kernel(bt_ref, sl_ref, q_ref, *refs, scale, page_size, value_dim,
            steps, pages_per_step):
    del bt_ref
    kv_refs = refs[:pages_per_step]
    o_ref, m_scr, l_scr, acc_scr = refs[pages_per_step:]
    bi, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _page(kv_ref, first):
        page = kv_ref[0, 0]                               # [ps, width]
        s = jax.lax.dot_general(q_ref[0], page, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = pos < sl_ref[bi]
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(page.dtype), page[:, :value_dim],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new

    # pages past the sequence's end (every page of an inactive slot) skip
    # the compute; their block index is the null page's, fetched once
    for i, kv_ref in enumerate(kv_refs):
        first = (j * pages_per_step + i) * page_size
        pl.when(first < sl_ref[bi])(functools.partial(_page, kv_ref, first))

    @pl.when(j == steps - 1)
    def _finish():
        l = l_scr[:]
        o_ref[0] = (acc_scr[:] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


#: pages a program attends: the pool is passed once a page slot, each with
#: its own block-table index, and Pallas pipelines the slots' DMAs. Worth 5%
#: on the chip (1.91 -> 1.81 ms a layer at 290k cached tokens, 0.41 ms at the
#: roofline; 2, 5 and 10 pages read the same): the kernel is not
#: program-count bound but MXU-bound, a page's tiles being loaded for 64
#: streamed rows (PERF.md, PR 26)
PAGES_PER_STEP = 4


def mla_decode_attention(q, latent_pages, block_tables, seq_lens, *,
                         value_dim: int, scale: float,
                         interpret: Optional[bool] = None):
    """The Pallas kernel: grid ``(b, pages_per_seq / PAGES_PER_STEP)``,
    every head of one sequence against ``PAGES_PER_STEP`` latent pages a
    program, online softmax across the pages. Returns ``[b, heads,
    value_dim]`` in ``q.dtype``."""
    b, heads, width = q.shape
    one, _, page_size, pw = latent_pages.shape
    if one != 1 or pw != width:
        raise ValueError(f"latent_pages {latent_pages.shape} does not match "
                         f"q {q.shape}: want [1, num_pages, page_size, "
                         f"{width}]")
    interpret = _compat.resolve_interpret(interpret)
    if not interpret and (width % 128 or value_dim % 128 or page_size % 8
                          or heads % 8):
        raise ValueError(
            f"row width {width} and value_dim {value_dim} must be multiples "
            f"of 128, page_size {page_size} and heads {heads} of 8 (lane "
            f"and sublane tiles); use the reference path otherwise")
    pps = min(PAGES_PER_STEP, block_tables.shape[1])
    # whole steps: the columns added point at the null page, past every end
    block_tables = jnp.pad(block_tables,
                           ((0, 0), (0, -block_tables.shape[1] % pps)))
    m = block_tables.shape[1]
    kernel = functools.partial(_kernel, scale=scale, page_size=page_size,
                               value_dim=value_dim, steps=m // pps,
                               pages_per_step=pps)

    def page_spec(i):
        return pl.BlockSpec(
            (1, 1, page_size, width),
            lambda bi, j, bt, sl: (0, bt[bi * m + j * pps + i], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, m // pps),
        in_specs=[
            pl.BlockSpec((1, heads, width), lambda bi, j, bt, sl: (bi, 0, 0)),
            *[page_spec(i) for i in range(pps)],
        ],
        out_specs=pl.BlockSpec((1, heads, value_dim),
                               lambda bi, j, bt, sl: (bi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((heads, 1), jnp.float32),
                        pltpu.VMEM((heads, 1), jnp.float32),
                        pltpu.VMEM((heads, value_dim), jnp.float32)],
    )
    with _prof.scope("mla_decode_attention"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, heads, value_dim), q.dtype),
            interpret=interpret,
        )(block_tables.reshape(-1).astype(jnp.int32),
          seq_lens.astype(jnp.int32), q, *[latent_pages] * pps)
