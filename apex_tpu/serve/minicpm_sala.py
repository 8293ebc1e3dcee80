"""Serving a MiniCPM-SALA model (:mod:`apex_tpu.models.minicpm_sala`)
through the engine: two kinds of cache in the one manager.

**What is cached.** A ``minicpm4`` (sparse) layer caches a token's K|V in
the paged pool like GPT (``kv_heads`` = the model's 2, K beside V: 256
lanes) and, beside each pool leaf, the page's COMPRESSED keys
(``CacheConfig.keys_per_page``: 4 a page of 64 tokens a K|V head), which the
block selection scores. A ``lightning-attn`` layer caches no token: it keeps
one recurrent state ``f32[heads, d, d]`` a SEQUENCE, in a leaf indexed by
the sequence's batch row (``CacheConfig.state_leaves``). The page size is
the selection's block size: a chosen block IS a page.

**One structure, two forwards** (:func:`_block`): ``prefill`` runs a chunk
of one sequence's prompt from ``start`` (``None`` = the whole prompt in one
program), ``decode`` one token a batch row. A lightning layer's chunk starts
from the state the chunks before it left in the sequence's row (from zero at
``start == 0``, whoever had the row before) and leaves the state decode goes
on from; a sparse layer's chunk writes its K|V and the compressed keys it
completes, then attends the pages of the whole context so far under each
query token's own choice of blocks. Decode: write the token, complete a
compressed key where one is due, choose, walk the chosen pages.

Rounding: matmuls take ``cfg.dtype`` (bf16) rows and weights and leave
float32; the residual stream, the norms, the rotation, the selection's
scores and the recurrent state are float32; q, k and v enter the attention
kernels and the pool in ``cfg.dtype``.

Out of scope, refused at engine construction: ``tp > 1``, fp8 pages, fp8
weights, speculation.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.models import deepseek as ds
from apex_tpu.models.minicpm_sala import LIGHTNING, SPARSE
from apex_tpu.monitor import hooks as _mhooks
from apex_tpu.monitor import profile as _prof
from apex_tpu.ops import lightning_attention as la
from apex_tpu.ops import sparse_attention as sa
from apex_tpu.serve import cache as cache_mod
from apex_tpu.serve.model import PAGED_IMPLS, PREFILL_IMPLS

F32 = jnp.float32


class MiniCPMSalaServed:
    """The model behind the engine's interface
    (:class:`apex_tpu.serve.model.GPTServed` spells it out)."""

    param_rules = cache_rules = None       # tp > 1 is refused in check()

    def __init__(self, cfg):
        self.cfg = cfg

    @property
    def max_seq_len(self) -> int:
        return self.cfg.max_seq_len

    def check(self, *, tp: int, fp8_kv: bool = False,
              fp8_weights: bool = False, spec_k: int = 0,
              prefill_chunk: int = 0):
        for on, what in ((tp > 1, "tp > 1"), (fp8_kv, "fp8 pages"),
                         (fp8_weights, "fp8 weights"),
                         (spec_k, "speculative decoding (a rejected token "
                                  "has already moved the recurrent state)")):
            if on:
                raise NotImplementedError(
                    f"serve/minicpm_sala.py: {what} is out of scope "
                    f"(ROADMAP, queue R)")

    def page_geometry(self, tp: int) -> dict:
        cfg = self.cfg
        return dict(kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
                    group=cfg.group, dtype=cfg.dtype)

    def cache_config(self, *, num_pages: int, page_size: int,
                     fp8: bool = False, fp8_margin: float = 2.0,
                     max_batch: int = 0):
        cfg = self.cfg
        if page_size != cfg.sparse.block_size:
            raise ValueError(
                f"page_size {page_size} must be the selection's block_size "
                f"{cfg.sparse.block_size}: a chosen block is a page")
        n, d = cfg.lightning_nh, cfg.lightning_head_dim
        return cache_mod.CacheConfig(
            num_layers=cfg.count(SPARSE), kv_heads=cfg.num_key_value_heads,
            head_dim=cfg.head_dim, num_pages=num_pages, page_size=page_size,
            dtype=cfg.dtype, state_leaves=cfg.count(LIGHTNING),
            state_rows=max_batch, state_shape=(n, d, d),
            keys_per_page=cfg.sparse.per_block)

    def prefill(self, ccfg, params, state, block_table, length, ids, **kw):
        return prefill_forward(self.cfg, ccfg, params, state, block_table,
                               length, ids, **kw)

    def decode(self, ccfg, params, state, block_tables, positions, tokens,
               active, **kw):
        return decode_forward(self.cfg, ccfg, params, state, block_tables,
                              positions, tokens, active, **kw)

    def record_round(self, aux_round) -> None:
        """A decode round's counters: ``sparse/blocks_chosen``,
        ``sparse/tokens_attended`` and ``sparse/context_tokens`` (a sparse
        layer's, summed over the round's rows and K|V heads alike: what the
        attention read against what a dense one would have) and
        ``state/rows_live`` (rows whose recurrent states the round moved)."""
        for name, n in aux_round.items():
            _mhooks.counter(name, int(n))


# -- the layer -------------------------------------------------------------------

def _proj(x, w):
    return jnp.dot(x, w, preferred_element_type=F32)


def _heads(cfg, p, a, n, n_kv, d):
    """q ``[t, n, d]`` and k, v ``[t, n_kv, d]`` in float32, q and k
    RMSNorm-ed a head."""
    t = a.shape[0]
    q = ds.rms_norm(_proj(a, p["q"]).reshape(t, n, d), p["q_norm"],
                    cfg.rms_norm_eps)
    k = ds.rms_norm(_proj(a, p["k"]).reshape(t, n_kv, d), p["k_norm"],
                    cfg.rms_norm_eps)
    return q, k, _proj(a, p["v"]).reshape(t, n_kv, d)


def _gated_out(cfg, p, a, ctx):
    gate = jax.nn.sigmoid(_proj(a, p["gate"]))
    return _proj((ctx.astype(F32) * gate).astype(cfg.dtype), p["o"])


def _block(cfg, i, layer, x, mixers):
    """Layer ``i`` on rows ``x`` ``[t, h]`` float32: the ONE copy of the
    serve-side structure. ``mixers[kind](leaf, p, a)`` owns the cache
    interaction and returns the sub-layer's output ``[t, h]``."""
    kind = cfg.mixer_types[i]
    a = ds.rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
    with _prof.scope("lightning_attn" if kind == LIGHTNING
                     else "sparse_attn"):
        x = x + cfg.residual_scale * mixers[kind](cfg.leaf(i),
                                                  layer["attn"], a)
    m = ds.rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps).astype(cfg.dtype)
    with _prof.scope("dense_ffn"):
        mlp = layer["mlp"]
        return x + cfg.residual_scale * ds.gated_mlp(
            m, mlp["gate"], mlp["up"], mlp["down"], acc=F32)


def _lightning_inputs(cfg, p, a, positions):
    n, d = cfg.lightning_nh, cfg.lightning_head_dim
    q, k, v = _heads(cfg, p, a, n, n, d)
    q = ds.rope(q, positions, cfg) * d ** -0.5
    k = ds.rope(k, positions, cfg)
    return (y.astype(cfg.dtype) for y in (q, k, v))


def _lightning_out(cfg, p, a, o):
    """``o`` float32 ``[t, n, d]``: RMSNorm over the concatenated heads,
    the gate, the output projection."""
    o = ds.rms_norm(o.reshape(o.shape[0], -1), p["o_norm"], cfg.rms_norm_eps)
    return _gated_out(cfg, p, a, o)


def _embed(cfg, params, ids):
    return cfg.scale_emb * jnp.take(params["embed"], ids, axis=0).astype(F32)


def _logits(cfg, params, x):
    x = ds.rms_norm(x, params["norm_f"], cfg.rms_norm_eps) / cfg.logit_divisor
    with _prof.scope("lm_head"):
        return _proj(x.astype(cfg.dtype), params["head"])


def _aux(attended, round_counters=None):
    """Each row's attended blocks ``[t, sparse layers, kv, blocks]`` and,
    of a decode round, its counters."""
    aux = {}
    if attended:
        aux["rows"] = {"attended": jnp.stack(attended, axis=1)}
    if round_counters is not None:
        aux["round"] = round_counters
    return aux


# -- decode ----------------------------------------------------------------------

def decode_forward(cfg, ccfg: cache_mod.CacheConfig, params,
                   state: cache_mod.CacheState, block_tables, positions,
                   tokens, active, *, paged_impl: str = "reference",
                   interpret: Optional[bool] = None,
                   autotune: Optional[str] = None):
    """One decode step over the fixed-capacity batch. Same contract as
    ``serve.model.decode_forward``; returns ``(logits [B, V] f32, new_state,
    aux)``. A row's recurrent states are row ``b`` of the state leaves: the
    batch row IS the sequence's state slot."""
    del autotune
    if paged_impl not in PAGED_IMPLS:
        raise ValueError(f"paged_impl must be one of {PAGED_IMPLS}, got "
                         f"{paged_impl!r}")
    B = tokens.shape[0]
    spec, page = cfg.sparse, ccfg.page_size
    n, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    positions = jnp.where(active, positions, 0).astype(jnp.int32)
    page_ids = jnp.where(
        active, block_tables[jnp.arange(B), positions // page],
        0).astype(jnp.int32)
    slots = (positions % page).astype(jnp.int32)
    rows = sa.listed_rows(positions, active, spec, block_tables.shape[1])
    box, attended = [state], []

    def lightning(leaf, p, a):
        q, k, v = _lightning_inputs(cfg, p, a, positions)
        o, new = la.lightning_decode(q, k, v, box[0].states[leaf], active,
                                     impl=paged_impl, interpret=interpret)
        box[0] = cache_mod.with_leaf(box[0], "states", leaf, new)
        return _lightning_out(cfg, p, a, o)

    def sparse(leaf, p, a):
        q, k, v = (y.astype(cfg.dtype) for y in _heads(cfg, p, a, n, kv, d))
        box[0] = cache_mod.write_token(ccfg, box[0], leaf, page_ids, slots,
                                       k, v, impl=paged_impl,
                                       interpret=interpret)
        pool = box[0].pools[leaf]
        q4 = q.reshape(B, kv, cfg.group, d)
        with _prof.scope("sparse_select"):
            ckeys = sa.write_token_keys(box[0].ckeys[leaf], pool,
                                        block_tables, positions, active,
                                        spec)
            box[0] = cache_mod.with_leaf(box[0], "ckeys", leaf, ckeys)
            ck = sa.gather_keys(ckeys, block_tables, kv, spec)
        idx, att = sa.select(q4, ck, positions, block_tables.shape[1], spec,
                             d ** -0.5)
        attended.append(att)
        pages = sa.decode_page_lists(idx, block_tables, positions, spec)
        ctx = sa.sparse_decode_attention(q4, pool, pages, rows,
                                         scale=d ** -0.5, impl=paged_impl,
                                         interpret=interpret)
        return _gated_out(cfg, p, a, ctx.reshape(B, n * d))

    mixers = {LIGHTNING: lightning, SPARSE: sparse}
    with _prof.scope("serve_decode"):
        x = _embed(cfg, params, jnp.where(active, tokens, 0))
        for i in range(cfg.num_layers):
            with _prof.scope(f"block_{i}"):
                x = _block(cfg, i, params[f"layer_{i}"], x, mixers)
        logits = _logits(cfg, params, x)
    counters = {
        "state/rows_live": jnp.sum(active.astype(jnp.int32)),
        "sparse/blocks_chosen": jnp.sum(-(-rows // spec.block_size)),
        "sparse/tokens_attended": jnp.sum(rows),
        "sparse/context_tokens": jnp.sum(jnp.where(active, positions + 1,
                                                   0))}
    return logits, box[0], _aux(attended, counters)


# -- prefill ---------------------------------------------------------------------

def prefill_forward(cfg, ccfg: cache_mod.CacheConfig, params,
                    state: cache_mod.CacheState, block_table, length, ids, *,
                    slot, start=None, attention_impl: str = "reference",
                    interpret: Optional[bool] = None,
                    autotune: Optional[str] = None):
    """One chunk of ONE sequence's prompt: ``ids`` ``[C]`` at positions
    ``start .. start + C - 1`` of which the first ``length`` are live;
    ``start`` ``None``: the whole prompt, from 0. ``block_table`` lists the
    sequence's pages from position 0 and bounds the context a chunk attends
    (``len(block_table)`` pages); ``slot`` is the sequence's batch row, whose
    recurrent states the chunk continues (from zero at ``start == 0``).
    Returns ``(logits [V] f32 of row length - 1, new_state, aux)``."""
    if attention_impl not in PREFILL_IMPLS:
        raise ValueError(f"attention_impl must be one of {PREFILL_IMPLS}, "
                         f"got {attention_impl!r}")
    C = ids.shape[0]
    spec, page = cfg.sparse, ccfg.page_size
    n, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    kernels = attention_impl == "flash"
    impl = "kernel" if kernels else "reference"
    chunk_pages = -(-C // page)
    if start is None:
        start, ctx_pages = jnp.int32(0), chunk_pages
    else:
        ctx_pages = block_table.shape[0]
    positions = start + jnp.arange(C, dtype=jnp.int32)
    own = jax.lax.dynamic_slice(block_table, (start // page,),
                                (chunk_pages,))
    ctx_table = block_table[:ctx_pages]
    box, attended = [state], []

    def lightning(leaf, p, a):
        q, k, v = (y.transpose(1, 0, 2)
                   for y in _lightning_inputs(cfg, p, a, positions))
        o, new = la.lightning_prefill(q, k, v, box[0].states[leaf], slot,
                                      start, length, impl=impl,
                                      interpret=interpret)
        box[0] = cache_mod.with_leaf(box[0], "states", leaf, new)
        return _lightning_out(cfg, p, a, o.transpose(1, 0, 2))

    def sparse(leaf, p, a):
        q, k, v = (y.astype(cfg.dtype) for y in _heads(cfg, p, a, n, kv, d))
        box[0] = cache_mod.write_prompt(ccfg, box[0], leaf, own, length, k,
                                        v, impl=impl, interpret=interpret)
        pool = box[0].pools[leaf]
        q4 = q.reshape(C, kv, cfg.group, d)
        with _prof.scope("sparse_select"):
            ckeys = sa.write_chunk_keys(box[0].ckeys[leaf], pool,
                                        block_table, start, length, k, spec)
            box[0] = cache_mod.with_leaf(box[0], "ckeys", leaf, ckeys)
            ck = sa.gather_keys(ckeys, ctx_table, kv, spec)
        _, att = sa.select(q4, ck, positions, ctx_pages, spec, d ** -0.5)
        attended.append(att)
        # the context as the pool holds it: what decode will read
        rows = sa.gather_pages(pool, ctx_table).reshape(
            kv, ctx_pages * page, 2 * d).transpose(1, 0, 2)
        ctx = sa.sparse_prefill_attention(
            q4, rows[..., :d], rows[..., d:], att, positions, spec,
            scale=d ** -0.5, attention_impl=attention_impl,
            interpret=interpret, autotune=autotune)
        return _gated_out(cfg, p, a, ctx.reshape(C, n * d))

    mixers = {LIGHTNING: lightning, SPARSE: sparse}
    with _prof.scope("serve_prefill"):
        x = _embed(cfg, params, ids)
        for i in range(cfg.num_layers):
            with _prof.scope(f"block_{i}"):
                x = _block(cfg, i, params[f"layer_{i}"], x, mixers)
        logits = _logits(cfg, params, jax.lax.dynamic_slice_in_dim(
            x, length - 1, 1, axis=0))[0]
    return logits, box[0], _aux(attended)
