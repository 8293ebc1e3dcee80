"""What the latent-attention models share on the serve side: the paged
LATENT cache, the one copy of the latent-attention sub-block, and the
decode and prefill forwards around a model's own layer.

:class:`LatentServed` answers the engine's model interface
(:class:`apex_tpu.serve.model.GPTServed` spells it out); a model derives
from it and gives its layer (``block``) and how many latent attentions a
layer holds (``leaves_per_layer``): :class:`apex_tpu.serve.deepseek.
DeepseekServed`, :class:`apex_tpu.serve.longcat.LongcatServed`.

**What is cached.** One row a token an attention sub-layer, shared by every
head: the RMSNorm-ed latent ``c_kv`` (``kv_lora_rank`` lanes), then the
rotated shared key head ``k_pe`` (``qk_rope_head_dim`` lanes), then zeros
up to a multiple of 128 lanes (the lane-aligned leaf the aliased Pallas
writes take as it lies, PERF.md PR 24). At the published 512 + 64 that is
640 lanes, 1,280 B in bf16 where 1,152 are payload. A sub-layer's leaf is
``[1, num_pages, page_size, 640]``: ``CacheConfig(kv_heads=1,
row_width=640)`` with ``num_layers`` = the pool's leaves, written by
``cache.write_token_rows`` / ``write_prompt_rows``.

**Two attention paths, one cache.** Decode takes the ABSORBED path:
``q_lat_h = q_nope_h W_kvb[K, h]`` (the key expansion folded into the
query), scores ``(q_lat_h . c_kv + q_pe_h . k_pe) * s`` straight over the
cached rows (``ops.mla_attention``: a page is read once, for keys and
values), ``o_h = (softmax . c_kv) W_kvb[V, h]``. Prefill takes the
EXPANDED path: ``k_h = [c_kv W_kvb[K, h] | k_pe]``, ``v_h = c_kv
W_kvb[V, h]``, causal flash attention at head size ``nope + rope``; where
``v_head_dim`` is smaller (LongCat: 128 under 192), the kernel, which
takes one head size, is given V with zero lanes up to it and the context
is cut back: exact, at half again the PV product. Both agree with the
references' one full forward (``tests/test_deepseek.py``,
``tests/test_longcat.py``).

Out of scope, refused at engine construction: an fp8 latent pool, ``tp >
1`` (a latent leaf has no head dim to shard), fp8 weights, speculation.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from apex_tpu.models import deepseek as ds
from apex_tpu.monitor import hooks as _mhooks
from apex_tpu.monitor import profile as _prof
from apex_tpu.ops.flash_attention import flash_attention, mha_reference
from apex_tpu.ops.mla_attention import (mla_attention_reference,
                                        mla_decode_attention)
from apex_tpu.serve import cache as cache_mod
from apex_tpu.serve.model import PAGED_IMPLS, PREFILL_IMPLS


def latent_row_width(cfg) -> int:
    """Lanes of a token's row as held: the latent and the shared key
    head, padded to whole 128-lane tiles."""
    return -(-cfg.latent_dim // 128) * 128


class LatentServed:
    """A latent-attention model behind the engine's interface. A model
    gives ``block(i, layer, x, positions, attend, moe_kw, stats)``: its
    ``i``-th layer on rows ``x`` [t, h], the ONE copy of its serve-side
    structure (decode and prefill share it), built from
    :func:`attention_sublayer` with ``attend`` and the leaf each
    attention writes; an expert layer's stats are appended to ``stats``."""

    param_rules = cache_rules = None       # tp > 1 is refused in check()
    leaves_per_layer = 1                   # latent attentions a layer
    #: dtype of the residual stream ``x`` between sub-layers; None = the
    #: model's own (every matmul takes ``cfg.dtype`` rows either way, and
    #: a projection back into the stream leaves its output in this dtype)
    residual_dtype = None

    def __init__(self, cfg):
        self.cfg = cfg

    @property
    def max_seq_len(self) -> int:
        return self.cfg.max_seq_len

    def check(self, *, tp: int, fp8_kv: bool = False,
              fp8_weights: bool = False, spec_k: int = 0,
              prefill_chunk: int = 0):
        for on, what in ((tp > 1, "tp > 1 (a latent leaf has no head dim "
                          "to shard)"),
                         (prefill_chunk, "chunked prefill over latent pages"),
                         (fp8_kv, "an fp8 latent pool"),
                         (fp8_weights, "fp8 weights"),
                         (spec_k, "speculative decoding")):
            if on:
                raise NotImplementedError(
                    f"serve/latent.py: {what} is out of scope for the "
                    f"latent-attention models (ROADMAP, queue R)")

    def page_geometry(self, tp: int) -> dict:
        return dict(kv_heads=1, head_dim=self.cfg.latent_dim,
                    group=self.cfg.num_heads, dtype=self.cfg.dtype)

    def cache_config(self, *, num_pages: int, page_size: int,
                     fp8: bool = False, fp8_margin: float = 2.0,
                     max_batch: int = 0):
        cfg = self.cfg
        ccfg = cache_mod.CacheConfig(
            num_layers=cfg.num_layers * self.leaves_per_layer, kv_heads=1,
            head_dim=cfg.latent_dim, num_pages=num_pages,
            page_size=page_size, dtype=cfg.dtype,
            row_width=latent_row_width(cfg))
        # once an engine: what a token costs a layer of the pool, as held
        _mhooks.counter("serve/latent_bytes_per_token",
                        ccfg.bytes_per_page() // (page_size * cfg.num_layers))
        return ccfg

    def prefill(self, ccfg, params, state, block_table, length, ids, *,
                slot=None, **kw):
        return prefill_forward(self, ccfg, params, state, block_table,
                               length, ids, **kw)

    def decode(self, ccfg, params, state, block_tables, positions, tokens,
               active, **kw):
        return decode_forward(self, ccfg, params, state, block_tables,
                              positions, tokens, active, **kw)

    def record_round(self, aux_round) -> None:
        """A decode round's routing, one counter event an expert layer
        each, ``moe/<name>`` for every scalar of the expert layer's stats:
        ``assignments_local`` (what this share was handed),
        ``expert_load_max`` (its fullest expert's rows),
        ``experts_touched`` and, where the router has zero-compute slots,
        ``assignments_zero`` and ``real_experts_per_token_max``."""
        for name, per_layer in aux_round.items():
            for i, n in enumerate(per_layer):
                _mhooks.counter(f"moe/{name}", int(n), layer=i)


def attention_sublayer(cfg, leaf, p, norm_weight, x, positions, attend,
                       **inputs_kw):
    """``x + Attn(RMSNorm(x))`` on rows ``x`` [t, h], the one copy of the
    latent-attention sub-block: projections
    (``models.deepseek.attention_inputs``, which takes ``inputs_kw``: a
    model's latent scales and where it rounds), then ``attend(leaf, p,
    q_nope, q_pe, c, k_pe)``, which owns the row's write into the pool's
    ``leaf`` and the attention form and returns the context ``[t, heads *
    v_head_dim]``, then the output projection."""
    with _prof.scope("mla_attn"):
        a = ds.rms_norm(x, norm_weight, cfg.rms_norm_eps).astype(cfg.dtype)
        ctx = attend(leaf, p, *ds.attention_inputs(cfg, p, a, positions,
                                                   **inputs_kw))
        return x + jnp.dot(ctx.astype(cfg.dtype), p["o"],
                           preferred_element_type=x.dtype)


def _latent_rows(c, k_pe, width):
    """A token's cache row a leaf: ``c | k_pe | zeros`` -> [t, 1, width]."""
    return _pad_lanes(jnp.concatenate([c, k_pe], -1), width)[:, None, :]


def _pad_lanes(x, width):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _aux(stats):
    """The engine's ``aux`` from the expert layers' stats: each row's
    chosen slots ``[t, layers, k]`` and the round's counters. Not among
    them: ``rows_moved``, which under the training size is the padded
    buffer's rows, a constant of the program's shape and no count of a
    round: the serve programs keep the results they had."""
    if not stats:
        return {}
    return {"rows": {"moe_idx": jnp.stack([s["idx"] for s in stats],
                                          axis=-2)},
            "round": {k: jnp.stack([s[k] for s in stats])
                      for k in stats[0] if k not in ("idx", "rows_moved")}}


def decode_forward(model: LatentServed, ccfg: cache_mod.CacheConfig,
                   params, state: cache_mod.CacheState, block_tables,
                   positions, tokens, active, *,
                   paged_impl: str = "reference",
                   interpret: Optional[bool] = None,
                   autotune: Optional[str] = None):
    """One decode step over the fixed-capacity batch, absorbed attention
    over the latent pool. Same contract as ``serve.model.decode_forward``;
    returns ``(logits [B, V] f32, new_state, aux)``."""
    del autotune
    if paged_impl not in PAGED_IMPLS:
        raise ValueError(f"paged_impl must be one of {PAGED_IMPLS}, got "
                         f"{paged_impl!r}")
    cfg = model.cfg
    B = tokens.shape[0]
    kernels = paged_impl == "kernel"
    scale = ds.softmax_scale(cfg)
    stats = []
    moe_kw = dict(active=active, interpret=interpret,
                  impl="kernel" if kernels else "reference")
    with _prof.scope("serve_decode"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(
            model.residual_dtype or cfg.dtype)
        seq_lens = jnp.where(active, positions + 1, 0).astype(jnp.int32)
        page_ids = jnp.where(
            active,
            block_tables[jnp.arange(B), positions // ccfg.page_size],
            0).astype(jnp.int32)
        slots = jnp.where(active, positions % ccfg.page_size,
                          0).astype(jnp.int32)
        # state is threaded through the attend closure: python-level
        # mutation is safe, the layer loop is sequential trace-time code
        box = [state]

        def attend(leaf, p, q_nope, q_pe, c, k_pe):
            box[0] = cache_mod.write_token_rows(
                ccfg, box[0], leaf, page_ids, slots,
                _latent_rows(c, k_pe, ccfg.width), impl=paged_impl,
                interpret=interpret)
            w_k, w_v = ds.kv_b_heads(cfg, p)
            q_lat = jnp.einsum("bnd,rnd->bnr", q_nope, w_k)
            q = _pad_lanes(jnp.concatenate([q_lat, q_pe], -1), ccfg.width)
            kw = dict(value_dim=cfg.kv_lora_rank, scale=scale)
            if kernels:
                o_lat = mla_decode_attention(
                    q, box[0].pools[leaf], block_tables, seq_lens,
                    interpret=interpret, **kw)
            else:
                o_lat = mla_attention_reference(
                    q, box[0].pools[leaf], block_tables, seq_lens, **kw)
            return jnp.einsum("bnr,rnv->bnv", o_lat, w_v).reshape(B, -1)

        for i in range(cfg.num_layers):
            with _prof.scope(f"block_{i}"):
                x = model.block(i, params[f"layer_{i}"], x, positions,
                                attend, moe_kw, stats)
        x = ds.rms_norm(x, params["norm_f"],
                        cfg.rms_norm_eps).astype(cfg.dtype)
        with _prof.scope("lm_head"):
            logits = jnp.dot(x, params["head"]).astype(jnp.float32)
    return logits, box[0], _aux(stats)


def prefill_forward(model: LatentServed, ccfg: cache_mod.CacheConfig,
                    params, state: cache_mod.CacheState, block_table, length,
                    ids, *, attention_impl: str = "reference",
                    interpret: Optional[bool] = None,
                    autotune: Optional[str] = None):
    """Full-prompt pass for ONE sequence, expanded attention; writes every
    live position's latent rows. Same contract as
    ``serve.model.prefill_forward``; returns ``(logits [V] f32 for position
    length - 1, new_state, aux)`` (``aux`` holds every prompt row's choice,
    ``[S, layers, k]``; rows past ``length`` mean nothing)."""
    if attention_impl not in PREFILL_IMPLS:
        raise ValueError(f"attention_impl must be one of {PREFILL_IMPLS}, "
                         f"got {attention_impl!r}")
    cfg = model.cfg
    S = ids.shape[0]
    scale = ds.softmax_scale(cfg)
    kernels = attention_impl == "flash"
    positions = jnp.arange(S, dtype=jnp.int32)
    live = positions < length
    sid = jnp.where(live, 0, -1)[None].astype(jnp.int32)
    stats = []
    moe_kw = dict(active=live, interpret=interpret,
                  impl="kernel" if kernels else "reference")
    with _prof.scope("serve_prefill"):
        x = jnp.take(params["embed"], ids, axis=0).astype(
            model.residual_dtype or cfg.dtype)
        box = [state]

        def attend(leaf, p, q_nope, q_pe, c, k_pe):
            box[0] = cache_mod.write_prompt_rows(
                ccfg, box[0], leaf, block_table, length,
                _latent_rows(c, k_pe, ccfg.width),
                impl="kernel" if kernels else "reference",
                interpret=interpret)
            w_k, w_v = ds.kv_b_heads(cfg, p)
            k = jnp.concatenate(
                [jnp.einsum("sr,rnd->snd", c, w_k),
                 jnp.broadcast_to(k_pe[:, None, :], q_pe.shape)], -1)
            v = jnp.einsum("sr,rnv->snv", c, w_v)
            q = jnp.concatenate([q_nope, q_pe], -1)
            if kernels and cfg.v_head_dim < cfg.qk_head_dim:
                v = _pad_lanes(v, cfg.qk_head_dim)   # the kernel's one d
            qh, kh, vh = (a.transpose(1, 0, 2)[None]
                          for a in (q, k, v))          # [1, n, S, d]
            with _prof.scope("prefill_attn"):
                if kernels:
                    ctx = flash_attention(
                        qh, kh, vh, causal=True, scale=scale,
                        segment_ids_q=sid, interpret=interpret,
                        autotune=autotune)[..., :cfg.v_head_dim]
                else:
                    ctx = mha_reference(qh, kh, vh, causal=True,
                                        scale=scale, segment_ids_q=sid)
            return ctx[0].transpose(1, 0, 2).reshape(S, -1)

        for i in range(cfg.num_layers):
            with _prof.scope(f"block_{i}"):
                x = model.block(i, params[f"layer_{i}"], x, positions,
                                attend, moe_kw, stats)
        x_last = ds.rms_norm(jnp.take(x, length - 1, axis=0),
                             params["norm_f"],
                             cfg.rms_norm_eps).astype(cfg.dtype)
        with _prof.scope("lm_head"):
            logits = jnp.dot(x_last, params["head"]).astype(jnp.float32)
    return logits, box[0], _aux(stats)
