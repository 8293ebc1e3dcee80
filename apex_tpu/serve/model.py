"""Serve-side GPT forward passes over the paged KV cache.

Pure functions over the SAME parameter tree ``models.gpt.GPT`` trains
(``wte/wpe/block_i/{ln1, attn/{qkv,proj}, ln2, mlp/{fc1,fc2}}/ln_f``),
applied through the SAME tensor-parallel layer modules
(``Column/RowParallelLinear``, ``VocabParallelEmbedding``,
``FusedLayerNorm``) — so a checkpoint trained anywhere on the stack
serves unmodified, and under ``shard_map`` over the tensor axis the
serve path pays exactly the training collectives (row-parallel psum,
logits gather). The only new math is the cache interaction:

- :func:`prefill_forward` runs one (padded) prompt through full causal
  attention and writes every position's K/V into the sequence's
  pages;
- :func:`decode_forward` runs ONE token per batch slot, writes its
  K/V, and attends over the cache through the block table (the
  paged-attention path of ``ops.flash_attention``).

The kernel paths (``paged_impl="kernel"``, ``attention_impl="flash"``)
also write the pool through the aliased Pallas writes, the reference
paths through the XLA scatter (``serve.cache``).

Both are jit-pure: the engine compiles them once per static shape with
the cache donated. ``monitor.profile`` scopes (``serve_prefill`` /
``serve_decode`` + the per-module tags inside the TP layers) thread the
per-request cost attribution through the existing analytic walk.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.models.gpt import GPTConfig
from apex_tpu.monitor import profile as _prof
from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.ops.flash_attention import flash_attention, mha_reference
from apex_tpu.ops.paged_attention import (
    paged_attention_reference, paged_decode_attention)
from apex_tpu.serve import cache as cache_mod
from apex_tpu.serve import rules as rules_mod
from apex_tpu.transformer import parallel_state as ps
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    mappings as tp_mappings)

PAGED_IMPLS = ("reference", "kernel")
PREFILL_IMPLS = ("reference", "flash")


class GPTServed:
    """GPT behind the engine's model interface.

    **The interface** (``docs/serve.md``): what ``ServeEngine`` asks of a
    model, so that it, the scheduler and the page allocator serve any
    model that answers. An object with

    - ``cfg`` (the model's static sizes), ``max_seq_len``;
    - ``check(tp=, fp8_kv=, fp8_weights=, spec_k=, prefill_chunk=)``:
      raises for what the model cannot be served with, at engine
      construction;
    - ``page_geometry(tp)``: the per-rank kernel geometry
      ``serve.cache.resolve_page_size`` takes (``kv_heads``, ``head_dim``,
      ``dtype``);
    - ``cache_config(num_pages=, page_size=, fp8=, fp8_margin=,
      max_batch=)``: the ``CacheConfig`` of its cache: the pool's layers,
      the geometry of a layer's leaf and of one token's row in it and,
      where the model keeps them, the recurrent-state leaves (a row a
      batch row, hence ``max_batch``) and what it derives a page
      (``serve/cache.py``);
    - ``prefill(ccfg, params, state, block_table, length, ids, slot=,
      **impls)`` and ``decode(ccfg, params, state, block_tables,
      positions, tokens, active, **impls)``, jit-pure, returning ``(logits
      f32, new state, aux)``. ``slot`` is the sequence's batch row (where
      its recurrent state lives; a model without one drops it). An engine
      built with ``prefill_chunk`` passes ``start=`` as well: ``ids`` are
      then the ``prefill_chunk`` tokens at positions ``start ..``, of
      which ``length`` are live, ``block_table`` lists the prompt's pages
      from position 0, and the logits are those of the chunk's last live
      row. ``aux`` is ``{}`` or ``{"rows": {...}, "round": {...}}``:
      small arrays that leave the program beside the logits: ``rows``,
      kept with the logits under ``record_logits`` (a decode step's with
      one entry a batch row, a prefill's whole), and ``round`` for
      ``record_round``;
    - ``record_round(aux_round)``: the host's side of ``aux["round"]`` (the
      model's own counters), called with numpy values while a recorder is
      attached;
    - ``param_rules`` / ``cache_rules``: sharding rules for ``tp > 1``;
    - ``quantize_weights(params, margin=)`` and ``derive_draft(params,
      num_layers=)`` for ``fp8_weights`` and ``spec_k``.
    """

    param_rules = rules_mod.GPT_PARAM_RULES
    cache_rules = rules_mod.CACHE_RULES

    def __init__(self, cfg: GPTConfig):
        self.cfg = cfg

    @property
    def max_seq_len(self) -> int:
        return self.cfg.max_seq_len

    @property
    def head_dim(self) -> int:
        return self.cfg.hidden_size // self.cfg.num_heads

    def check(self, *, tp: int, prefill_chunk: int = 0, **_):
        if prefill_chunk:
            raise NotImplementedError(
                "serve/model.py: prefill takes a whole prompt (a chunk "
                "would need its position offset and attention over the "
                "pages before it: ROADMAP, queue R)")
        if self.cfg.num_heads % tp:
            raise ValueError(f"num_heads {self.cfg.num_heads} not "
                             f"divisible by tp {tp}")

    def page_geometry(self, tp: int) -> dict:
        # the pool is allocated at GLOBAL head count: under tp the
        # shard_map in_specs split the heads dim, each rank holding its
        # local heads' pages; page-size resolution sees the PER-RANK
        # kernel geometry
        return dict(kv_heads=self.cfg.num_heads // tp,
                    head_dim=self.head_dim, dtype=self.cfg.dtype)

    def cache_config(self, *, num_pages: int, page_size: int,
                     fp8: bool = False, fp8_margin: float = 2.0,
                     max_batch: int = 0):
        return cache_mod.CacheConfig(
            num_layers=self.cfg.num_layers, kv_heads=self.cfg.num_heads,
            head_dim=self.head_dim, num_pages=num_pages,
            page_size=page_size, dtype=self.cfg.dtype, fp8=fp8,
            fp8_margin=fp8_margin)

    def prefill(self, ccfg, params, state, block_table, length, ids, *,
                slot=None, **kw):
        logits, state = prefill_forward(self.cfg, ccfg, params, state,
                                        block_table, length, ids, **kw)
        return logits, state, {}

    def decode(self, ccfg, params, state, block_tables, positions, tokens,
               active, **kw):
        logits, state = decode_forward(self.cfg, ccfg, params, state,
                                       block_tables, positions, tokens,
                                       active, **kw)
        return logits, state, {}

    def record_round(self, aux_round) -> None:
        pass

    def quantize_weights(self, params, *, margin: float = 0.0):
        return quantize_gpt_weights(self.cfg, params, margin=margin)

    def derive_draft(self, params, *, num_layers: int):
        from apex_tpu.serve import spec as spec_mod
        cfg, draft = spec_mod.derive_draft(self.cfg, params,
                                           num_layers=num_layers)
        return GPTServed(cfg), draft


def as_served(model):
    """The engine's first argument as a served model: a ``GPTConfig``
    (what the engine has always taken) is wrapped, anything else is taken
    to answer the interface itself."""
    return GPTServed(model) if isinstance(model, GPTConfig) else model


def _mods(cfg: GPTConfig):
    h = cfg.hidden_size
    return dict(
        wte=VocabParallelEmbedding(num_embeddings=cfg.vocab_size,
                                   embedding_dim=h),
        ln=FusedLayerNorm(normalized_shape=h, dtype=cfg.dtype),
        qkv=ColumnParallelLinear(input_size=h, output_size=3 * h,
                                 gather_output=False),
        proj=RowParallelLinear(input_size=h, output_size=h,
                               input_is_parallel=True),
        fc1=ColumnParallelLinear(input_size=h, output_size=cfg.ffn,
                                 gather_output=False),
        fc2=RowParallelLinear(input_size=cfg.ffn, output_size=h,
                              input_is_parallel=True),
    )


def _apply(mod, sub, x):
    return mod.apply({"params": sub}, x)


def _linear(mod, sub, x, *, row=False, autotune=None, interpret=None):
    """One block linear, dispatching on the param LEAVES: a sub-tree
    carrying a ``scale`` sibling (written by :func:`quantize_gpt_weights`)
    streams its kernel as e4m3 through the fused dequant-matmul
    (``ops.fp8_matmul``, resolution explicit > tuned cache > reference);
    otherwise the ordinary TP layer module applies. The fp8 path
    replays the layer's TP semantics by hand — column shards need no
    collective in a serve forward, row shards psum — so the SAME
    shard_map in_specs serve both modes (the e4m3 kernel keeps the bf16
    kernel's shape, and the scalar scale falls to the rules'
    replicate catch-all)."""
    if "scale" not in sub:
        return _apply(mod, sub, x)
    from apex_tpu.ops import fp8_matmul as fp8mm
    y = fp8mm.fp8_dequant_matmul(x, sub["kernel"], sub["scale"],
                                 out_dtype=x.dtype, autotune=autotune,
                                 interpret=interpret)
    if row and ps.get_tensor_model_parallel_world_size() > 1:
        y = tp_mappings.reduce_from_tensor_model_parallel_region(
            y, ps.TENSOR_AXIS)
    if "bias" in sub:
        y = y + sub["bias"].astype(y.dtype)
    return y


_FP8_WEIGHT_LINEARS = (("attn", "qkv"), ("attn", "proj"),
                       ("mlp", "fc1"), ("mlp", "fc2"))


def _as_dict(tree):
    """Shallow plain-dict view of a mapping (dict or FrozenDict)."""
    return {k: tree[k] for k in tree}


def quantize_gpt_weights(cfg: GPTConfig, params, *, margin: float = 0.0):
    """Per-tensor e4m3 quantization of every block linear kernel
    (qkv / proj / fc1 / fc2): each ``kernel`` leaf is replaced by its
    fp8 encoding plus a sibling scalar ``scale`` leaf (amax-derived,
    :func:`apex_tpu.ops.fp8_matmul.quantize_weight`). Embeddings,
    positionals, norms and biases stay in their training dtype — they
    are a rounding error of the streamed bytes. Runs ONCE at engine
    build; the returned tree serves through the same shard_map specs
    (shapes unchanged; scales replicate)."""
    from apex_tpu.ops import fp8_matmul as fp8mm
    out = _as_dict(params)
    for i in range(cfg.num_layers):
        blk = _as_dict(out[f"block_{i}"])
        for group, name in _FP8_WEIGHT_LINEARS:
            grp = _as_dict(blk[group])
            lin = _as_dict(grp[name])
            q, scale = fp8mm.quantize_weight(lin["kernel"], margin=margin)
            lin["kernel"] = q
            lin["scale"] = scale
            grp[name] = lin
            blk[group] = grp
        out[f"block_{i}"] = blk
    return out


def weight_stream_bytes(cfg: GPTConfig, params) -> int:
    """HBM bytes of the block linear weights one decode step streams
    (kernels + fp8 scales; biases/norms excluded on both sides so the
    fp8-vs-bf16 ratio measures exactly what quantization changed).
    Host-side ints — the ``monitor.memory`` serve weight accounting and
    the bench's streamed-bytes assertion both come from here."""
    import numpy as np
    total = 0
    for i in range(cfg.num_layers):
        blk = params[f"block_{i}"]
        for group, name in _FP8_WEIGHT_LINEARS:
            lin = blk[group][name]
            kern = lin["kernel"]
            total += kern.size * np.dtype(kern.dtype).itemsize
            if "scale" in lin:
                scale = lin["scale"]
                total += scale.size * np.dtype(scale.dtype).itemsize
    return int(total)


def _split_qkv(cfg: GPTConfig, qkv):
    """[..., 3h/tp] -> q, k, v [..., heads_per, d] (the GPT packing:
    per-head [q|k|v] groups, so the tp column shard is a head split)."""
    tp = ps.get_tensor_model_parallel_world_size()
    heads_per = cfg.num_heads // tp
    d = cfg.hidden_size // cfg.num_heads
    qkv = qkv.reshape(qkv.shape[:-1] + (heads_per, 3 * d))
    return jnp.split(qkv, 3, axis=-1)


def _logits(cfg: GPTConfig, mods, params, x):
    """Vocab-parallel LM head + full-vocab gather (serve samples on the
    host; decode needs the whole row for argmax/top-k)."""
    with _prof.scope("lm_head"):
        emb = params["wte"]
        wte = mods["wte"]
        logits = wte.apply({"params": emb}, x, method=wte.attend)
        if ps.get_tensor_model_parallel_world_size() > 1:
            logits = tp_mappings.gather_from_tensor_model_parallel_region(
                logits, ps.TENSOR_AXIS, -1)
        return logits.astype(jnp.float32)


def _mlp(cfg: GPTConfig, mods, blk, x, lin_kw):
    y = _linear(mods["fc1"], blk["mlp"]["fc1"], x, **lin_kw)
    y = jax.nn.gelu(y.astype(jnp.float32), approximate=True).astype(x.dtype)
    return _linear(mods["fc2"], blk["mlp"]["fc2"], y, row=True, **lin_kw)


def _block_forward(cfg: GPTConfig, mods, blk, x, attend, lin_kw=None):
    """One transformer block — the ONE copy of the serve-side block
    structure (shared by decode, prefill and the no-cache baseline).
    ``attend(q, k, v)`` owns the per-variant cache interaction and
    returns the context in ``x``'s leading shape + ``[..., local_h]``.
    ``lin_kw`` threads the fp8-weight resolution knobs
    (autotune/interpret) into the four block linears.
    """
    lin_kw = lin_kw or {}
    h1 = _apply(mods["ln"], blk["ln1"], x)
    q, k, v = _split_qkv(cfg, _linear(mods["qkv"], blk["attn"]["qkv"], h1,
                                      **lin_kw))
    ctx = attend(q, k, v)
    x = x + _linear(mods["proj"], blk["attn"]["proj"],
                    ctx.astype(cfg.dtype), row=True, **lin_kw)
    h2 = _apply(mods["ln"], blk["ln2"], x)
    return x + _mlp(cfg, mods, blk, h2, lin_kw)


def decode_forward(cfg: GPTConfig, ccfg: cache_mod.CacheConfig, params,
                   state: cache_mod.CacheState, block_tables, positions,
                   tokens, active, *, paged_impl: str = "reference",
                   interpret: Optional[bool] = None,
                   autotune: Optional[str] = None):
    """One decode step over a fixed-capacity batch.

    ``tokens``/``positions``/``active``: [B] (the token being fed, its
    position = index in the sequence, and whether the slot is live —
    inactive slots carry token 0, position 0 and write to the null
    page). ``block_tables``: [B, m] int32. Returns ``(logits [B, V]
    f32, new_state)`` — rows of inactive slots are garbage by contract.
    Every slot's row depends only on its own inputs (no cross-row
    reduction anywhere), which is what makes decode-replay after a
    preemption bit-exact regardless of batch company.
    """
    if paged_impl not in PAGED_IMPLS:
        raise ValueError(f"paged_impl must be one of {PAGED_IMPLS}, got "
                         f"{paged_impl!r}")
    mods = _mods(cfg)
    B = tokens.shape[0]
    lin_kw = dict(autotune=autotune, interpret=interpret)
    with _prof.scope("serve_decode"):
        x = _apply(mods["wte"], params["wte"], tokens)
        x = (x + jnp.take(params["wpe"], positions, axis=0)).astype(cfg.dtype)
        seq_lens = jnp.where(active, positions + 1, 0).astype(jnp.int32)
        page_ids = jnp.where(
            active,
            block_tables[jnp.arange(B), positions // ccfg.page_size],
            0).astype(jnp.int32)
        slots = jnp.where(active, positions % ccfg.page_size,
                          0).astype(jnp.int32)
        # state is threaded through the attend closure: python-level
        # mutation is safe here because the layer loop is sequential
        # trace-time code
        state_box = [state]
        for i in range(cfg.num_layers):
            def attend(q, k, v, *, _i=i):
                state_box[0] = cache_mod.write_token(
                    ccfg, state_box[0], _i, page_ids, slots, k, v,
                    impl=paged_impl, interpret=interpret)
                st = state_box[0]
                with _prof.scope("paged_attn"):
                    q4 = q[:, :, None, :]            # [B, hp, group=1, d]
                    scales = {}
                    if ccfg.fp8:
                        scales = dict(k_scales=st.k_scale[_i],
                                      v_scales=st.v_scale[_i])
                    if paged_impl == "kernel":
                        ctx = paged_decode_attention(
                            q4, st.pools[_i], block_tables, seq_lens,
                            interpret=interpret, **scales)
                    else:
                        ctx = paged_attention_reference(
                            q4, st.pools[_i], block_tables, seq_lens,
                            **scales)
                return ctx[:, :, 0, :].reshape(B, -1)

            with _prof.scope(f"block_{i}"):
                x = _block_forward(cfg, mods, params[f"block_{i}"], x,
                                   attend, lin_kw)
        x = _apply(mods["ln"], params["ln_f"], x)
        return _logits(cfg, mods, params, x), state_box[0]


def prefill_forward(cfg: GPTConfig, ccfg: cache_mod.CacheConfig, params,
                    state: cache_mod.CacheState, block_table, length,
                    ids, *, attention_impl: str = "reference",
                    interpret: Optional[bool] = None,
                    autotune: Optional[str] = None):
    """Full-prompt pass for ONE sequence (padded to the engine's static
    prompt length). ``ids``: [S] int32 (padded with anything past
    ``length``); ``block_table``: [m] int32 — pages covering positions
    ``0..length-1`` (padded entries unused). Writes every live
    position's K/V and returns ``(logits [V] f32 for position
    length-1, new_state)``.
    """
    if attention_impl not in PREFILL_IMPLS:
        raise ValueError(f"attention_impl must be one of {PREFILL_IMPLS}, "
                         f"got {attention_impl!r}")
    mods = _mods(cfg)
    S = ids.shape[0]
    d = cfg.hidden_size // cfg.num_heads
    lin_kw = dict(autotune=autotune, interpret=interpret)
    write_impl = "kernel" if attention_impl == "flash" else "reference"
    with _prof.scope("serve_prefill"):
        x = _apply(mods["wte"], params["wte"], ids[None])
        x = (x + params["wpe"][None, :S]).astype(cfg.dtype)
        sid = jnp.where(jnp.arange(S) < length, 0, -1)[None].astype(jnp.int32)
        state_box = [state]
        for i in range(cfg.num_layers):
            def attend(q, k, v, *, _i=i):
                state_box[0] = cache_mod.write_prompt(
                    ccfg, state_box[0], _i, block_table, length, k[0],
                    v[0], impl=write_impl, interpret=interpret)
                ctx = _causal_attend(q, k, v, d, sid, attention_impl,
                                     interpret, "prefill_attn")
                return ctx.reshape(1, S, -1)

            with _prof.scope(f"block_{i}"):
                x = _block_forward(cfg, mods, params[f"block_{i}"], x,
                                   attend, lin_kw)
        x = _apply(mods["ln"], params["ln_f"], x)
        x_last = jnp.take(x[0], length - 1, axis=0)
        return _logits(cfg, mods, params, x_last), state_box[0]


def _causal_attend(q, k, v, d, sid, attention_impl, interpret, scope):
    """Full causal attention over padded [b, S] token batches with
    padding segment ids — the shared attention of prefill and the
    no-cache baseline. Returns [b, S, hp, d]-shaped context."""
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    with _prof.scope(scope):
        if attention_impl == "flash":
            ctx = flash_attention(qh, kh, vh, causal=True, scale=d ** -0.5,
                                  segment_ids_q=sid, interpret=interpret)
        else:
            ctx = mha_reference(qh, kh, vh, causal=True, scale=d ** -0.5,
                                segment_ids_q=sid)
    return ctx.transpose(0, 2, 1, 3)


def full_forward_logits(cfg: GPTConfig, params, ids, lengths, *,
                        attention_impl: str = "reference"):
    """The NO-cache baseline forward: full causal attention over the
    whole padded context, logits at each row's last live position.
    ``ids``: [B, S] int32, ``lengths``: [B] int32. One fixed-shape
    program regardless of how far generation has progressed — this is
    what "naive full-recompute decode" pays per token, and what the
    ``serve_decode`` bench section measures the paged cache against.
    """
    if attention_impl not in PREFILL_IMPLS:
        raise ValueError(f"attention_impl must be one of {PREFILL_IMPLS}, "
                         f"got {attention_impl!r}")
    mods = _mods(cfg)
    B, S = ids.shape
    d = cfg.hidden_size // cfg.num_heads
    x = _apply(mods["wte"], params["wte"], ids)
    x = (x + params["wpe"][None, :S]).astype(cfg.dtype)
    sid = jnp.where(jnp.arange(S)[None, :] < lengths[:, None], 0,
                    -1).astype(jnp.int32)
    for i in range(cfg.num_layers):
        def attend(q, k, v):
            return _causal_attend(q, k, v, d, sid, attention_impl, None,
                                  "full_attn").reshape(B, S, -1)

        x = _block_forward(cfg, mods, params[f"block_{i}"], x, attend)
    x = _apply(mods["ln"], params["ln_f"], x)
    x_last = jnp.take_along_axis(x, (lengths - 1)[:, None, None],
                                 axis=1)[:, 0]
    return _logits(cfg, mods, params, x_last)
