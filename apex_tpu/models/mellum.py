"""Mellum 2 decoders (``model_type: mellum``, JetBrains
``Mellum2-12B-A2.5B-Instruct``): pre-RMSNorm blocks of grouped-query
attention that is a SLIDING WINDOW in three layers of four and full in the
fourth, each followed by an expert layer (softmax router, top-k,
renormalised, no shared expert); no bias anywhere, untied head. The model
of this library that TRAINS through its expert layer.

    a = x + Wo . Attn(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x))
    y = a + sum_{e in top-k} w_e . down_e(silu(gate_e n2(a)) * up_e n2(a))
    p = softmax(n2(a) Wr) in float32 over ALL experts, w = p_top / sum(p_top)

``layer_types[i]`` names layer ``i``'s attention: ``sliding_attention``
(causal, query ``i`` sees keys ``j`` with ``i - sliding_window < j <= i``;
plain rotary embedding) or ``full_attention`` (causal; YaRN frequencies and
the factor ``0.1 ln(factor) + 1`` on cos and sin: ``models/deepseek.py``'s
``yarn_inv_freq`` and ``rope_factor``). Rotary pairs are the lanes ``(i, i +
head_dim / 2)``. Attention is :func:`apex_tpu.ops.flash_attention.
flash_attention` with ``window=`` and the key/value heads as they are
(``num_kv_heads`` of them: the kernels index ``h // group``, nothing is
repeated); the expert layer is :func:`apex_tpu.transformer.moe_dropless.
expert_layer`; the loss is the fused LM-head cross entropy over the rows of
the vocabulary held here.

**What a block keeps.** Every block runs under ``jax.checkpoint``: the
backward holds a block's input and runs the block again (the norms, the
output projection, the whole expert layer with its worst-case row buffers),
EXCEPT what the flash kernels' backward reads, kept by name: the kernel's
output and log-sum-exp (``FLASH_OUT``, ``FLASH_LSE`` of
``ops/flash_attention.py``) and its three operands, the rotated ``q`` and
``k`` and ``v`` (:data:`QKV`). The forward kernel, the three projections
and the rotation run once a layer a step.

**A chip's share**, as in ``models/deepseek.py``: ``n_local_experts`` of the
``n_routed_experts`` from ``first_expert`` (the router keeps its published
width; what the absent experts would add is left out and the partial result
goes on); ``vocab_size`` is the number of rows held.

Parameter tree (``h`` hidden, ``n`` query heads, ``m`` key/value heads, ``d``
head size)::

    embed [V, h]   head [V, h]   norm_f [h]
    layer_i/attn_norm, ffn_norm [h]
           /attn/q [h, n*d]  k, v [h, m*d]  o [n*d, h]
           /moe/router [h, E]
               /experts/gate_up [n_local, h, 2*Im]  down [n_local, Im, h]

Import the module by name (``apex_tpu.models`` does not).
"""

from __future__ import annotations

import dataclasses
import functools
import types
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu.models import deepseek as _ds
from apex_tpu.monitor import hooks as _mon
from apex_tpu.monitor import profile as _prof
from apex_tpu.ops.flash_attention import (FLASH_LSE, FLASH_OUT,
                                          flash_attention)
from apex_tpu.ops.lm_head_ce import fused_lm_head_cross_entropy
from apex_tpu.transformer import moe_dropless

SLIDING, FULL = "sliding_attention", "full_attention"
#: the name under which ``attention`` tags a layer's rotated queries and keys
#: and its values (``jax.ad_checkpoint.checkpoint_name``), the flash kernel's
#: three operands, for the recomputed blocks' policy
QKV = "mellum_attention_qkv"
#: what a layer's expert layer counts, in the loss's ``aux`` and as counters
MOE_COUNTS = ("assignments_local", "expert_load_max", "experts_touched",
              "rows_moved")


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """Static sizes (hashable). Field names follow the published keys, but
    ``num_layers`` / ``num_heads`` / ``num_kv_heads`` / ``n_routed_experts``
    (``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
    ``num_experts``), which the shared code reads under those names."""

    vocab_size: int                     # rows held here
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    moe_intermediate_size: int
    n_routed_experts: int               # the router's width
    num_experts_per_tok: int
    layer_types: Tuple[str, ...]        # one entry a layer held here
    sliding_window: int
    first_expert: int = 0
    n_local_experts: Optional[int] = None   # None = all of them
    rms_norm_eps: float = 1e-6
    rope_theta: float = 500000.0
    #: ((key, value), ...) of ``rope_parameters.full_attention`` (YaRN), or ()
    rope_scaling: Tuple[Tuple[str, Any], ...] = ()
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02

    #: what the expert layer asks of a description (``moe_dropless``)
    routing = "softmax_topk_renorm"
    zero_expert_num = 0

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if any(t not in (SLIDING, FULL) for t in self.layer_types):
            raise ValueError(f"layer_types {self.layer_types}")
        n = self.local_experts
        if not 0 <= self.first_expert <= self.n_routed_experts - n:
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert + n}) "
                f"are not among the {self.n_routed_experts} routed ones")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def local_experts(self) -> int:
        return (self.n_routed_experts if self.n_local_experts is None
                else self.n_local_experts)


def init_params(cfg: MellumConfig, key):
    """Seeded random weights in ``cfg.dtype`` (normal, ``init_std``; norm
    weights 1, float32; the router float32 as published: ``amp``'s O2 casts
    the model's copy of both). Jit-pure."""
    h, dt, std = cfg.hidden_size, cfg.dtype, cfg.init_std
    n, m, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    im, nl = cfg.moe_intermediate_size, cfg.local_experts
    keys = iter(jax.random.split(key, 2 + 7 * cfg.num_layers))

    def w(*shape, dtype=dt):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    def ones():
        return jnp.ones((h,), jnp.float32)

    params = {"embed": w(cfg.vocab_size, h), "head": w(cfg.vocab_size, h),
              "norm_f": ones()}
    for i in range(cfg.num_layers):
        params[f"layer_{i}"] = {
            "attn_norm": ones(), "ffn_norm": ones(),
            "attn": {"q": w(h, n * d), "k": w(h, m * d), "v": w(h, m * d),
                     "o": w(n * d, h)},
            "moe": {"router": w(h, cfg.n_routed_experts, dtype=jnp.float32),
                    "experts": {"gate_up": w(nl, h, 2 * im),
                                "down": w(nl, im, h)}}}
    return params


# -- attention ---------------------------------------------------------------

def _rope_of(cfg, kind: str):
    """The description ``models/deepseek.py``'s YaRN functions read, for a
    layer of ``kind``: the whole head rotates; YaRN in the full layers."""
    return types.SimpleNamespace(
        qk_rope_head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        rope_scaling=cfg.rope_scaling if kind == FULL else ())


def rope(x, positions, cfg, kind: str):
    """Rotate the pairs ``(i, i + d/2)`` of ``x`` ``[b, n, s, d]`` by
    ``positions`` ``[s]``, in float32, back in ``x.dtype``."""
    desc = _rope_of(cfg, kind)
    ang = positions.astype(jnp.float32)[:, None] * _ds.yarn_inv_freq(desc)
    factor = _ds.rope_factor(desc)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor    # [s, d/2]
    x32 = x.astype(jnp.float32)
    a, b = jnp.split(x32, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def attention(cfg, p, x, kind: str, *, interpret=None):
    """One attention sub-layer's branch for normalised ``x`` ``[b, s, h]``:
    projections, rotation, the flash kernel and the output projection,
    under ``apx:attn_window`` or ``apx:attn_full``."""
    b, s, _ = x.shape
    n, m, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    window = cfg.sliding_window if kind == SLIDING else None
    with _prof.scope("attn_window" if window else "attn_full"):
        def heads(w, count):
            return jnp.dot(x, w).reshape(b, s, count, d).transpose(0, 2, 1, 3)

        pos = jnp.arange(s)
        q, k, v = checkpoint_name(
            (rope(heads(p["q"], n), pos, cfg, kind),
             rope(heads(p["k"], m), pos, cfg, kind), heads(p["v"], m)), QKV)
        o = flash_attention(q, k, v, causal=True, window=window,
                            scale=d ** -0.5, interpret=interpret)
        return jnp.dot(o.transpose(0, 2, 1, 3).reshape(b, s, n * d), p["o"])


# -- the model ---------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _kept():
    """What a recomputed block keeps. ONE object for every layer: JAX caches
    a jitted call's split into kept and recomputed parts by the policy's
    identity, so a policy a layer would lower the flash kernel once a layer."""
    return jax.checkpoint_policies.save_only_these_names(FLASH_OUT, FLASH_LSE,
                                                         QKV)


def _block(cfg, kind, p, x, impl, interpret):
    b, s, h = x.shape
    eps = cfg.rms_norm_eps
    x = x + attention(cfg, p["attn"], _ds.rms_norm(x, p["attn_norm"], eps),
                      kind, interpret=interpret)
    y, stats = moe_dropless.expert_layer(
        cfg, p["moe"], _ds.rms_norm(x, p["ffn_norm"], eps).reshape(b * s, h),
        impl=impl, interpret=interpret)
    return x + y.reshape(b, s, h), stats


def hidden(cfg: MellumConfig, params, ids, *, impl: str = "kernel",
           interpret=None):
    """The final normalised hidden state ``[b, s, h]`` for token ids ``[b,
    s]`` and ``aux``: ``{"moe": {name: int32 [layers]} for name in
    MOE_COUNTS, "moe_idx": the experts each token chose [layers, b*s, k]}``.
    ``impl``: the grouped matmul's (``ops.grouped_matmul.IMPLS``)."""
    x = jnp.take(params["embed"], ids, axis=0)
    stats = []
    for i, kind in enumerate(cfg.layer_types):
        # the backward keeps a layer's input and runs the layer again: the
        # expert layer's worst-case row buffers of four layers fit no chip.
        # It keeps the flash kernel's operands and its two results too: 304
        # MB a layer at 2 x 8,192 tokens, which cost 4.7-9.8 ms of kernel
        # and ~5 ms of projections and rotation a layer to rebuild
        block = jax.checkpoint(
            functools.partial(_block, cfg, kind, impl=impl,
                              interpret=interpret), policy=_kept())
        x, st = block(params[f"layer_{i}"], x)
        stats.append(st)
    aux = {"moe": {k: jnp.stack([st[k] for st in stats])
                   for k in MOE_COUNTS},
           "moe_idx": jnp.stack([st["idx"] for st in stats])}
    return _ds.rms_norm(x, params["norm_f"], cfg.rms_norm_eps), aux


def forward(cfg: MellumConfig, params, ids, **kw):
    """``(logits [b, s, V] over the rows held, aux)``."""
    x, aux = hidden(cfg, params, ids, **kw)
    return jnp.dot(x, params["head"].T), aux


def loss(cfg: MellumConfig, params, ids, labels, **kw):
    """``(mean next-token cross entropy over the rows of the vocabulary
    held here, aux)`` through the fused LM-head kernel: the ``[tokens, V]``
    logits are never in memory. ``aux`` without the per-token choices."""
    x, aux = hidden(cfg, params, ids, **kw)
    per_token = fused_lm_head_cross_entropy(
        x, params["head"], labels, interpret=kw.get("interpret"))
    return jnp.mean(per_token), {"moe": aux["moe"]}


def record_step(aux) -> None:
    """A step's expert-layer counts as counters on the attached recorder:
    ``moe/assignments_local`` (first: a reader opens a step at layer 0's),
    ``moe/expert_load_max``, ``moe/experts_touched``, ``moe/rows_moved``
    (those of :data:`MOE_COUNTS` that ``aux`` holds), one event a layer
    (``layer=``). For whoever owns the loop, on an ``aux`` it has FETCHED
    (this reads the values)."""
    import numpy as np
    counts = {k: np.asarray(aux["moe"][k]) for k in MOE_COUNTS
              if k in aux["moe"]}
    for layer in range(len(counts[MOE_COUNTS[0]])):
        for name, per_layer in counts.items():
            _mon.counter(f"moe/{name}", int(per_layer[layer]), layer=layer)
