"""LongCat-Flash decoders (``meituan-longcat/LongCat-Flash-Chat``): every
published layer holds TWO latent attentions and TWO dense gated-SiLU
feed-forwards, and one expert layer on a shortcut: it reads the state after
attention 0 and is added after feed-forward 1. The router is a softmax over
``n_routed_experts + zero_expert_num`` slots with a correction bias that
moves the choice only, top ``moe_topk`` with no groups and no
renormalisation, times ``routed_scaling_factor``; a chosen zero-compute slot
returns the layer's input times its weight. No shared expert, no bias,
untied head.

This module is the static description and the parameter tree; the attention
mathematics (:func:`~apex_tpu.models.deepseek.attention_inputs`, ``rope``,
``rms_norm``, ``gated_mlp``, ``kv_b_heads``) is :mod:`apex_tpu.models.deepseek`'s,
which reads the sizes it needs from any description that has them. The
serving forwards are in :mod:`apex_tpu.serve.longcat`, the expert layer in
:mod:`apex_tpu.transformer.moe_dropless`. Import the module by name.

**A chip's share**, as in ``models/deepseek.py``: ``n_local_experts`` of the
``n_routed_experts`` from ``first_expert``; the router keeps its published
width (zero-compute slots hold no weights: every chip computes them for the
tokens it holds); ``vocab_size`` is the number of rows held.

Parameter tree (``h`` hidden, ``n`` heads, ``j`` in 0, 1)::

    embed [V, h]   head [h, V]   norm_f [h]
    layer_i/sub_j/attn_norm, ffn_norm [h]
                 /attn/q_a, q_norm, q_b, kv_a, kv_norm, kv_b, o   (deepseek's)
                 /mlp/gate, up [h, ffn]  down [ffn, h]
    layer_i/moe/router [h, E + Z] f32  bias [E + Z] f32
               /experts/gate_up [n_local, h, 2*Im]  down [n_local, Im, h]
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from apex_tpu.models.deepseek import attention_params


@dataclasses.dataclass(frozen=True)
class LongcatConfig:
    """Static sizes (hashable). Field names follow the published keys, but
    ``num_heads`` (``num_attention_heads``), which the shared attention
    mathematics reads under that name."""

    vocab_size: int                     # rows held here
    hidden_size: int
    num_layers: int                     # published layers: two sub-layers each
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    ffn_hidden_size: int                # the dense feed-forwards
    expert_ffn_hidden_size: int
    n_routed_experts: int               # the router's real experts
    zero_expert_num: int                # its identity slots, after them
    moe_topk: int
    routed_scaling_factor: float = 1.0
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    first_expert: int = 0
    n_local_experts: Optional[int] = None   # None = all of them
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02

    routing = "softmax_topk"            # transformer/moe_dropless.py
    rope_scaling = ()                   # plain rotary (models/deepseek.py)

    def __post_init__(self):
        n = self.local_experts
        if not 0 <= self.first_expert <= self.n_routed_experts - n:
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert + n}) "
                f"are not among the {self.n_routed_experts} routed ones")

    @property
    def local_experts(self) -> int:
        return (self.n_routed_experts if self.n_local_experts is None
                else self.n_local_experts)

    @property
    def router_slots(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def q_scale(self) -> float:
        """On the normalised query latent: ``(hidden / q_lora_rank)^0.5``."""
        return ((self.hidden_size / self.q_lora_rank) ** 0.5
                if self.mla_scale_q_lora else 1.0)

    @property
    def kv_scale(self) -> float:
        return ((self.hidden_size / self.kv_lora_rank) ** 0.5
                if self.mla_scale_kv_lora else 1.0)


def init_params(cfg: LongcatConfig, key):
    """Seeded random weights in ``cfg.dtype`` (normal, ``init_std``; norm
    weights 1; router and its correction bias float32). The bias is normal
    at half a uniform slot's score, ``0.5 / router_slots``: small beside the
    scores that decide (a chosen slot's is ~10 x that), non-zero so that it
    is exercised. Jit-pure."""
    h, dt, std = cfg.hidden_size, cfg.dtype, cfg.init_std
    im, nl, f = cfg.expert_ffn_hidden_size, cfg.local_experts, \
        cfg.ffn_hidden_size
    keys = iter(jax.random.split(key, 4 + 32 * cfg.num_layers))

    def w(*shape, dtype=dt, std=std):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    def ones(d):
        return jnp.ones((d,), jnp.float32)

    def sub():
        return {"attn_norm": ones(h), "ffn_norm": ones(h),
                "attn": attention_params(cfg, w, ones),
                "mlp": {"gate": w(h, f), "up": w(h, f), "down": w(f, h)}}

    params = {"embed": w(cfg.vocab_size, h), "head": w(h, cfg.vocab_size),
              "norm_f": ones(h)}
    for i in range(cfg.num_layers):
        params[f"layer_{i}"] = {
            "sub_0": sub(), "sub_1": sub(),
            "moe": {
                "router": w(h, cfg.router_slots, dtype=jnp.float32),
                "bias": w(cfg.router_slots, dtype=jnp.float32,
                          std=0.5 / cfg.router_slots),
                "experts": {"gate_up": w(nl, h, 2 * im),
                            "down": w(nl, im, h)}}}
    return params
