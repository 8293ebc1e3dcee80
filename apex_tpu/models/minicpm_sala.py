"""MiniCPM-SALA decoders (``openbmb/MiniCPM-SALA``): a stack that mixes two
kinds of attention layer, ``mixer_types`` says which a layer:

- ``lightning-attn``: linear attention with a per-head scalar decay over a
  recurrent state ``f32[d, d]`` a head (:mod:`apex_tpu.ops.lightning_attention`):
  ``lightning_nh`` heads for q, k and v alike, RMSNorm a head on q and k
  (``qk_norm``), rotary on q and k (``lightning_use_rope``), the output
  RMSNorm-ed and gated by ``sigmoid(W_g x)`` before ``W_o``;
- ``minicpm4``: grouped-query softmax attention (``num_key_value_heads`` K|V
  heads), RMSNorm a head on q and k, NO rotary, the context gated by
  ``sigmoid(W_g x)`` before ``W_o``; over a context longer than
  ``dense_len`` it attends a selection of blocks (InfLLM-v2,
  :mod:`apex_tpu.ops.sparse_attention`).

Every layer is ``h <- h + (scale_depth / sqrt(mup_denominator)) *
f(RMSNorm(h))`` for its attention and again for its gated-SiLU MLP; ``h0 =
scale_emb * E[ids]``; ``logits = W_head (RMSNorm(h) / (hidden_size /
dim_model_base))``. The residual scale is the PUBLISHED depth's whatever
depth is held.

This module is the static description and the parameter tree; ``rms_norm``,
``rope`` and ``gated_mlp`` are :mod:`apex_tpu.models.deepseek`'s. The serving
forwards are in :mod:`apex_tpu.serve.minicpm_sala`. Import the module by name.

Parameter tree (``h`` hidden, ``n`` heads, ``d`` head size)::

    embed [V, h]   head [h, V]   norm_f [h]
    layer_i/attn_norm, mlp_norm [h]
           /attn/q [h, n d]  k, v [h, n_kv d]  gate [h, n d]  o [n d, h]
                /q_norm, k_norm [d]   (lightning: + o_norm [n d])
           /mlp/gate, up [h, I]  down [I, h]
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops.sparse_attention import SparseSpec

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


@dataclasses.dataclass(frozen=True)
class MiniCPMSalaConfig:
    """Static sizes (hashable); field names are the published keys."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    mixer_types: Tuple[str, ...]            # one a layer HELD
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    mup_denominator: int = 32               # the published depth
    dim_model_base: int = 256
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    sparse: SparseSpec = SparseSpec()
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02

    rope_scaling = ()                       # plain rotary (models/deepseek.py)

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        bad = set(self.mixer_types) - {SPARSE, LIGHTNING}
        if bad or not self.mixer_types:
            raise ValueError(f"mixer_types must hold {SPARSE!r} and "
                             f"{LIGHTNING!r}, got {sorted(bad)}")
        if self.lightning_nkv != self.lightning_nh:
            raise NotImplementedError("lightning_nkv != lightning_nh")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def group(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def qk_rope_head_dim(self) -> int:      # what models.deepseek.rope reads
        return self.lightning_head_dim

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / self.mup_denominator ** 0.5

    @property
    def logit_divisor(self) -> float:
        return self.hidden_size / self.dim_model_base

    def leaf(self, layer: int) -> int:
        """A layer's index among the cache leaves of ITS kind: the K|V pool
        leaves (sparse layers) or the state leaves (lightning layers)."""
        kind = self.mixer_types[layer]
        return sum(t == kind for t in self.mixer_types[:layer])

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.mixer_types)


def init_params(cfg: MiniCPMSalaConfig, key):
    """Seeded random weights in ``cfg.dtype`` (normal, ``init_std``; norm
    weights 1, float32). Jit-pure."""
    h, dt = cfg.hidden_size, cfg.dtype
    keys = iter(jax.random.split(key, 2 + 8 * cfg.num_layers))

    def w(*shape):
        return (cfg.init_std * jax.random.normal(next(keys), shape,
                                                 jnp.float32)).astype(dt)

    def ones(d):
        return jnp.ones((d,), jnp.float32)

    def attn(kind):
        if kind == LIGHTNING:
            n = n_kv = cfg.lightning_nh
            d = cfg.lightning_head_dim
        else:
            n, n_kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                          cfg.head_dim)
        p = {"q": w(h, n * d), "k": w(h, n_kv * d), "v": w(h, n_kv * d),
             "gate": w(h, n * d), "o": w(n * d, h),
             "q_norm": ones(d), "k_norm": ones(d)}
        if kind == LIGHTNING:
            p["o_norm"] = ones(n * d)
        return p

    params = {"embed": w(cfg.vocab_size, h), "head": w(h, cfg.vocab_size),
              "norm_f": ones(h)}
    f = cfg.intermediate_size
    for i, kind in enumerate(cfg.mixer_types):
        params[f"layer_{i}"] = {
            "attn_norm": ones(h), "mlp_norm": ones(h), "attn": attn(kind),
            "mlp": {"gate": w(h, f), "up": w(h, f), "down": w(f, h)}}
    return params
