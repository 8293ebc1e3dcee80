"""Serving a LongCat-Flash model (:mod:`apex_tpu.models.longcat`) through
the engine. A published layer is attention 0, then the expert layer AND
dense feed-forward 0 both fed from the same normalised state, then
attention 1, then dense feed-forward 1, and only then is the expert
layer's output added (shortcut-connected MoE): two latent rows a token a
layer in two leaves of the pool (``CacheConfig.num_layers`` = 2 x the
model's layers), and the expert layer's result alive across an attention
and a feed-forward. The cache, both attention forms and the forwards around
the layer are :mod:`apex_tpu.serve.latent`'s::

    eng = ServeEngine(LongcatServed(cfg), params, num_pages=..., ...)

**Where it rounds.** Every matmul takes ``cfg.dtype`` (bf16) rows and
weights, as DeepSeek's do, but this model keeps the residual stream and the
values between a projection and its norm, scale or rotation in float32
(``residual_dtype``, the ``acc`` of ``models.deepseek.attention_inputs``
and ``gated_mlp``): the two latent scales make the attention scores ~7 x
those of an unscaled latent, a bf16 rounding on their way costs the logits
that much more, and a layer has twice the sub-layers. On the chip it is
the difference between 0.033-0.051 and 0.027-0.046 of the benchmark's
``rel_err`` against its ceiling of 0.05, at no cost to the round (PERF.md,
PR 31).
"""

from __future__ import annotations

import jax.numpy as jnp

from apex_tpu.models import deepseek as ds
from apex_tpu.monitor import profile as _prof
from apex_tpu.serve.latent import LatentServed, attention_sublayer
from apex_tpu.transformer.moe_dropless import expert_layer


class LongcatServed(LatentServed):
    """The model behind the engine's interface."""

    leaves_per_layer = 2
    residual_dtype = jnp.float32

    def block(self, i, layer, x, positions, attend, moe_kw, stats):
        cfg = self.cfg
        inputs_kw = dict(q_scale=cfg.q_scale, kv_scale=cfg.kv_scale,
                         acc=self.residual_dtype)
        for j in (0, 1):
            sub = layer[f"sub_{j}"]
            x = attention_sublayer(cfg, 2 * i + j, sub["attn"],
                                   sub["attn_norm"], x, positions, attend,
                                   **inputs_kw)
            m = ds.rms_norm(x, sub["ffn_norm"],
                            cfg.rms_norm_eps).astype(cfg.dtype)
            if j == 0:          # the shortcut: computed here, added last
                shortcut, st = expert_layer(cfg, layer["moe"], m, **moe_kw)
                stats.append(st)
            with _prof.scope("dense_ffn"):
                mlp = sub["mlp"]
                x = x + ds.gated_mlp(m, mlp["gate"], mlp["up"], mlp["down"],
                                     acc=self.residual_dtype)
        return x + shortcut
