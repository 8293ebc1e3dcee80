"""Serving a DeepSeek-V3-shaped model (:mod:`apex_tpu.models.deepseek`)
through the engine: one latent attention a layer over the paged latent
cache, then a feed-forward that is dense in the leading layers and the
expert layer (:mod:`apex_tpu.transformer.moe_dropless`) after them. The
cache, both attention forms and the forwards around the layer are
:mod:`apex_tpu.serve.latent`'s::

    eng = ServeEngine(DeepseekServed(cfg), params, num_pages=..., ...)
"""

from __future__ import annotations

from apex_tpu.models import deepseek as ds
from apex_tpu.monitor import profile as _prof
from apex_tpu.serve.latent import (LatentServed, attention_sublayer,
                                   latent_row_width)  # noqa: F401
from apex_tpu.transformer.moe_dropless import expert_layer


class DeepseekServed(LatentServed):
    """The model behind the engine's interface."""

    def block(self, i, layer, x, positions, attend, moe_kw, stats):
        cfg = self.cfg
        x = attention_sublayer(cfg, i, layer["attn"], layer["attn_norm"], x,
                               positions, attend)
        h = ds.rms_norm(x, layer["ffn_norm"], cfg.rms_norm_eps)
        if "moe" in layer:
            y, st = expert_layer(cfg, layer["moe"], h, **moe_kw)
            stats.append(st)
            return x + y
        with _prof.scope("mlp"):
            m = layer["mlp"]
            return x + ds.gated_mlp(h, m["gate"], m["up"], m["down"])
