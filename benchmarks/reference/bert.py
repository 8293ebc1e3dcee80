"""BERT, plainly: ``jax.numpy`` in float32, no kernels. Follows Devlin et
al. 2018 and the ``config.json`` keys in
``benchmarks/configs/bert-large.json``: token + position + segment
embeddings, LayerNorm, post-LayerNorm blocks with bidirectional attention,
erf GELU, LayerNorm eps 1e-12, and the MLM head (dense, GELU, LayerNorm,
tied decoder).

Takes the parameter tree of ``apex_tpu.models.bert.Bert`` at tp=1.
Departures the tree forces: per-head ``[q|k|v]`` packing of the qkv kernel,
no decoder bias, no pooler and no next-sentence head, padded vocabulary.
The model code's own departures (tanh GELU, eps 1e-5) are NOT copied: the
reference follows the source, and the comparison's tolerance has to hold
across them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import gpt as _gpt            # the shared plain pieces


def forward(params, ids, *, n_head: int, eps: float = 1e-12):
    """MLM logits [b, s, padded_vocab] in float32; no padding, segment 0."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        s = ids.shape[1]
        x = p["wte"]["embedding"][ids] + p["wpe"][:s][None] + p["wtte"][0]
        x = _gpt._ln(x, p["ln_emb"], eps)
        n_layer = sum(1 for k in p if k.startswith("layer_"))
        for i in range(n_layer):
            lyr = p[f"layer_{i}"]
            x = _gpt._ln(x + _gpt._attention(x, lyr["attn"], n_head,
                                             causal=False), lyr["ln1"], eps)
            y = jax.nn.gelu(_gpt._linear(x, lyr["fc1"]), approximate=False)
            x = _gpt._ln(x + _gpt._linear(y, lyr["fc2"]), lyr["ln2"], eps)
        x = jax.nn.gelu(_gpt._linear(x, p["mlm_dense"]), approximate=False)
        x = _gpt._ln(x, p["mlm_ln"], eps)
        return x @ p["wte"]["embedding"].T


def loss(params, ids, labels, loss_mask, *, n_head: int,
         eps: float = 1e-12):
    """Mean MLM cross entropy over the masked positions."""
    logits = forward(params, ids, n_head=n_head, eps=eps)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
    w = loss_mask.astype(nll.dtype)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
