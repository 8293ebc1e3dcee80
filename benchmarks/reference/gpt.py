"""GPT-2, plainly: ``jax.numpy`` in float32, no kernels, no cache, no
batching tricks. Follows the published architecture (Radford et al. 2019;
``config.json`` keys named in ``benchmarks/configs/gpt2-*.json``): learned
positions, pre-LayerNorm blocks, tanh GELU ("gelu_new"), tied LM head.

Takes the parameter tree of ``apex_tpu.models.gpt.GPT`` at tp=1 so that the
same weights go through both. Departures from the checkpoint's layout,
which the tree forces: the qkv kernel packs per-head ``[q|k|v]`` groups, and
the vocabulary is padded (extra logit columns are returned as they are).

On a TPU a float32 matmul runs in bf16 passes unless the precision is
raised; the caller wraps calls in
``jax.default_matmul_precision("highest")`` (``forward`` does so itself).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["weight"] + p["bias"]


def _linear(x, p):
    return x @ p["kernel"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _attention(x, p, n_head, causal):
    b, s, h = x.shape
    d = h // n_head
    qkv = _linear(x, p["qkv"]).reshape(b, s, n_head, 3 * d)
    q, k, v = jnp.split(qkv, 3, axis=-1)                  # [b, s, n, d]
    scores = jnp.einsum("bsnd,btnd->bnst", q, k) / jnp.sqrt(float(d))
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bnst,btnd->bsnd", probs, v).reshape(b, s, h)
    return _linear(ctx, p["proj"])


def forward(params, ids, *, n_head: int, eps: float = 1e-5):
    """Logits [b, s, padded_vocab] in float32 for token ids [b, s]."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        s = ids.shape[1]
        x = p["wte"]["embedding"][ids] + p["wpe"][:s][None]
        n_layer = sum(1 for k in p if k.startswith("block_"))
        for i in range(n_layer):
            blk = p[f"block_{i}"]
            x = x + _attention(_ln(x, blk["ln1"], eps), blk["attn"],
                               n_head, causal=True)
            y = _gelu_new(_linear(_ln(x, blk["ln2"], eps),
                                  blk["mlp"]["fc1"]))
            x = x + _linear(y, blk["mlp"]["fc2"])
        x = _ln(x, p["ln_f"], eps)
        return x @ p["wte"]["embedding"].T


def loss(params, ids, labels, *, n_head: int, eps: float = 1e-5):
    """Mean next-token cross entropy, as ``GPT.loss`` defines it (labels
    are given, already shifted by the caller)."""
    logits = forward(params, ids, n_head=n_head, eps=eps)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))
