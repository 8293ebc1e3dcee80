"""Mellum 2 decoders (``model_type: mellum``), plainly: ``jax.numpy`` in
float32 at ``highest`` matmul precision, no kernels. Written from the
published ``config.json`` keys (named in ``benchmarks/configs/
mellum2-12b-ep4-l4.json``):

- block: ``a = x + Wo Attn(n1(x)); y = a + Experts(n2(a))``, RMSNorm, no
  bias anywhere; final RMSNorm and an untied head;
- attention: ``num_attention_heads`` query heads over ``num_key_value_heads``
  key/value heads of ``head_dim`` (a group of query heads shares one), scale
  ``head_dim^-0.5``, causal; ``layer_types[i]`` says whether layer ``i`` also
  hides the keys ``j <= i - sliding_window`` (``sliding_attention``) or not
  (``full_attention``); rotary embedding over the whole head, pairs ``(i, i +
  head_dim / 2)``, frequencies and the factor on cos and sin from
  ``rope_parameters[layer type]`` (``default``: ``theta^(-2i/d)``, factor 1;
  ``yarn``: the blended frequencies and ``attention_factor``);
- experts: ``p = softmax(n2(a) Wr)`` over all ``num_experts``, the
  ``num_experts_per_tok`` largest chosen, weights ``p_chosen / sum(p_chosen)``
  (``norm_topk_prob``), each expert a gated-SiLU MLP; no shared expert;
- loss: mean next-token cross entropy of the logits.

It takes the parameter tree of ``apex_tpu.models.mellum`` (so the same
weights go through both) and is written to fit beside the program under
test at the published widths and 8,192 tokens: attention is a masked softmax
over ALL keys computed a block of query rows at a time, the experts are a
dense sum over the held experts, one at a time, and under ``jax.grad`` each
layer, each query block and each expert is recomputed rather than kept.
Departures, which the tree and the cut force:

- a chip's share: the tree may hold only the experts ``first_expert ..
  first_expert + n_local`` of each layer. The router keeps its published
  width and rule; what an absent expert would have added is left out, here
  as in the program (model-configs guide, section 4);
- the vocabulary may be a slice: embedding and head have as many rows as the
  tree holds, and the loss is over those;
- ``forced`` [layers, b, s, k]: the sum runs over THOSE experts (the ones the
  program under test chose), weighted by this reference's own probabilities
  of them, renormalised over them: a comparison of arithmetic; the choice is
  compared apart (:func:`tie_distance`);
- no auxiliary load-balancing loss (the config gives no coefficient), no
  multi-token-prediction head (the config has no key for one).
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from . import deepseek as _deepseek

SLIDING = "sliding_attention"
#: query rows of one block of the masked softmax
QUERY_BLOCK = 512


# -- rotary embedding ----------------------------------------------------------

def inv_freq_and_factor(rope: dict, dim: int):
    """``(inverse frequencies [dim / 2] float32, factor on cos and sin)`` of
    one ``rope_parameters`` group, as the transformers initialisers of
    ``default`` and ``yarn`` compute them (the yarn blend is
    ``reference/deepseek.py``'s, over the whole head)."""
    kind = rope.get("rope_type", "default")
    assert kind in ("default", "yarn"), rope
    inv_freq = _deepseek.yarn_inv_freq({
        "qk_rope_head_dim": dim, "rope_theta": rope["rope_theta"],
        "rope_scaling": rope if kind == "yarn" else None})
    if kind == "default":
        return inv_freq, 1.0
    factor = rope.get("attention_factor")
    if factor is None:
        factor = _deepseek.yarn_mscale(float(rope["factor"]), 1.0)
    return inv_freq, float(factor)


def _rope(x, inv_freq, factor):
    """Rotate the pairs ``(i, i + d/2)`` of ``x`` [b, s, n, d]."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# -- pieces ----------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _attention(x, p, sizes, kind):
    b, s, _ = x.shape
    n, m = int(sizes["num_attention_heads"]), int(sizes["num_key_value_heads"])
    d = int(sizes["head_dim"])
    inv_freq, factor = inv_freq_and_factor(sizes["rope_parameters"][kind], d)
    w = {k: v.astype(jnp.float32) for k, v in p.items()}
    q = _rope((x @ w["q"]).reshape(b, s, n, d), inv_freq, factor)
    k = _rope((x @ w["k"]).reshape(b, s, m, d), inv_freq, factor)
    v = (x @ w["v"]).reshape(b, s, m, d)
    window = int(sizes["sliding_window"]) if kind == SLIDING else s
    blk = min(QUERY_BLOCK, s)
    assert s % blk == 0, (s, blk)
    q = q.reshape(b, s // blk, blk, m, n // m, d)
    keys = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(qb, first):
        rows = first + jnp.arange(blk)[:, None]
        seen = (keys <= rows) & (keys > rows - window)          # [blk, s]
        sc = jnp.einsum("bqmgd,bkmd->bmgqk", qb, k) * d ** -0.5
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bmgqk,bkmd->bqmgd", pr, v)

    ctx = jax.lax.map(lambda a: block(*a), (jnp.moveaxis(q, 1, 0),
                                            jnp.arange(s // blk) * blk))
    return jnp.moveaxis(ctx, 0, 1).reshape(b, s, n * d) @ w["o"]


def probabilities(x, router):
    """``(p, logits)`` [t, E] of float32 rows ``x``."""
    z = x @ router.astype(jnp.float32)
    return jax.nn.softmax(z, axis=-1), z


def choose(p, sizes):
    """The experts [t, k] the published rule picks: the ``k`` largest."""
    return jax.lax.top_k(p, int(sizes["num_experts_per_tok"]))[1]


def weights(p, idx, sizes):
    w = jnp.take_along_axis(p, idx, axis=1)
    if sizes.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    return w


def route(x, router, sizes):
    """The published rule on float32 rows ``x`` [t, h]: ``(idx [t, k],
    w [t, k])`` over ALL experts."""
    p, _ = probabilities(x, router)
    idx = choose(p, sizes)
    return idx, weights(p, idx, sizes)


def tie_distance(logits, theirs):
    """How far from a tie a choice ``theirs`` [t, k] is under the router
    logits ``logits`` [t, E]: the least amount by which every logit has to
    move (theirs up, the others down) for the rule to pick ``theirs``: half
    of what the best expert left out leads the worst one taken by, 0 where
    the rule picks ``theirs`` as it is. The softmax is monotone, so that is
    the nearest score vector, in the largest single change, under which
    ``theirs`` is right: a program whose hidden state differs from the
    reference's by rounding may choose otherwise at a near-tie and nowhere
    else."""
    taken = jnp.zeros(logits.shape, bool).at[
        jnp.arange(logits.shape[0])[:, None], theirs].set(True)
    worst_in = jnp.min(jnp.where(taken, logits, jnp.inf), axis=-1)
    best_out = jnp.max(jnp.where(taken, -jnp.inf, logits), axis=-1)
    return jnp.maximum(best_out - worst_in, 0.0) / 2.0


def _gated(x, gate_up, down):
    im = gate_up.shape[1] // 2
    gu = x @ gate_up
    return (jax.nn.silu(gu[:, :im]) * gu[:, im:]) @ down


def _moe(x, p, sizes, first_expert, forced=None):
    """This share's part of the expert layer: the held experts' terms of
    the published sum. Returns ``(y, own choice [b, s, k], router logits [b,
    s, E])``."""
    b, s, h = x.shape
    flat = x.reshape(b * s, h)
    pr, z = probabilities(flat, p["router"])
    idx = choose(pr, sizes)
    used = idx if forced is None else forced.reshape(b * s, -1)
    dense_w = jnp.zeros_like(pr).at[
        jnp.arange(b * s)[:, None], used].set(weights(pr, used, sizes))
    ex = p["experts"]
    n_local = ex["gate_up"].shape[0]
    held = jax.lax.dynamic_slice_in_dim(dense_w, first_expert, n_local, 1)

    @jax.checkpoint
    def one(y, e):
        gate_up, down, w = e
        return y + w[:, None] * _gated(flat, gate_up.astype(jnp.float32),
                                       down.astype(jnp.float32)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(flat),
                        (ex["gate_up"], ex["down"], held.T))
    E = pr.shape[1]
    return y.reshape(b, s, h), idx.reshape(b, s, -1), z.reshape(b, s, E)


def _layer(x, p, sizes, kind, first_expert, forced=None):
    eps = float(sizes["rms_norm_eps"])
    x = x + _attention(_rms(x, p["attn_norm"], eps), p["attn"], sizes, kind)
    out, idx, z = _moe(_rms(x, p["ffn_norm"], eps), p["moe"], sizes,
                       first_expert, forced)
    return x + out, (idx, z)


#: the keys of a configuration that the mathematics reads
KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "rope_parameters", "sliding_window",
        "num_experts_per_tok", "norm_topk_prob")

_LAYERS = {}


def _jitted_layer(sizes: dict, kind: str, first_expert: int):
    """``_layer`` jitted for one configuration, kind of layer and share."""
    sizes = {k: sizes[k] for k in KEYS if k in sizes}
    key = (json.dumps(sizes, sort_keys=True), kind, first_expert)
    if key not in _LAYERS:
        def fn(x, p, forced=None):
            with jax.default_matmul_precision("highest"):
                return _layer(x, p, sizes, kind, first_expert, forced)
        _LAYERS[key] = jax.jit(fn)
    return _LAYERS[key]


def _n_layers(params) -> int:
    return sum(1 for k in params if k.startswith("layer_"))


def forward(params, ids, sizes: dict, *, first_expert: int = 0,
            routing: bool = False, forced=None):
    """Logits in float32 for token ids [b, s]: ``[b, s, V]``. With
    ``routing`` also ``(the experts each token chose [layers, b, s, k], the
    router's logits [layers, b, s, E])``. ``forced`` [layers, b, s, k] makes
    every expert layer sum over those experts instead of its own choice.
    One jitted call a layer, so one layer's float32 weights live at a time;
    ``sizes["layer_types"]`` names the layers the tree holds."""
    x = _embed(params["embed"], ids)
    chosen, logits = [], []
    for i in range(_n_layers(params)):
        layer = _jitted_layer(sizes, sizes["layer_types"][i],
                              int(first_expert))
        args = () if forced is None else (forced[i],)
        x, (idx, z) = layer(x, params[f"layer_{i}"], *args)
        chosen.append(idx)
        logits.append(z)
    out = _head(x, params["norm_f"], params["head"],
                float(sizes["rms_norm_eps"]))
    if routing:
        return out, jnp.stack(chosen), jnp.stack(logits)
    return out


@jax.jit
def _embed(table, ids):
    return table.astype(jnp.float32)[ids]


@jax.jit
def _head(x, w, head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, w, eps) @ head.astype(jnp.float32).T


def loss(params, ids, labels, sizes: dict, *, first_expert: int = 0,
         forced=None, reduce=jnp.mean):
    """``reduce`` (mean: the published loss; sum: the check's) of the
    next-token cross entropy over the rows of the vocabulary held. One
    program, every layer recomputed under ``jax.grad``."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(jnp.float32)[ids]
        for i in range(_n_layers(params)):
            layer = jax.checkpoint(functools.partial(
                _layer, sizes=sizes, kind=sizes["layer_types"][i],
                first_expert=int(first_expert)))
            x, _ = layer(x, params[f"layer_{i}"],
                         forced=None if forced is None else forced[i])
        logits = _rms(x, params["norm_f"], float(sizes["rms_norm_eps"])) \
            @ params["head"].astype(jnp.float32).T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return reduce(-jnp.take_along_axis(logp, labels[..., None], -1))


def grads(params, ids, labels, sizes: dict, leaves, **kw):
    """``jax.grad`` of :func:`loss` to the named leaves alone: ``leaves`` a
    list of key paths (``("layer_0", "attn", "q")``); the rest of the tree
    is a constant. Returns ``{path: gradient}`` in float32."""
    def get(tree, path):
        for k in path:
            tree = tree[k]
        return tree

    def put(tree, path, value):
        if not path:
            return value
        return {**tree, path[0]: put(tree[path[0]], path[1:], value)}

    def f(picked):
        tree = params
        for path, value in zip(leaves, picked):
            tree = put(tree, tuple(path), value)
        return loss(tree, ids, labels, sizes, **kw)

    picked = [get(params, path).astype(jnp.float32) for path in leaves]
    return dict(zip(map(tuple, leaves), jax.grad(f)(picked)))
