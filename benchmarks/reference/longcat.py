"""LongCat-Flash decoders (``meituan-longcat/LongCat-Flash-Chat``), plainly:
``jax.numpy`` in float32 at ``highest`` matmul precision, no kernels, no
cache, no batching tricks. Written from the published ``config.json`` keys
(named in ``benchmarks/configs/longcat-flash-560b-ep32.json``) and the
shortcut-connected layer those keys describe (``N`` = RMSNorm at
``rms_norm_eps``; no bias anywhere; final RMSNorm and an untied head)::

    for j in (0, 1):                    # one published layer = two sub-layers
        x = x + Attn_j(N_in[j](x))      # attention()
        m = N_post[j](x)
        if j == 0:  s = MoE(m)          # experts(): computed here ...
        x = x + FFN_j(m)                # feed_forward(): dense gated SiLU
    x = x + s                           # ... added at the end of the layer

- latent attention: low-rank q (``q_a`` -> RMSNorm -> x ``(hidden /
  q_lora_rank)^0.5`` -> ``q_b``), one joint ``kv_a`` projection to a
  ``kv_lora_rank`` latent (RMSNorm, x ``(hidden / kv_lora_rank)^0.5``) and
  ONE rotary key head shared by all query heads (not scaled), ``kv_b``
  expanding the latent to per-head ``k_nope | v`` (``v_head_dim`` lanes,
  fewer than the key's); plain rotary at ``rope_theta``; softmax scale
  ``(nope + rope)^-0.5``;
- experts: ``p = softmax(m W_r)`` over ``n_routed_experts +
  zero_expert_num`` slots; a correction bias moves the CHOICE only; the
  top ``moe_topk`` of ``p + b``, no groups; weights ``p`` of the chosen
  times ``routed_scaling_factor``, not renormalised; a chosen slot past
  ``n_routed_experts`` is an identity expert: its weight times ``m``.

It takes the parameter tree of ``apex_tpu.models.longcat`` (so the same
weights go through both) and works on it piece by piece so that the
published widths fit beside the program under test: every piece is one
jitted call that converts its own weights to float32, a wide feed-forward
runs a block of columns at a time and the expert layer an expert at a
time. Departures, which the tree and the cut force:

- a chip's share: the tree may hold only the experts ``first_expert ..
  first_expert + n_local`` of each layer. The router keeps its published
  width and rule; what an absent expert would have added is left out, here
  as in the program (model-configs guide, section 4). The identity part is
  computed by every chip for the tokens it holds, so it is here;
- the vocabulary may be a slice: embedding and head have as many rows as
  the tree holds;
- rotary pairs are the interleaved ``(2i, 2i+1)`` lanes, as in the DeepSeek
  formulation this attention follows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: a feed-forward wider than this is summed over column blocks of its
#: intermediate size, so that its float32 weights are never whole in memory
BLOCK_COLUMNS = 4096


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _gated(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _rope(x, pos, theta):
    """Rotate the interleaved pairs of ``x`` [..., s, n, rope_dim]."""
    dim = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]   # [s, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _piece(fn):
    """One jitted call at ``highest`` matmul precision; ``sizes`` (a tuple
    of sorted items, hashable) and ``first_expert`` are static."""
    @functools.partial(jax.jit, static_argnames=("sizes", "first_expert"))
    def run(*args, sizes, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, dict(sizes), **kw)
    return run


# -- the pieces of a layer -------------------------------------------------------

def _attention(x, sub, sizes):
    b, s, h = x.shape
    n = int(sizes["num_attention_heads"])
    nope, rope = int(sizes["qk_nope_head_dim"]), int(sizes["qk_rope_head_dim"])
    vd, r = int(sizes["v_head_dim"]), int(sizes["kv_lora_rank"])
    eps, theta = float(sizes["rms_norm_eps"]), float(sizes["rope_theta"])
    p = _f32(sub["attn"])
    q_scale = (h / int(sizes["q_lora_rank"])) ** 0.5 \
        if sizes.get("mla_scale_q_lora") else 1.0
    kv_scale = (h / r) ** 0.5 if sizes.get("mla_scale_kv_lora") else 1.0
    a = _rms(x, sub["attn_norm"], eps)
    pos = jnp.arange(s)
    cq = _rms(a @ p["q_a"], p["q_norm"], eps) * q_scale
    q = (cq @ p["q_b"]).reshape(b, s, n, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], pos, theta)
    ckv = a @ p["kv_a"]
    c = _rms(ckv[..., :r], p["kv_norm"], eps) * kv_scale
    k_pe = _rope(ckv[..., None, r:], pos, theta)[:, :, 0]       # one head
    kv = (c @ p["kv_b"]).reshape(b, s, n, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("bsnd,btnd->bnst", q_nope, k_nope)
              + jnp.einsum("bsnd,btd->bnst", q_pe, k_pe)
              ) * (nope + rope) ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    ctx = jnp.einsum("bnst,btnd->bsnd", jax.nn.softmax(scores, -1), v)
    return x + ctx.reshape(b, s, n * vd) @ p["o"]


def _feed_forward(x, sub, sizes):
    m = _rms(x, sub["ffn_norm"], float(sizes["rms_norm_eps"]))
    p = sub["mlp"]
    width = p["gate"].shape[1]
    if width <= BLOCK_COLUMNS or width % BLOCK_COLUMNS:
        q = _f32(p)
        return x + _gated(m, q["gate"], q["up"], q["down"])

    def one(j, y):
        cols = [jax.lax.dynamic_slice_in_dim(p[k], j * BLOCK_COLUMNS,
                                             BLOCK_COLUMNS, axis=1)
                for k in ("gate", "up")]
        rows = jax.lax.dynamic_slice_in_dim(p["down"], j * BLOCK_COLUMNS,
                                            BLOCK_COLUMNS, axis=0)
        return y + _gated(m, *(a.astype(jnp.float32)
                               for a in (*cols, rows)))

    return jax.lax.fori_loop(0, width // BLOCK_COLUMNS, one, x)


def scores(m, router, bias):
    """``(p, cor)`` [t, E + Z]: the softmax scores of float32 rows ``m``
    and the same with the correction bias, which only the choice reads."""
    p = jax.nn.softmax(m @ router.astype(jnp.float32), axis=-1)
    return p, p + bias.astype(jnp.float32)


def choose(cor, sizes):
    """The slots [t, k] the published rule picks from corrected scores."""
    return jax.lax.top_k(cor, int(sizes["moe_topk"]))[1]


def weights(p, idx, sizes):
    """The chosen slots' weights: their UNcorrected scores, scaled."""
    return jnp.take_along_axis(p, idx, axis=1) \
        * float(sizes["routed_scaling_factor"])


def route(m, router, bias, sizes):
    """The published rule on float32 rows ``m`` [t, h]: ``(idx [t, k],
    w [t, k])`` over ALL slots."""
    p, cor = scores(m, router, bias)
    idx = choose(cor, sizes)
    return idx, weights(p, idx, sizes)


def tie_distance(cor, theirs):
    """How far from a tie a choice ``theirs`` [t, k] is under the corrected
    scores ``cor`` [t, E + Z]: the least RELATIVE move of every score
    (theirs up by ``1 + d``, the others down by ``1 - d``) under which the
    top ``k`` are ``theirs``; 0.0 where they are as it is, ``inf`` for a
    choice whose weakest score is not positive. Relative, because a softmax
    over hundreds of slots scores ~0.001-0.01 where it decides, and a
    relative move of a score is the same move of the router's logit: a
    program whose hidden state differs from the reference's by rounding may
    choose otherwise at a near-tie and nowhere else."""
    cor = np.asarray(cor, np.float64)
    mask = np.zeros(cor.shape, bool)
    np.put_along_axis(mask, np.asarray(theirs), True, -1)
    lo = np.where(mask, cor, np.inf).min(-1)        # weakest chosen
    hi = np.where(mask, -np.inf, cor).max(-1)       # strongest passed over
    with np.errstate(divide="ignore", invalid="ignore"):
        need = np.where(lo > 0, (hi - lo) / (hi + lo), np.inf)
    return np.maximum(need, 0.0)


def _experts(x, sub, p, sizes, first_expert=0, forced=None):
    """This share's part of the expert layer on ``m = N_post(x)``: the held
    routed experts' terms of the published sum, and the identity experts'
    (every chip computes those alike for its tokens: summing the shares
    counts them once). With ``forced`` [b, s, k] the sum runs over THOSE
    slots, weighted by this layer's own scores of them (what the program
    under test chose: a comparison of arithmetic, the choice itself is
    compared apart). Returns ``(s, own choice [b, s, k], corrected scores
    [b, s, E + Z])``."""
    b, s, h = x.shape
    m = _rms(x, sub["ffn_norm"], float(sizes["rms_norm_eps"])).reshape(
        b * s, h)
    sc, cor = scores(m, p["router"], p["bias"])
    idx = choose(cor, sizes)
    used = idx if forced is None else forced.reshape(b * s, -1)
    E = int(sizes["n_routed_experts"])
    slots = E + int(sizes["zero_expert_num"])
    dense_w = jnp.zeros((b * s, slots), jnp.float32).at[
        jnp.arange(b * s)[:, None], used].set(weights(sc, used, sizes))
    ex = p["experts"]
    n_local, _, two_i = ex["gate_up"].shape
    im = two_i // 2

    def one(e, y):
        gu = ex["gate_up"][e].astype(jnp.float32)
        dn = ex["down"][e].astype(jnp.float32)
        out = _gated(m, gu[:, :im], gu[:, im:], dn)
        return y + dense_w[:, first_expert + e][:, None] * out

    y = jax.lax.fori_loop(0, n_local, one, jnp.zeros_like(m))
    y = y + dense_w[:, E:].sum(-1, keepdims=True) * m       # identity
    return (y.reshape(b, s, h), idx.reshape(b, s, -1),
            cor.reshape(b, s, slots))


attention = _piece(_attention)          # (x, sub, sizes=) -> x + Attn
feed_forward = _piece(_feed_forward)    # (x, sub, sizes=) -> x + FFN
experts = _piece(_experts)              # (x, sub_0, moe, sizes=, ...) -> s, ..


#: the keys of a configuration that the mathematics reads
KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "kv_lora_rank", "q_lora_rank", "rms_norm_eps",
        "rope_theta", "mla_scale_q_lora", "mla_scale_kv_lora",
        "n_routed_experts", "zero_expert_num", "moe_topk",
        "routed_scaling_factor")


def static(sizes: dict):
    """``sizes`` cut to :data:`KEYS`, hashable: what the pieces take."""
    return tuple(sorted((k, sizes[k]) for k in KEYS))


def layer(x, p, sizes, first_expert=0, forced=None):
    """One published layer on ``x`` [b, s, h] (``sizes`` from
    :func:`static`): ``(x, (own choice, corrected scores))``."""
    x = attention(x, p["sub_0"], sizes=sizes)
    s, idx, cor = experts(x, p["sub_0"], p["moe"], sizes=sizes,
                          first_expert=first_expert, forced=forced)
    x = feed_forward(x, p["sub_0"], sizes=sizes)
    x = attention(x, p["sub_1"], sizes=sizes)
    x = feed_forward(x, p["sub_1"], sizes=sizes)
    return x + s, (idx, cor)


@jax.jit
def embed(table, ids):
    return table.astype(jnp.float32)[ids]


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, w, table, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, w, eps) @ table.astype(jnp.float32)


def forward(params, ids, sizes: dict, *, rows=None, first_expert: int = 0,
            routing: bool = False, forced=None):
    """Logits in float32 for token ids [b, s]: ``[b, s, V]``, or with
    ``rows`` [b, r] only those positions ``[b, r, V]``. With ``routing``
    also ``(the slots each token chose [layers, b, s, k], its corrected
    scores [layers, b, s, E + Z])``. ``forced`` [layers, b, s, k] makes
    every expert layer sum over those slots instead of its own choice
    (``_experts``)."""
    st = static(sizes)
    x = embed(params["embed"], ids)
    chosen, cors = [], []
    n_layer = sum(1 for k in params if k.startswith("layer_"))
    for i in range(n_layer):
        x, (idx, cor) = layer(x, params[f"layer_{i}"], st,
                              first_expert=int(first_expert),
                              forced=None if forced is None else forced[i])
        chosen.append(idx)
        cors.append(cor)
    if rows is not None:
        x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    logits = head(x, params["norm_f"], params["head"],
                  eps=float(sizes["rms_norm_eps"]))
    if routing:
        return logits, jnp.stack(chosen), jnp.stack(cors)
    return logits
