"""DeepSeek-V3-shaped decoders (``model_type: deepseek_v3``), plainly:
``jax.numpy`` in float32 at ``highest`` matmul precision, no kernels, no
cache, no batching tricks. Written from the published ``config.json`` keys
(named in ``benchmarks/configs/gigachat3.1-702b-ep16.json``) and the
DeepSeek-V3 formulation those keys name:

- block: ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``, no bias anywhere;
  ``FFN`` is a gated-SiLU MLP in the leading dense layers and the expert
  layer after them; final RMSNorm and an untied head;
- latent attention: low-rank q (``q_a`` -> RMSNorm -> ``q_b``), one joint
  ``kv_a`` projection to a ``kv_lora_rank`` latent and ONE rotary key head
  shared by all query heads, ``kv_b`` expanding the normalised latent to
  per-head ``k_nope | v``; scores over ``[nope | rope]`` keys, YaRN-scaled
  rotary frequencies and softmax scale;
- experts: sigmoid scores, a per-expert correction bias that moves the
  CHOICE only, group-limited top-k (a group's score is the sum of its top
  two corrected scores), weights from the uncorrected scores normalised to
  sum to ``routed_scaling_factor``, plus shared experts on every token.

It takes the parameter tree of ``apex_tpu.models.deepseek`` (so the same
weights go through both) and works on it piece by piece so that the
published widths fit beside the program under test: one layer's weights are
converted to float32 at a time, and an expert layer runs an expert at a
time. Departures, which the tree and the cut force:

- a chip's share: the tree may hold only the experts ``first_expert ..
  first_expert + n_local`` of each layer. The router keeps its published
  width and rule; what an absent expert would have added is left out, here
  as in the program (model-configs guide, section 4);
- the vocabulary may be a slice: embedding and head have as many rows as
  the tree holds;
- rotary pairs are the interleaved ``(2i, 2i+1)`` lanes, left in place (the
  published code permutes them to halves first; q and k are permuted
  alike, so every score is the same);
- the multi-token-prediction module is not part of the inference forward.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp


# -- YaRN ---------------------------------------------------------------------

def yarn_inv_freq(sizes: dict):
    """Rotary inverse frequencies [rope_dim / 2], as the transformers
    ``yarn`` initialiser computes them (plain ``theta^(-2i/d)`` without
    ``rope_scaling``)."""
    dim = int(sizes["qk_rope_head_dim"])
    base = float(sizes["rope_theta"])
    extrap = [base ** (-2.0 * i / dim) for i in range(dim // 2)]
    rs = sizes.get("rope_scaling")
    if not rs:
        return jnp.asarray(extrap, jnp.float32)
    factor = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(extrap):
        ramp = min(1.0, max(0.0, (i - low) / (high - low)))
        out.append((f / factor) * ramp + f * (1.0 - ramp))
    return jnp.asarray(out, jnp.float32)


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(sizes: dict) -> float:
    d = int(sizes["qk_nope_head_dim"]) + int(sizes["qk_rope_head_dim"])
    rs = sizes.get("rope_scaling") or {}
    if not rs.get("mscale_all_dim"):
        return d ** -0.5
    m = yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return d ** -0.5 * m * m


def rope_factor(sizes: dict) -> float:
    """What cos and sin are multiplied by (1 at the published keys)."""
    rs = sizes.get("rope_scaling")
    if not rs:
        return 1.0
    f = float(rs["factor"])
    if rs.get("mscale") and rs.get("mscale_all_dim"):
        return yarn_mscale(f, float(rs["mscale"])) \
            / yarn_mscale(f, float(rs["mscale_all_dim"]))
    return yarn_mscale(f, 1.0)


def _rope(x, pos, inv_freq, factor):
    """Rotate the interleaved pairs of ``x`` [..., s, n, rope_dim]."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]   # [s, d/2]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


# -- pieces ---------------------------------------------------------------------

def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _gated(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


#: a feed-forward wider than this is summed over column blocks of its
#: intermediate size, so that its float32 weights are never whole in memory
BLOCK_COLUMNS = 4608


def _gated_blocked(x, p):
    """``_gated`` over the tree ``p`` (any dtype), a block of intermediate
    columns at a time: the sum over blocks is the same product."""
    width = p["gate"].shape[1]
    if width <= BLOCK_COLUMNS or width % BLOCK_COLUMNS:
        q = _f32(p)
        return _gated(x, q["gate"], q["up"], q["down"])

    def one(j, y):
        cols = [jax.lax.dynamic_slice_in_dim(p[k], j * BLOCK_COLUMNS,
                                             BLOCK_COLUMNS, axis=1)
                for k in ("gate", "up")]
        rows = jax.lax.dynamic_slice_in_dim(p["down"], j * BLOCK_COLUMNS,
                                            BLOCK_COLUMNS, axis=0)
        return y + _gated(x, *(a.astype(jnp.float32)
                               for a in (*cols, rows)))

    return jax.lax.fori_loop(0, width // BLOCK_COLUMNS, one,
                             jnp.zeros_like(x))


def _attention(x, p, sizes):
    b, s, _ = x.shape
    n = int(sizes["num_attention_heads"])
    nope, rope = int(sizes["qk_nope_head_dim"]), int(sizes["qk_rope_head_dim"])
    vd, r = int(sizes["v_head_dim"]), int(sizes["kv_lora_rank"])
    eps = float(sizes["rms_norm_eps"])
    pos = jnp.arange(s)
    inv_freq, factor = yarn_inv_freq(sizes), rope_factor(sizes)
    q = (_rms(x @ p["q_a"], p["q_norm"], eps) @ p["q_b"]).reshape(
        b, s, n, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    ckv = x @ p["kv_a"]
    c, k_pe = _rms(ckv[..., :r], p["kv_norm"], eps), ckv[..., r:]
    kv = (c @ p["kv_b"]).reshape(b, s, n, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = _rope(q_pe, pos, inv_freq, factor)
    k_pe = _rope(k_pe[:, :, None, :], pos, inv_freq, factor)   # one head
    scores = (jnp.einsum("bsnd,btnd->bnst", q_nope, k_nope)
              + jnp.einsum("bsnd,btd->bnst", q_pe, k_pe[:, :, 0])
              ) * softmax_scale(sizes)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    ctx = jnp.einsum("bnst,btnd->bsnd", jax.nn.softmax(scores, -1), v)
    return ctx.reshape(b, s, n * vd) @ p["o"]


def scores(x, router, bias):
    """``(sc, cor)`` [t, E]: the sigmoid scores of float32 rows ``x`` and
    the same with the correction bias, which only the choice reads."""
    sc = jax.nn.sigmoid(x @ router)
    return sc, sc + bias


def choose(cor, sizes):
    """The experts [t, k] the published rule picks from corrected scores
    ``cor`` [t, E]: groups by the sum of their two best, the best
    ``topk_group`` groups stay, the others' scores become 0, top ``k``."""
    E, k = int(sizes["n_routed_experts"]), int(sizes["num_experts_per_tok"])
    G, kg = int(sizes["n_group"]), int(sizes["topk_group"])
    grp = jnp.sum(jax.lax.top_k(cor.reshape(-1, G, E // G), 2)[0], -1)
    keep = jnp.zeros_like(grp).at[
        jnp.arange(grp.shape[0])[:, None],
        jax.lax.top_k(grp, kg)[1]].set(1.0)                   # [t, G]
    masked = jnp.where(jnp.repeat(keep, E // G, axis=1) > 0, cor, 0.0)
    return jax.lax.top_k(masked, k)[1]


def weights(sc, idx, sizes):
    """The chosen experts' weights from the UNcorrected scores."""
    w = jnp.take_along_axis(sc, idx, axis=1)
    if sizes.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * float(sizes["routed_scaling_factor"])


def route(x, router, bias, sizes):
    """The published rule on float32 rows ``x`` [t, h]: ``(idx [t, k],
    w [t, k])`` over ALL routed experts."""
    sc, cor = scores(x, router, bias)
    idx = choose(cor, sizes)
    return idx, weights(sc, idx, sizes)


_CHOOSERS = {}


def _jitted_choose(sizes: dict):
    """``choose`` as one program a configuration and shape: op by op it is
    23 small compilations for every new number of rows."""
    mine = {k: int(sizes[k]) for k in (
        "n_routed_experts", "num_experts_per_tok", "n_group", "topk_group")}
    key = tuple(mine.values())
    if key not in _CHOOSERS:
        _CHOOSERS[key] = jax.jit(lambda cor: choose(cor, mine))
    return _CHOOSERS[key]


def tie_distance(cor, theirs, sizes, ladder=(1e-3, 2e-3, 5e-3, 1e-2, 2e-2,
                                             3e-2, 5e-2, 7.5e-2, 1e-1, 2e-1)):
    """How far from a tie a choice ``theirs`` [t, k] is under the corrected
    scores ``cor`` [t, E]: the smallest step of ``ladder`` by which every
    score may move (theirs up, the others down) for the rule to pick
    ``theirs``; 0.0 where the rule picks it as it is, ``inf`` past the
    ladder. The rule is monotone, so that is the nearest score vector, in
    the largest single change, under which ``theirs`` is right: a program
    whose hidden state differs from the reference's by rounding may choose
    otherwise at a near-tie and nowhere else."""
    import numpy as np
    pick = _jitted_choose(sizes)
    mine = np.sort(np.asarray(pick(cor)), -1)
    theirs = np.sort(np.asarray(theirs), -1)
    need = np.where((mine == theirs).all(-1), 0.0, np.inf)
    favour = np.zeros(cor.shape, np.float32)
    np.put_along_axis(favour, theirs, 1.0, -1)
    for step in ladder:
        moved = np.sort(np.asarray(pick(
            cor + step * (2.0 * favour - 1.0))), -1)
        hit = (moved == theirs).all(-1) & np.isinf(need)
        need = np.where(hit, step, need)
    return need


def _moe(x, p, sizes, first_expert, forced=None):
    """This share's part of the expert layer: the held routed experts'
    terms of the published sum, and the shared expert (every chip computes
    that one alike: summing the shares counts it once). With ``forced`` [b, s, k] the sum runs over THOSE experts,
    weighted by this layer's own scores of them (what the program under
    test chose: a comparison of arithmetic, the choice itself is compared
    apart). Returns ``(y, own choice [b, s, k], corrected scores [b, s,
    E])``."""
    b, s, h = x.shape
    flat = x.reshape(b * s, h)
    sc, cor = scores(flat, p["router"].astype(jnp.float32),
                     p["bias"].astype(jnp.float32))
    idx = choose(cor, sizes)
    used = idx if forced is None else forced.reshape(b * s, -1)
    E = int(sizes["n_routed_experts"])
    dense_w = jnp.zeros((b * s, E), jnp.float32).at[
        jnp.arange(b * s)[:, None], used].set(weights(sc, used, sizes))
    ex = p["experts"]
    n_local, _, two_i = ex["gate_up"].shape
    im = two_i // 2

    def one(e, y):
        gu = ex["gate_up"][e].astype(jnp.float32)
        dn = ex["down"][e].astype(jnp.float32)
        out = _gated(flat, gu[:, :im], gu[:, im:], dn)
        return y + dense_w[:, first_expert + e][:, None] * out

    y = jax.lax.fori_loop(0, n_local, one, jnp.zeros_like(flat))
    sh = _f32(p["shared"])
    y = y + _gated(flat, sh["gate"], sh["up"], sh["down"])
    return (y.reshape(b, s, h), idx.reshape(b, s, -1),
            cor.reshape(b, s, E))


@jax.jit
def _embed(table, ids):
    return table.astype(jnp.float32)[ids]


def _layer(x, p, sizes, first_expert, forced=None):
    eps = float(sizes["rms_norm_eps"])
    x = x + _attention(_rms(x, p["attn_norm"].astype(jnp.float32), eps),
                       _f32(p["attn"]), sizes)
    y = _rms(x, p["ffn_norm"].astype(jnp.float32), eps)
    if "moe" in p:
        out, idx, cor = _moe(y, p["moe"], sizes, first_expert,
                             forced=forced)
        return x + out, (idx, cor)
    return x + _gated_blocked(y, p["mlp"]), None


#: the keys of a configuration that the mathematics reads
KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
        "rope_scaling", "n_routed_experts", "num_experts_per_tok",
        "n_group", "topk_group", "norm_topk_prob", "routed_scaling_factor")

_LAYERS = {}


def _jitted_layer(sizes: dict, first_expert: int):
    """``_layer`` jitted for one configuration and share."""
    sizes = {k: sizes[k] for k in KEYS if k in sizes}
    key = (json.dumps(sizes, sort_keys=True), first_expert)
    if key not in _LAYERS:
        def fn(x, p, forced=None):
            with jax.default_matmul_precision("highest"):
                return _layer(x, p, sizes, first_expert, forced)
        _LAYERS[key] = jax.jit(fn)
    return _LAYERS[key]


def forward(params, ids, sizes: dict, *, rows=None, first_expert: int = 0,
            routing: bool = False, forced=None):
    """Logits in float32 for token ids [b, s]: ``[b, s, V]``, or with
    ``rows`` [b, r] only those positions ``[b, r, V]``. With ``routing``
    also ``(the experts each token chose [moe layers, b, s, k], its
    corrected scores [moe layers, b, s, E])``. ``forced`` [moe layers, b,
    s, k] makes every expert layer sum over those experts instead of its
    own choice (``_moe``). One jitted call a layer, so one layer's float32
    weights live at a time."""
    layer = _jitted_layer(sizes, int(first_expert))
    x = _embed(params["embed"], ids)
    chosen, cors = [], []
    n_layer = sum(1 for k in params if k.startswith("layer_"))
    for i in range(n_layer):
        p = params[f"layer_{i}"]
        if "moe" in p and forced is not None:
            x, own = layer(x, p, forced[len(chosen)])
        else:
            x, own = layer(x, p)
        if own is not None:
            chosen.append(own[0])
            cors.append(own[1])
    if rows is not None:
        x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    logits = _head(x, params["norm_f"], params["head"],
                   float(sizes["rms_norm_eps"]))
    if routing:
        return logits, jnp.stack(chosen), jnp.stack(cors)
    return logits


@jax.jit
def _head(x, w, head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, w.astype(jnp.float32), eps) @ head.astype(jnp.float32)
