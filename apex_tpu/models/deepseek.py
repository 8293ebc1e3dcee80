"""DeepSeek-V3-shaped decoders (``model_type: deepseek_v3``): pre-RMSNorm
blocks of latent attention (MLA) with YaRN rotary embeddings and a
gated-SiLU feed-forward that is dense in the leading layers and a
bias-corrected, group-limited top-k expert layer with shared experts after
them; untied head.

This module is the model's static description and the mathematics every
path shares: :class:`DeepseekConfig`, the parameter tree
(:func:`init_params`), the YaRN constants, the rotary rotation and the
attention projections. The serving forwards over the paged latent cache
are in :mod:`apex_tpu.serve.latent` (this model's layer in
:mod:`apex_tpu.serve.deepseek`), the expert layer in
:mod:`apex_tpu.transformer.moe_dropless`. Nothing here is imported by
``apex_tpu.models`` itself: import the module by name.

**A chip's share.** ``n_local_experts`` of the ``n_routed_experts`` live
here, starting at ``first_expert``; the router keeps its published width.
With ``n_local_experts == n_routed_experts`` the tree is the whole model.
``vocab_size`` is the number of rows held (a slice of the vocabulary is a
smaller vocabulary).

Parameter tree (no bias anywhere; ``h`` hidden, ``n`` heads)::

    embed [V, h]   head [h, V]   norm_f [h]
    layer_i/attn_norm, ffn_norm [h]
    layer_i/attn/q_a [h, q_lora]  q_norm [q_lora]  q_b [q_lora, n*(nope+rope)]
                 kv_a [h, kv_lora+rope]  kv_norm [kv_lora]
                 kv_b [kv_lora, n*(nope+v)]  o [n*v, h]
    layer_i/mlp/gate, up [h, I]  down [I, h]              (i < first_k_dense)
    layer_i/moe/router [h, E] f32  bias [E] f32           (i >= first_k_dense)
              /experts/gate_up [n_local, h, 2*Im]  down [n_local, Im, h]
              /shared/gate, up [h, Im*n_shared]  down [Im*n_shared, h]

``q_b`` and ``kv_b`` are packed per head (``[nope | rope]`` and
``[k_nope | v]``), an expert's ``gate_up`` holds gate in its first ``Im``
columns and up in the rest (one matmul for both).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops.layer_norm import fused_rms_norm_affine


@dataclasses.dataclass(frozen=True)
class DeepseekConfig:
    """Static sizes (hashable). Field names follow the published keys."""

    vocab_size: int                     # rows held here
    hidden_size: int
    num_layers: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int              # dense layers
    moe_intermediate_size: int
    n_routed_experts: int               # the router's width
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    first_k_dense_replace: int          # leading dense layers held here
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    first_expert: int = 0
    n_local_experts: Optional[int] = None   # None = all of them
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: ((key, value), ...) of the published ``rope_scaling`` group, or ()
    rope_scaling: Tuple[Tuple[str, Any], ...] = ()
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02

    #: what the expert layer asks of a description beside the share
    #: (``transformer/moe_dropless.py``): the rule, and how many of the
    #: router's slots past the routed experts compute nothing
    routing = "sigmoid_group_limited"
    zero_expert_num = 0

    def __post_init__(self):
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must divide into n_group")
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
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """What one token caches a layer: the normalised latent and the
        rotated shared key head."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace


# -- YaRN -----------------------------------------------------------------------

def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg):
    """Rotary inverse frequencies, float32 ``[rope_dim / 2]``: between the
    correction dims of ``beta_fast`` and ``beta_slow`` a linear ramp blends
    ``theta^(-2i/d)`` (fast dims, kept) into the same over ``factor`` (slow
    dims, interpolated)."""
    dim, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    plain = [base ** (-2.0 * i / dim) for i in range(dim // 2)]
    rs = dict(cfg.rope_scaling)
    if not rs:
        return jnp.asarray(plain, jnp.float32)
    factor = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = [min(1.0, max(0.0, (i - low) / (high - low)))
            for i in range(dim // 2)]
    return jnp.asarray([f / factor * r + f * (1.0 - r)
                        for f, r in zip(plain, ramp)], jnp.float32)


def rope_factor(cfg) -> float:
    """The factor on cos and sin (``mscale / mscale_all_dim`` scales)."""
    rs = dict(cfg.rope_scaling)
    if not rs:
        return 1.0
    f = float(rs["factor"])
    if rs.get("mscale") and rs.get("mscale_all_dim"):
        return _yarn_mscale(f, float(rs["mscale"])) \
            / _yarn_mscale(f, float(rs["mscale_all_dim"]))
    return _yarn_mscale(f, 1.0)


def softmax_scale(cfg) -> float:
    """``qk_head_dim^-0.5``, times the squared YaRN ``mscale_all_dim``
    scale where the configuration has one."""
    rs = dict(cfg.rope_scaling)
    s = cfg.qk_head_dim ** -0.5
    if rs.get("mscale_all_dim"):
        m = _yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
        s *= m * m
    return s


def rope(x, positions, cfg):
    """Rotate the interleaved pairs ``(2i, 2i+1)`` of ``x`` ``[t, ...,
    rope_dim]`` by ``positions`` ``[t]``, in float32, back in ``x.dtype``."""
    ang = positions.astype(jnp.float32)[:, None] * yarn_inv_freq(cfg)[None]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[1],)
    factor = rope_factor(cfg)
    cos = (jnp.cos(ang) * factor).reshape(shape)
    sin = (jnp.sin(ang) * factor).reshape(shape)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# -- parameters -------------------------------------------------------------------

def attention_params(cfg, w, ones):
    """One latent attention's sub-tree: ``w(*shape)`` draws a matrix,
    ``ones(d)`` is a norm weight (the order of the draws is part of what a
    seed means)."""
    h, n = cfg.hidden_size, cfg.num_heads
    return {"q_a": w(h, cfg.q_lora_rank),
            "q_norm": ones(cfg.q_lora_rank),
            "q_b": w(cfg.q_lora_rank, n * cfg.qk_head_dim),
            "kv_a": w(h, cfg.latent_dim),
            "kv_norm": ones(cfg.kv_lora_rank),
            "kv_b": w(cfg.kv_lora_rank,
                      n * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "o": w(n * cfg.v_head_dim, h)}


def init_params(cfg: DeepseekConfig, key):
    """Seeded random weights in ``cfg.dtype`` (normal, ``init_std``; norm
    weights 1; router and its correction bias float32, the bias small and
    non-zero so that it is exercised). Jit-pure."""
    h, dt, std = cfg.hidden_size, cfg.dtype, cfg.init_std
    im, nl = cfg.moe_intermediate_size, cfg.local_experts
    keys = iter(jax.random.split(key, 4 + 16 * cfg.num_layers))

    def w(*shape, dtype=dt):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    def ones(d):
        return jnp.ones((d,), jnp.float32)

    params = {"embed": w(cfg.vocab_size, h), "head": w(h, cfg.vocab_size),
              "norm_f": ones(h)}
    for i in range(cfg.num_layers):
        layer = {"attn_norm": ones(h), "ffn_norm": ones(h),
                 "attn": attention_params(cfg, w, ones)}
        if cfg.is_moe(i):
            sh = im * cfg.n_shared_experts
            layer["moe"] = {
                "router": w(h, cfg.n_routed_experts, dtype=jnp.float32),
                "bias": 0.1 * w(cfg.n_routed_experts, dtype=jnp.float32),
                "experts": {"gate_up": w(nl, h, 2 * im),
                            "down": w(nl, im, h)},
                "shared": {"gate": w(h, sh), "up": w(h, sh),
                           "down": w(sh, h)}}
        else:
            layer["mlp"] = {"gate": w(h, cfg.intermediate_size),
                            "up": w(h, cfg.intermediate_size),
                            "down": w(cfg.intermediate_size, h)}
        params[f"layer_{i}"] = layer
    return params


# -- shared mathematics ------------------------------------------------------------

def rms_norm(x, weight, eps):
    return fused_rms_norm_affine(x, weight.astype(x.dtype), x.shape[-1:],
                                 eps)


def gated_mlp(x, gate, up, down, *, acc=None):
    """``down(silu(gate x) * up x)``; the product in float32. ``acc``: the
    dtype the three matmuls leave their outputs in (None = ``x.dtype``;
    float32 spares gate and up a rounding before the product and hands a
    float32 residual stream an unrounded result: ``attention_inputs``)."""
    dot = functools.partial(jnp.dot, preferred_element_type=acc or x.dtype)
    g = dot(x, gate).astype(jnp.float32)
    u = dot(x, up).astype(jnp.float32)
    return dot((jax.nn.silu(g) * u).astype(x.dtype), down)


def attention_inputs(cfg, p, x, positions, *, q_scale: float = 1.0,
                     kv_scale: float = 1.0, acc=None):
    """The projections both attention paths start from, for rows ``x``
    ``[t, h]`` at ``positions`` ``[t]``: ``q_nope [t, n, nope]``, rotated
    ``q_pe [t, n, rope]``, and the token's cache row parts: the normalised
    latent ``c [t, kv_lora]`` and the rotated shared key ``k_pe [t, rope]``.
    ``q_scale`` / ``kv_scale`` multiply the normalised query / key-value
    latent (LongCat's ``mla_scale_q_lora`` / ``mla_scale_kv_lora``; at 1
    nothing is multiplied). ``acc``: the dtype in which a projection's
    output stays until the next matmul, the cache or the kernel needs it in
    ``x.dtype`` (None = ``x.dtype``: every output is rounded as it leaves
    its matmul; float32 rounds each of the four results once, after its
    norm and scale or its rotation: with the scales the attention scores
    are ~7 x as large, and every rounding on their way costs that much
    more). ``cfg``: any description with this module's attention sizes
    (:mod:`apex_tpu.models.longcat` has them too).
    """
    t, dt = x.shape[0], x.dtype
    eps = cfg.rms_norm_eps
    dot = functools.partial(jnp.dot, preferred_element_type=acc or dt)
    cq = rms_norm(dot(x, p["q_a"]), p["q_norm"], eps)
    if q_scale != 1.0:
        cq = cq * q_scale
    q = dot(cq.astype(dt), p["q_b"])
    q = q.reshape(t, cfg.num_heads, cfg.qk_head_dim)
    q_nope, q_pe = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    ckv = dot(x, p["kv_a"])
    c = rms_norm(ckv[:, :cfg.kv_lora_rank], p["kv_norm"], eps)
    if kv_scale != 1.0:
        c = c * kv_scale
    k_pe = rope(ckv[:, cfg.kv_lora_rank:], positions, cfg)
    return (q_nope.astype(dt), rope(q_pe, positions, cfg).astype(dt),
            c.astype(dt), k_pe.astype(dt))


def kv_b_heads(cfg, p):
    """``kv_b`` by head: ``(W_k [kv_lora, n, nope], W_v [kv_lora, n, v])``."""
    w = p["kv_b"].reshape(cfg.kv_lora_rank, cfg.num_heads,
                          cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]
