"""Qwen3-Next decoders (``model_type: qwen3_next``, Qwen
``Qwen3-Next-80B-A3B-Instruct``): pre-norm blocks whose mixer is a GATED
DELTA RULE linear attention in three layers of four and a gated softmax
attention in the fourth, each followed by an expert layer (softmax router,
top-k renormalised, beside a shared expert behind a sigmoid gate); no bias
anywhere, untied head. Trains; the serve engine does not hold it yet.

Every norm is ``rms(x) * (1 + w)`` (a zero-centred weight, eps
``rms_norm_eps``) but the one after the scan. A layer is ``x += mixer(n1(x));
x += experts(n2(x))``; layer ``i`` is full attention where ``(i + 1) %
full_attention_interval == 0``.

*Gated delta layer* (``apx:attn_gdn``). ``[q|k|v|z] = x W_qkvz`` (``n_k d_k
+ n_k d_k + n_v d_v + n_v d_v`` lanes, in that order), ``[b|a] = x W_ba``
(``n_v + n_v``). ``[q|k|v]`` passes a causal depthwise convolution of
``linear_conv_kernel_dim`` taps, no bias, then SiLU (``apx:gdn_conv``). A
value head ``h`` reads key head ``h // (n_v / n_k)``; ``q`` and ``k`` are
L2-normalised over their lanes (eps 1e-6) and ``q`` scaled by ``d_k^-0.5``;
``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)`` in float32;
the recurrence is :func:`apex_tpu.ops.gated_delta.gated_delta_rule`
(``apx:gdn_scan``). Then ``o = rms(o) * w * silu(z)`` a head and ``y = o
W_out``.

*Full-attention layer* (``apx:attn_full``). ``[query|gate] = x W_q`` (all
heads' queries, then all heads' gates), ``k, v = x W_k, x W_v``; ``query``
and ``k`` pass an RMSNorm over the head; a rotary embedding turns the pairs
``(i, i + r/2)`` of the first ``r = partial_rotary_factor * head_dim`` lanes,
the rest is left as it is; causal flash attention, the key/value heads as
they are; ``y = (attn * sigmoid(gate)) W_o``.

*Expert layer*: :func:`apex_tpu.transformer.moe_dropless.expert_layer` with
``routing = "softmax_topk_renorm"``, and in the layer's tree a ``shared``
expert with an ``out_gate`` (``sigmoid(x w) * shared(x)``), computed whole on
every chip.

**What a block keeps**, as ``models/mellum.py``: every block runs under
``jax.checkpoint``; a full-attention block keeps the flash kernel's two
results and its three operands by name, a gated-delta block keeps nothing
(the scan's chunk states are rebuilt in the backward: 268 MB a layer in
float32 at 16,384 tokens, live for one layer at a time).

**A chip's share**: ``n_local_experts`` of ``n_routed_experts`` from
``first_expert``, ``vocab_size`` the rows held. ``A_log`` and ``dt_bias``
stay float32 under amp (:func:`keep_fp32`): ``exp(A_log)`` times a softplus
is a log-decay, and a bf16 copy of either moves every decay of a head.

Parameter tree (``h`` hidden)::

    embed [V, h]   head [V, h]   norm_f [h]
    layer_i/attn_norm, ffn_norm [h]
           /gdn/qkvz [h, 2 n_k d_k + 2 n_v d_v]  ba [h, 2 n_v]
               /conv [taps, 2 n_k d_k + n_v d_v]  A_log, dt_bias [n_v]
               /norm [d_v]  out [n_v d_v, h]              (a linear layer)
           /attn/q [h, 2 n d]  k, v [h, m d]  o [n d, h]
                /q_norm, k_norm [d]                       (a full layer)
           /moe/router [h, E]
               /experts/gate_up [n_local, h, 2 Im]  down [n_local, Im, h]
               /shared/gate, up [h, Is]  down [Is, h]  out_gate [h, 1]

Import the module by name (``apex_tpu.models`` does not).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu.models import deepseek as _ds
from apex_tpu.models.mellum import MOE_COUNTS, QKV, record_step  # noqa: F401
from apex_tpu.monitor import profile as _prof
from apex_tpu.ops.flash_attention import (FLASH_LSE, FLASH_OUT,
                                          flash_attention)
from apex_tpu.ops.gated_delta import gated_delta_rule
from apex_tpu.ops.lm_head_ce import fused_lm_head_cross_entropy
from apex_tpu.transformer import moe_dropless

LINEAR, FULL = "linear_attention", "full_attention"
#: the leaves amp's O2 leaves in float32 (``keep_fp32``)
FP32_LEAVES = ("A_log", "dt_bias")
_L2_EPS = 1e-6


def keep_fp32(names, leaf) -> bool:
    """``amp.initialize(keep_fp32_predicate=...)`` for this family: False
    (kept as it is) for a gated-delta layer's ``A_log`` and ``dt_bias``."""
    del leaf
    return names[-1] not in FP32_LEAVES


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """Static sizes (hashable). Field names follow the published keys, but
    ``num_layers`` / ``num_heads`` / ``num_kv_heads`` / ``n_routed_experts``
    (``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
    ``num_experts``), which the shared code reads under those names."""

    vocab_size: int                     # rows held here
    hidden_size: int
    num_layers: int                     # layers held here, from layer 0
    num_heads: int
    num_kv_heads: int
    head_dim: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    n_routed_experts: int               # the router's width
    num_experts_per_tok: int
    full_attention_interval: int = 4
    linear_conv_kernel_dim: int = 4
    partial_rotary_factor: float = 0.25
    first_expert: int = 0
    n_local_experts: Optional[int] = None   # None = all of them
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    dtype: Any = jnp.bfloat16
    init_std: float = 0.02

    #: what the expert layer asks of a description (``moe_dropless``)
    routing = "softmax_topk_renorm"
    zero_expert_num = 0

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("linear_num_value_heads must be a multiple of "
                             "linear_num_key_heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of a "
                f"head of {self.head_dim} is no even number of lanes")
        if self.shared_expert_intermediate_size <= 0:
            raise ValueError("the family has one shared expert a layer")
        n = self.local_experts
        if not 0 <= self.first_expert <= self.n_routed_experts - n:
            raise ValueError(
                f"experts [{self.first_expert}, {self.first_expert + n}) "
                f"are not among the {self.n_routed_experts} routed ones")

    @property
    def layer_types(self):
        return tuple(FULL if (i + 1) % self.full_attention_interval == 0
                     else LINEAR for i in range(self.num_layers))

    @property
    def local_experts(self) -> int:
        return (self.n_routed_experts if self.n_local_experts is None
                else self.n_local_experts)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim


def init_params(cfg: Qwen3NextConfig, key):
    """Seeded random weights in ``cfg.dtype`` (normal, ``init_std``). The
    zero-centred norm weights 0 and the gated norm's 1 (both the identity),
    the router float32 as published, ``A_log = log U(0, 16)`` and ``dt_bias``
    the inverse softplus of ``exp U(log 0.001, log 0.1)`` in float32 (the
    published implementation's initialisers). Jit-pure."""
    h, dt, std = cfg.hidden_size, cfg.dtype, cfg.init_std
    n, m, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    nv = cfg.linear_num_value_heads
    im, ish, nl = (cfg.moe_intermediate_size,
                   cfg.shared_expert_intermediate_size, cfg.local_experts)
    keys = iter(jax.random.split(key, 2 + 16 * cfg.num_layers))
    f32 = jnp.float32

    def w(*shape, dtype=dt):
        return (std * jax.random.normal(next(keys), shape, f32)).astype(dtype)

    def zeros(size=h):
        return jnp.zeros((size,), f32)

    params = {"embed": w(cfg.vocab_size, h), "head": w(cfg.vocab_size, h),
              "norm_f": zeros()}
    for i, kind in enumerate(cfg.layer_types):
        layer = {
            "attn_norm": zeros(), "ffn_norm": zeros(),
            "moe": {"router": w(h, cfg.n_routed_experts, dtype=f32),
                    "experts": {"gate_up": w(nl, h, 2 * im),
                                "down": w(nl, im, h)},
                    "shared": {"gate": w(h, ish), "up": w(h, ish),
                               "down": w(ish, h), "out_gate": w(h, 1)}}}
        if kind == FULL:
            layer["attn"] = {"q": w(h, 2 * n * d), "k": w(h, m * d),
                             "v": w(h, m * d), "o": w(n * d, h),
                             "q_norm": zeros(d), "k_norm": zeros(d)}
        else:
            dt_init = jnp.exp(jax.random.uniform(
                next(keys), (nv,), minval=jnp.log(0.001),
                maxval=jnp.log(0.1)))
            layer["gdn"] = {
                "qkvz": w(h, 2 * cfg.key_dim + 2 * cfg.value_dim),
                "ba": w(h, 2 * nv),
                "conv": w(cfg.linear_conv_kernel_dim,
                          2 * cfg.key_dim + cfg.value_dim),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (nv,), minval=1e-3, maxval=16.0)),
                # softplus^-1(x) = x + log(1 - exp(-x))
                "dt_bias": dt_init + jnp.log(-jnp.expm1(-dt_init)),
                "norm": jnp.ones((cfg.linear_value_head_dim,), f32),
                "out": w(cfg.value_dim, h)}
        params[f"layer_{i}"] = layer
    return params


# -- pieces ------------------------------------------------------------------

def rms_norm(x, w, eps):
    """``rms(x) * (1 + w)``: the family's zero-centred norm."""
    return _ds.rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def partial_rope(x, positions, cfg):
    """Rotate the pairs ``(i, i + r/2)`` of the first ``r = cfg.rotary_dim``
    lanes of ``x`` ``[b, n, s, d]`` by ``positions`` ``[s]``, in float32; the
    other lanes pass."""
    r = cfg.rotary_dim
    inv_freq = cfg.rope_theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq     # [s, r/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x[..., :r].astype(jnp.float32)
    a, b = jnp.split(x32, 2, axis=-1)
    turned = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate([turned.astype(x.dtype), x[..., r:]], axis=-1)


def causal_conv(x, w):
    """``y_t = sum_i w[i] * x[t - (taps - 1) + i]`` a channel, zeros before
    the sequence: ``x`` ``[b, s, c]``, ``w`` ``[taps, c]``; float32 sums,
    back in ``x.dtype``."""
    taps, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + s].astype(jnp.float32) * w[i].astype(jnp.float32)
            for i in range(taps))
    return y.astype(x.dtype)


def _l2_normalised(x, scale=1.0):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.sum(x32 * x32, -1, keepdims=True) + _L2_EPS)
    return (x32 * (inv * scale)).astype(x.dtype)


def gated_delta_attention(cfg, p, x, *, scan_impl="kernel", interpret=None):
    """A gated-delta sub-layer's branch for normalised ``x`` ``[b, s, h]``,
    under ``apx:attn_gdn``."""
    b, s, _ = x.shape
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    f32 = jnp.float32
    with _prof.scope("attn_gdn"):
        qkvz = jnp.dot(x, p["qkvz"])
        ba = jnp.dot(x, p["ba"]).astype(f32)
        qkv, z = qkvz[..., :2 * cfg.key_dim + cfg.value_dim], \
            qkvz[..., 2 * cfg.key_dim + cfg.value_dim:]
        with _prof.scope("gdn_conv"):
            qkv = jax.nn.silu(causal_conv(qkv, p["conv"]).astype(f32)
                              ).astype(x.dtype)

        def heads(lanes, count, d):
            return lanes.reshape(b, s, count, d).transpose(0, 2, 1, 3)

        # a value head reads the key head it shares: h // (n_v / n_k)
        q = jnp.repeat(_l2_normalised(
            heads(qkv[..., :cfg.key_dim], nk, dk), dk ** -0.5), nv // nk, 1)
        k = jnp.repeat(_l2_normalised(
            heads(qkv[..., cfg.key_dim:2 * cfg.key_dim], nk, dk)),
            nv // nk, 1)
        v = heads(qkv[..., 2 * cfg.key_dim:], nv, dv)
        beta = jax.nn.sigmoid(ba[..., :nv]).transpose(0, 2, 1)
        g = (-jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
            ba[..., nv:] + p["dt_bias"].astype(f32))).transpose(0, 2, 1)
        o, _ = gated_delta_rule(q, k, v, g, beta, impl=scan_impl,
                                interpret=interpret)      # [b, nv, s, dv]
        o32 = o.transpose(0, 2, 1, 3).astype(f32)
        o32 = o32 * jax.lax.rsqrt(
            jnp.mean(o32 * o32, -1, keepdims=True) + cfg.rms_norm_eps)
        o = o32 * p["norm"].astype(f32) \
            * jax.nn.silu(z.reshape(b, s, nv, dv).astype(f32))
        return jnp.dot(o.astype(x.dtype).reshape(b, s, nv * dv), p["out"])


def attention(cfg, p, x, *, interpret=None):
    """The full-attention sub-layer's branch for normalised ``x`` ``[b, s,
    h]``, under ``apx:attn_full``."""
    b, s, _ = x.shape
    n, m, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    with _prof.scope("attn_full"):
        def heads(lanes, count):
            return lanes.reshape(b, s, count, d).transpose(0, 2, 1, 3)

        qg = jnp.dot(x, p["q"])
        gate = qg[..., n * d:]
        pos = jnp.arange(s)
        q, k, v = checkpoint_name(
            (partial_rope(rms_norm(heads(qg[..., :n * d], n), p["q_norm"],
                                   eps), pos, cfg),
             partial_rope(rms_norm(heads(jnp.dot(x, p["k"]), m), p["k_norm"],
                                   eps), pos, cfg),
             heads(jnp.dot(x, p["v"]), m)), QKV)
        o = flash_attention(q, k, v, causal=True, scale=d ** -0.5,
                            interpret=interpret)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, n * d).astype(jnp.float32) \
            * jax.nn.sigmoid(gate.astype(jnp.float32))
        return jnp.dot(o.astype(x.dtype), p["o"])


# -- the model ---------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _kept():
    """What a recomputed block keeps (ONE object for every layer, as
    ``models/mellum.py:_kept``)."""
    return jax.checkpoint_policies.save_only_these_names(FLASH_OUT, FLASH_LSE,
                                                         QKV)


def _block(cfg, kind, p, x, impl, scan_impl, interpret):
    b, s, h = x.shape
    eps = cfg.rms_norm_eps
    xn = rms_norm(x, p["attn_norm"], eps)
    if kind == FULL:
        x = x + attention(cfg, p["attn"], xn, interpret=interpret)
    else:
        x = x + gated_delta_attention(cfg, p["gdn"], xn, scan_impl=scan_impl,
                                      interpret=interpret)
    y, stats = moe_dropless.expert_layer(
        cfg, p["moe"], rms_norm(x, p["ffn_norm"], eps).reshape(b * s, h),
        impl=impl, interpret=interpret)
    return x + y.reshape(b, s, h), stats


def hidden(cfg: Qwen3NextConfig, params, ids, *, impl: str = "kernel",
           scan_impl: str = "kernel", interpret=None):
    """The final normalised hidden state ``[b, s, h]`` for token ids ``[b,
    s]`` and ``aux`` (``models/mellum.py:hidden``'s). ``impl``: the grouped
    matmul's; ``scan_impl``: the gated delta rule's."""
    x = jnp.take(params["embed"], ids, axis=0)
    stats = []
    for i, kind in enumerate(cfg.layer_types):
        block = jax.checkpoint(
            functools.partial(_block, cfg, kind, impl=impl,
                              scan_impl=scan_impl, interpret=interpret),
            policy=_kept())
        x, st = block(params[f"layer_{i}"], x)
        stats.append(st)
    aux = {"moe": {k: jnp.stack([st[k] for st in stats])
                   for k in MOE_COUNTS},
           "moe_idx": jnp.stack([st["idx"] for st in stats])}
    return rms_norm(x, params["norm_f"], cfg.rms_norm_eps), aux


def forward(cfg: Qwen3NextConfig, params, ids, **kw):
    """``(logits [b, s, V] over the rows held, aux)``."""
    x, aux = hidden(cfg, params, ids, **kw)
    return jnp.dot(x, params["head"].T), aux


def loss(cfg: Qwen3NextConfig, params, ids, labels, **kw):
    """``(mean next-token cross entropy over the rows of the vocabulary
    held here, aux)`` through the fused LM-head kernel; ``aux`` without the
    per-token choices."""
    x, aux = hidden(cfg, params, ids, **kw)
    per_token = fused_lm_head_cross_entropy(
        x, params["head"], labels, interpret=kw.get("interpret"))
    return jnp.mean(per_token), {"moe": aux["moe"]}
