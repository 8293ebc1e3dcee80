"""Qwen3-Next decoders (``model_type: qwen3_next``), plainly: ``jax.numpy``
in float32 at ``highest`` matmul precision, no kernels. Written from the
published ``config.json`` keys (named in ``benchmarks/configs/
qwen3-next-80b-ep32-l4.json``):

- every norm is ``x / rms(x) * (1 + w)`` (eps ``rms_norm_eps``) but the one
  after the recurrence; a layer is ``x += mixer(n1(x)); x += experts(n2(x))``;
  layer ``i`` is full attention where ``(i + 1) % full_attention_interval ==
  0`` (here: where the tree's layer holds ``attn`` and not ``gdn``);
- gated delta layer: ``[q|k|v|z] = x W_qkvz``, ``[b|a] = x W_ba``; ``[q|k|v]``
  through a causal depthwise convolution of ``linear_conv_kernel_dim`` taps
  and SiLU; ``linear_num_key_heads`` heads of ``q``, ``k`` (L2-normalised, eps
  1e-6; ``q`` times ``linear_key_head_dim^-0.5``), ``linear_num_value_heads``
  of ``v``, value head ``h`` on key head ``h // (n_v / n_k)``; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; TOKEN BY TOKEN from
  a zero state ``S`` ``[d_k, d_v]``::

      S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T
      o_t = S^T q_t

  then ``o / rms(o) * w * silu(z)`` a head and ``W_out``;
- full-attention layer: ``[query|gate] = x W_q``, RMSNorm of ``query`` and
  ``k`` over the head, a rotary embedding (``rope_theta``, pairs ``(i, i +
  r/2)``) over the first ``r = partial_rotary_factor * head_dim`` lanes,
  causal softmax attention at ``head_dim^-0.5`` with ``num_key_value_heads``
  shared by groups, ``(attn * sigmoid(gate)) W_o``;
- experts: ``reference/mellum.py``'s rule (softmax over all ``num_experts``,
  the ``num_experts_per_tok`` largest, renormalised) plus ``sigmoid(x w_sg) *
  shared(x)``;
- loss: mean next-token cross entropy of the logits.

It takes the parameter tree of ``apex_tpu.models.qwen3_next`` and is written
to fit beside the program under test at the published widths and 16,384
tokens: the recurrence is a ``lax.scan`` over tokens nested in one over
stretches of :data:`STRETCH` tokens, attention a masked softmax a block of
query rows at a time, the experts one at a time, and under ``jax.grad``
each layer, stretch, query block and expert is recomputed rather than kept.
Nothing here knows of chunks, of a triangular system or of a cumulated
decay: the recurrence is the definition.

Departures, which the tree and the cut force, are ``reference/mellum.py``'s:
a chip's share of the experts and of the vocabulary, ``forced`` choices for a
comparison of arithmetic (the choice is compared apart, ``tie_distance``), no
auxiliary loss, no multi-token-prediction module (the config has no key for
one).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from . import mellum as _mellum

tie_distance = _mellum.tie_distance

#: query rows of one block of the masked softmax
QUERY_BLOCK = 512
#: tokens of one recomputed stretch of the recurrence
STRETCH = 128
_L2_EPS = 1e-6

#: the keys of a configuration that the mathematics reads
KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
        "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim",
        "partial_rotary_factor", "rope_theta", "rms_norm_eps",
        "num_experts_per_tok", "norm_topk_prob")


# -- pieces ----------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w.astype(jnp.float32))


def _partial_rope(x, theta, r):
    """Rotate the pairs ``(i, i + r/2)`` of the first ``r`` lanes of ``x``
    [b, s, n, d]."""
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]],
                           axis=-1)


def _attention(x, p, sizes):
    b, s, _ = x.shape
    n, m = int(sizes["num_attention_heads"]), int(sizes["num_key_value_heads"])
    d, eps = int(sizes["head_dim"]), float(sizes["rms_norm_eps"])
    theta = float(sizes["rope_theta"])
    r = int(d * float(sizes["partial_rotary_factor"]))
    w = {k: v.astype(jnp.float32) for k, v in p.items()}
    qg = x @ w["q"]
    gate = qg[..., n * d:]
    q = _partial_rope(_rms(qg[..., :n * d].reshape(b, s, n, d), w["q_norm"],
                           eps), theta, r)
    k = _partial_rope(_rms((x @ w["k"]).reshape(b, s, m, d), w["k_norm"],
                           eps), theta, r)
    v = (x @ w["v"]).reshape(b, s, m, d)
    blk = math.gcd(QUERY_BLOCK, s)
    q = q.reshape(b, s // blk, blk, m, n // m, d)
    keys = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(qb, first):
        seen = keys <= first + jnp.arange(blk)[:, None]          # [blk, s]
        sc = jnp.einsum("bqmgd,bkmd->bmgqk", qb, k) * d ** -0.5
        pr = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bmgqk,bkmd->bqmgd", pr, v)

    ctx = jax.lax.map(lambda a: block(*a), (jnp.moveaxis(q, 1, 0),
                                            jnp.arange(s // blk) * blk))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, s, n * d)
    return (ctx * jax.nn.sigmoid(gate)) @ w["o"]


def _conv(x, w):
    """``y_t = sum_i w[i] x[t - (taps - 1) + i]``, zeros before token 0."""
    taps, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, i:i + s] * w[i] for i in range(taps))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + _L2_EPS)


def recurrence(q, k, v, g, beta):
    """The gated delta rule token by token: ``q, k`` [b, s, n_v, d_k], ``v``
    [b, s, n_v, d_v], ``g, beta`` [b, s, n_v], all float32. Returns ``o`` [b,
    s, n_v, d_v]."""
    b, s, nv, dk = q.shape
    dv = v.shape[-1]

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs            # [b, nv, .]
        S = jnp.exp(g_t)[..., None, None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bnk,bnkv->bnv", k_t, S))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bnk,bnkv->bnv", q_t, S)

    @jax.checkpoint
    def stretch(S, xs):
        return jax.lax.scan(token, S, xs)

    step = math.gcd(STRETCH, s)
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape((s // step, step) + x.shape[:1]
                                             + x.shape[2:])
               for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(stretch, jnp.zeros((b, nv, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o.reshape((s, b, nv, dv)), 0, 1)


def _gated_delta(x, p, sizes):
    b, s, _ = x.shape
    nk, nv = (int(sizes["linear_num_key_heads"]),
              int(sizes["linear_num_value_heads"]))
    dk, dv = (int(sizes["linear_key_head_dim"]),
              int(sizes["linear_value_head_dim"]))
    w = {k: v.astype(jnp.float32) for k, v in p.items()}
    kd, vd = nk * dk, nv * dv
    qkvz, ba = x @ w["qkvz"], x @ w["ba"]
    qkv = jax.nn.silu(_conv(qkvz[..., :2 * kd + vd], w["conv"]))
    z = qkvz[..., 2 * kd + vd:].reshape(b, s, nv, dv)
    q = jnp.repeat(_l2(qkv[..., :kd].reshape(b, s, nk, dk)) * dk ** -0.5,
                   nv // nk, axis=2)
    k = jnp.repeat(_l2(qkv[..., kd:2 * kd].reshape(b, s, nk, dk)),
                   nv // nk, axis=2)
    v = qkv[..., 2 * kd:].reshape(b, s, nv, dv)
    beta = jax.nn.sigmoid(ba[..., :nv])
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[..., nv:] + w["dt_bias"])
    o = recurrence(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + float(sizes["rms_norm_eps"]))
    return (o * w["norm"] * jax.nn.silu(z)).reshape(b, s, vd) @ w["out"]


def _moe(x, p, sizes, first_expert, forced=None):
    """This share's part of the expert layer (``reference/mellum.py``'s: the
    held experts' terms of the published sum) plus the shared expert behind
    its gate, whole. Returns ``(y, own choice, router logits)``."""
    y, idx, z = _mellum._moe(x, p, sizes, first_expert, forced)
    sh = {k: v.astype(jnp.float32) for k, v in p["shared"].items()}
    shared = (jax.nn.silu(x @ sh["gate"]) * (x @ sh["up"])) @ sh["down"]
    return y + jax.nn.sigmoid(x @ sh["out_gate"]) * shared, idx, z


def _layer(x, p, sizes, first_expert, forced=None):
    eps = float(sizes["rms_norm_eps"])
    xn = _rms(x, p["attn_norm"], eps)
    x = x + (_attention(xn, p["attn"], sizes) if "attn" in p
             else _gated_delta(xn, p["gdn"], sizes))
    out, idx, z = _moe(_rms(x, p["ffn_norm"], eps), p["moe"], sizes,
                       first_expert, forced)
    return x + out, (idx, z)


_LAYERS = {}


def _jitted_layer(sizes: dict, first_expert: int):
    """``_layer`` jitted for one configuration and share (the kind of a
    layer follows from its sub-tree)."""
    sizes = {k: sizes[k] for k in KEYS if k in sizes}
    key = (json.dumps(sizes, sort_keys=True), first_expert)
    if key not in _LAYERS:
        def fn(x, p, forced=None):
            with jax.default_matmul_precision("highest"):
                return _layer(x, p, sizes, first_expert, forced)
        _LAYERS[key] = jax.jit(fn)
    return _LAYERS[key]


def _n_layers(params) -> int:
    return sum(1 for k in params if k.startswith("layer_"))


def forward(params, ids, sizes: dict, *, first_expert: int = 0,
            routing: bool = False, forced=None):
    """Logits in float32 for token ids [b, s]: ``[b, s, V]``; with
    ``routing`` also the choices [layers, b, s, k] and the router's logits
    [layers, b, s, E]; ``forced`` as ``reference/mellum.py:forward``. One
    jitted call a layer."""
    x = _embed(params["embed"], ids)
    chosen, logits = [], []
    layer = _jitted_layer(sizes, int(first_expert))
    for i in range(_n_layers(params)):
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
                _layer, sizes=sizes, first_expert=int(first_expert)))
            x, _ = layer(x, params[f"layer_{i}"],
                         forced=None if forced is None else forced[i])
        logits = _rms(x, params["norm_f"], float(sizes["rms_norm_eps"])) \
            @ params["head"].astype(jnp.float32).T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return reduce(-jnp.take_along_axis(logp, labels[..., None], -1))


def grads(params, ids, labels, sizes: dict, leaves, **kw):
    """``jax.grad`` of :func:`loss` to the named leaves alone (key paths,
    as ``reference/mellum.py:grads``): ``{path: gradient}`` in float32."""
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
