"""MiniCPM-SALA decoders (``openbmb/MiniCPM-SALA``), plainly: ``jax.numpy``
in float32 at ``highest`` matmul precision, no kernels, no cache, no chunk
algebra. Written from the published ``config.json`` keys and the sizes
``benchmarks/configs/minicpm-sala-9b-l12.json`` lists under ``assumed``
(``N`` = RMSNorm at ``rms_norm_eps``; no bias anywhere; untied head)::

    h = scale_emb * E[ids]
    for each layer (its kind from mixer_types):
        h = h + r * Mixer(N(h))           r = scale_depth / sqrt(mup_denominator)
        h = h + r * down(silu(gate m) * up m),  m = N(h)
    logits = W_head (N(h) / (hidden_size / dim_model_base))

- ``lightning-attn``: ``q, k, v = W_q x, W_k x, W_v x`` (``lightning_nh``
  heads of ``lightning_head_dim`` each), RMSNorm a head on q and k, rotary
  at ``rope_theta`` on q and k; a head's state ``S_t = lam_h S_{t-1} + k_t^T
  v_t``, ``o_t = (q_t / sqrt(d)) S_t``, ``lam_h = exp(-2^(-8 (h + 1) / H))``:
  THE RECURRENCE, a ``lax.scan`` over tokens; then ``W_o (N(o) *
  sigmoid(W_g x))``, the norm over the concatenated heads;
- ``minicpm4``: ``num_attention_heads`` query heads, ``num_key_value_heads``
  K|V heads (a group of query heads shares one), RMSNorm a head on q and k,
  no rotary; the query at position ``t``: ``t + 1 <= dense_len``: causal
  softmax attention; else over the tokens up to ``t`` of ``topk`` blocks of
  ``block_size``: the first ``init_blocks``, the blocks the last
  ``window_size`` tokens lie in, and the best-scoring others, a block's
  score the maximum over the compressed keys that overlap it of ``sum over
  the group's heads of softmax_j(q_t . kbar_j / sqrt(d))`` over the compressed
  keys ``kbar_j = mean(k[stride j : stride j + kernel_size])`` wholly within
  ``0 .. t``; written as A MASK over the dense score matrix; then ``W_o
  (attn * sigmoid(W_g x))``.

It takes the parameter tree of ``apex_tpu.models.minicpm_sala`` (so the same
weights go through both) ONE SEQUENCE at a time and works on it piece by
piece so that 16k tokens at the published widths fit beside the program
under test: every piece is one jitted call that converts its own weights to
float32, the MLP runs a block of columns at a time and the sparse attention
a block of query rows at a time. ``forced`` makes a sparse layer attend the
blocks the PROGRAM chose (a bf16 score flips near-ties of a top-64-of-~300
choice, and one flipped block moves the logits more than the arithmetic's
own error): the check then holds the choice itself to a near-tie
(:func:`tie_distance`). Rotary pairs are the interleaved ``(2i, 2i + 1)``
lanes (a permutation of q and k alike: every product ``q . k`` is the same).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: the MLP is summed over column blocks of its intermediate size, so that
#: its float32 weights are never whole in memory
BLOCK_COLUMNS = 4096
#: query rows a call of the sparse attention: [heads, rows, s] float32 scores
BLOCK_ROWS = 128

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
        "lightning_nh", "lightning_head_dim", "rms_norm_eps", "rope_theta",
        "kernel_size", "kernel_stride", "block_size", "window_size",
        "init_blocks", "topk", "dense_len")


def static(sizes: dict):
    """``sizes`` cut to :data:`KEYS`, hashable: what the pieces take."""
    return tuple(sorted((k, sizes[k]) for k in KEYS))


def _piece(fn):
    """One jitted call at ``highest`` matmul precision; ``sizes`` static."""
    @functools.partial(jax.jit, static_argnames=("sizes",))
    def run(*args, sizes, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*args, dict(sizes), **kw)
    return run


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """Rotate the interleaved pairs of ``x`` [s, n, d]."""
    dim = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _qkv(a, p, n, n_kv, d, eps):
    s = a.shape[0]
    f32 = lambda w: w.astype(jnp.float32)
    q = _rms((a @ f32(p["q"])).reshape(s, n, d), p["q_norm"], eps)
    k = _rms((a @ f32(p["k"])).reshape(s, n_kv, d), p["k_norm"], eps)
    return q, k, (a @ f32(p["v"])).reshape(s, n_kv, d)


def _gated_out(a, p, ctx):
    f32 = lambda w: w.astype(jnp.float32)
    return (ctx * jax.nn.sigmoid(a @ f32(p["gate"]))) @ f32(p["o"])


# -- lightning attention: the recurrence ----------------------------------------

def decay(num_heads: int):
    """``lam_h = exp(-2^(-8 (h + 1) / H))``."""
    h = np.arange(1, num_heads + 1, dtype=np.float64)
    return np.exp(-(2.0 ** (-8.0 * h / num_heads))).astype(np.float32)


@_piece
def lightning(x, w_norm, p, sizes):
    """``Mixer(N(x))`` of a lightning layer on ``x`` [s, h]."""
    n, d = sizes["lightning_nh"], sizes["lightning_head_dim"]
    eps = sizes["rms_norm_eps"]
    a = _rms(x, w_norm, eps)
    pos = jnp.arange(x.shape[0])
    q, k, v = _qkv(a, p, n, n, d, eps)
    q = _rope(q, pos, sizes["rope_theta"]) / d ** 0.5
    k = _rope(k, pos, sizes["rope_theta"])
    lam = jnp.asarray(decay(n))[:, None, None]

    def step(S, qkv):
        q_t, k_t, v_t = qkv                          # [n, d] each
        S = lam * S + k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("ni,nij->nj", q_t, S)

    _, o = jax.lax.scan(step, jnp.zeros((n, d, d), jnp.float32), (q, k, v))
    o = _rms(o.reshape(x.shape[0], n * d), p["o_norm"], eps)
    return _gated_out(a, p, o)


# -- sparse attention: a mask over the dense scores ------------------------------

def live_and_forced(pos, n_blocks, sizes, xp=np):
    """bool ``[r, n_blocks]`` each: the blocks that hold a token up to
    ``pos`` ``[r]``, and those of them a sparse row always attends
    (``xp``: ``numpy``, or ``jax.numpy`` inside a piece)."""
    B = sizes["block_size"]
    b = xp.arange(n_blocks)[None, :]
    pos = pos[:, None]
    live = b <= pos // B
    first_w = xp.maximum(pos - sizes["window_size"] + 1, 0) // B
    return live, live & ((b < sizes["init_blocks"]) | (b >= first_w))


def _block_scores(q, k, pos, n_blocks, sizes):
    """``[r, kv, n_blocks]``: each block's score for the queries ``q`` [r,
    n, d] at positions ``pos`` over the keys ``k`` [s, kv, d] of the whole
    sequence; -1 where no complete compressed key overlaps the block."""
    ks, st, B = sizes["kernel_size"], sizes["kernel_stride"], \
        sizes["block_size"]
    s, kv, d = k.shape
    r, n, _ = q.shape
    ov, per = ks // st, B // st
    nk = s // st - ov + 1
    part = k[:s // st * st].reshape(s // st, st, kv, d).sum(1)
    kbar = sum(part[e:e + nk] for e in range(ov)) / ks     # [nk, kv, d]
    sc = jnp.einsum("rkgd,jkd->rkgj", q.reshape(r, kv, n // kv, d),
                    kbar) / d ** 0.5
    j = jnp.arange(nk)
    whole = (j[None, :] * st + ks <= pos[:, None] + 1)[:, None, None, :]
    p = jax.nn.softmax(jnp.where(whole, sc, -jnp.inf), axis=-1)
    p = jnp.where(whole, p, 0.0).sum(2)                     # [r, kv, nk]
    p = jnp.where(whole[:, :, 0], p, -1.0)
    # block b overlaps the keys b * per - (ov - 1) .. b * per + per - 1
    p = jnp.pad(p[..., :n_blocks * per],
                ((0, 0), (0, 0), (ov - 1, max(0, n_blocks * per - nk))),
                constant_values=-1.0)
    return jnp.stack([p[..., e::per][..., :n_blocks]
                      for e in range(per + ov - 1)]).max(0)


@_piece
def sparse_rows(q, pos, k, v, forced, sizes):
    """A block of query rows of a sparse layer: ``(context [r, n d], own
    attended blocks bool [r, kv, nb], block scores [r, kv, nb])``.
    ``forced`` (bool ``[r, kv, nb]`` or None): the blocks a sparse row
    attends instead of its own choice."""
    B, topk = sizes["block_size"], sizes["topk"]
    s, kv, d = k.shape
    r, n, _ = q.shape
    nb = -(-s // B)
    scores = _block_scores(q, k, pos, nb, sizes)
    live, must = live_and_forced(pos, nb, sizes, jnp)
    ranked = jnp.where(must[:, None], jnp.inf,
                       jnp.where(live[:, None], scores, -jnp.inf))
    # exactly topk; equal scores are structural (two adjacent blocks share
    # the compressed key that straddles them): the earlier block first
    best = jnp.argsort(-ranked, axis=-1, stable=True)[..., :min(topk, nb)]
    own = (best[..., None] == jnp.arange(nb)).any(-2) & live[:, None]
    dense = (pos + 1 <= sizes["dense_len"])[:, None, None]
    own = jnp.where(dense, live[:, None], own)
    # a row the program never fed has no choice to force: its own
    use = own if forced is None else jnp.where(
        dense | ~forced.any(-1, keepdims=True), own, forced)
    t = jnp.arange(s)
    mask = jnp.repeat(use, B, axis=-1)[..., :s] \
        & (t[None, None, :] <= pos[:, None, None])          # [r, kv, s]
    g = n // kv
    sc = jnp.einsum("rkgd,skd->rkgs", q.reshape(r, kv, g, d), k) / d ** 0.5
    p = jax.nn.softmax(jnp.where(mask[:, :, None], sc, -jnp.inf), axis=-1)
    ctx = jnp.einsum("rkgs,skd->rkgd", p, v)
    return ctx.reshape(r, n * d), own, scores


@_piece
def sparse_inputs(x, w_norm, p, sizes):
    eps = sizes["rms_norm_eps"]
    a = _rms(x, w_norm, eps)
    return (a,) + _qkv(a, p, sizes["num_attention_heads"],
                       sizes["num_key_value_heads"], sizes["head_dim"], eps)


@_piece
def sparse_output(a, p, ctx, sizes):
    return _gated_out(a, p, ctx)


def sparse(x, w_norm, p, sizes, forced=None):
    """``Mixer(N(x))`` of a sparse layer on ``x`` [s, h], a block of rows
    at a time: ``(y, own attended [s, kv, nb], scores [s, kv, nb])``."""
    s = x.shape[0]
    a, q, k, v = sparse_inputs(x, w_norm, p, sizes=sizes)
    R = min(BLOCK_ROWS, s)
    pad = -s % R
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    pos = jnp.minimum(jnp.arange(s + pad), s - 1)
    fp = None if forced is None else jnp.pad(
        jnp.asarray(forced), ((0, pad), (0, 0), (0, 0)))
    ctx, own, scores = [], [], []
    for r0 in range(0, s + pad, R):
        c, o, sc = sparse_rows(qp[r0:r0 + R], pos[r0:r0 + R], k, v,
                               None if fp is None else fp[r0:r0 + R],
                               sizes=sizes)
        ctx.append(c)
        own.append(np.asarray(o))
        scores.append(np.asarray(sc))
    ctx = jnp.concatenate(ctx)[:s]
    return (sparse_output(a, p, ctx, sizes=sizes),
            np.concatenate(own)[:s], np.concatenate(scores)[:s])


def tie_distance(scores, theirs, pos, sizes: dict):
    """How far from a tie the choices ``theirs`` (bool ``[r, kv, nb]``) are
    under the reference's block scores ``[r, kv, nb]`` for queries at
    ``pos``: the least RELATIVE move (theirs up by ``1 + d``, the
    passed-over down by ``1 - d``) under which the free picks, the chosen
    blocks beside the forced ones, are the best-scoring; 0 where they are
    as it is. Relative, because a softmax over ~1,000 compressed keys summed
    over a group scores ~0.01-0.02 a block. ``[r, kv]``."""
    scores = np.asarray(scores, np.float64)
    live, forced = live_and_forced(np.asarray(pos), scores.shape[-1], sizes)
    free = (live & ~forced)[:, None, :]
    lo = np.where(theirs & free, scores, np.inf).min(-1)
    hi = np.where(~theirs & free, scores, -np.inf).max(-1)
    with np.errstate(divide="ignore", invalid="ignore"):
        need = np.where(np.isfinite(lo) & np.isfinite(hi),
                        (hi - lo) / (hi + lo), 0.0)
    return np.maximum(need, 0.0)


# -- the stack -------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps",))
def mlp(x, w_norm, p, eps):
    with jax.default_matmul_precision("highest"):
        m = _rms(x, w_norm, eps)
        y = jnp.zeros_like(x)
        for c0 in range(0, p["gate"].shape[1], BLOCK_COLUMNS):
            cols = slice(c0, c0 + BLOCK_COLUMNS)
            gate, up = (p[w][:, cols].astype(jnp.float32)
                        for w in ("gate", "up"))
            y = y + (jax.nn.silu(m @ gate) * (m @ up)) \
                @ p["down"][cols].astype(jnp.float32)
        return y


@functools.partial(jax.jit, static_argnames=("eps", "divisor"))
def head(x, w, table, eps, divisor):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, w, eps) / divisor
        return jnp.concatenate(
            [x @ table[:, c0:c0 + 4 * BLOCK_COLUMNS].astype(jnp.float32)
             for c0 in range(0, table.shape[1], 4 * BLOCK_COLUMNS)], axis=-1)


def forward(params, ids, sizes: dict, mixer_types, *, rows=None,
            forced=None, selection: bool = False):
    """Logits in float32 for ONE sequence's token ids ``[s]``: ``[s, V]``,
    or with ``rows`` ``[r]`` only those positions. ``mixer_types``: the kind
    of each layer of ``params``. ``forced`` (bool ``[sparse layers, s, kv,
    blocks]``): the blocks each sparse layer attends where it is sparse.
    With ``selection`` also ``(own attended blocks, block scores)``, each
    ``[sparse layers, s, kv, blocks]``."""
    st = static(sizes)
    eps = float(sizes["rms_norm_eps"])
    r = float(sizes["scale_depth"]) / float(sizes["mup_denominator"]) ** 0.5
    x = float(sizes["scale_emb"]) * jnp.take(
        params["embed"], jnp.asarray(ids), axis=0).astype(jnp.float32)
    owns, scores = [], []
    for i, kind in enumerate(mixer_types):
        p = params[f"layer_{i}"]
        if kind == LIGHTNING:
            y = lightning(x, p["attn_norm"], p["attn"], sizes=st)
        else:
            y, own, sc = sparse(
                x, p["attn_norm"], p["attn"], st,
                None if forced is None else forced[len(owns)])
            owns.append(own)
            scores.append(sc)
        x = x + r * y
        x = x + r * mlp(x, p["mlp_norm"], p["mlp"], eps=eps)
    if rows is not None:
        x = jnp.take(x, jnp.asarray(rows), axis=0)
    logits = head(x, params["norm_f"], params["head"], eps=eps,
                  divisor=float(sizes["hidden_size"])
                  / float(sizes["dim_model_base"]))
    if selection:
        return logits, np.stack(owns), np.stack(scores)
    return logits
