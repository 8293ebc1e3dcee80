"""A dropless expert layer that is told which experts it holds.

The capacity-based layer of :mod:`apex_tpu.transformer.moe` (top-1/top-2,
one-hot ``[t, E, C]`` dispatch, dropped tokens) cannot express top-8 of
256. This one routes every token over ALL of the router's slots by the
rule its model names (``cfg.routing``, below), keeps the assignments whose
expert lies in ``[first_expert, first_expert + n_local)``, lays them out by
expert (``ops.grouped_matmul.tile_layout``), runs gate/up and down as two
grouped matmuls, gathers each token's rows back weighted, and adds what
every chip computes alike: the shared expert where the layer has one, and
for the zero-compute slots a token chose (slots past ``n_routed_experts``:
identity experts that hold no weights) the sum of their weights times the
token's own input. No token is dropped, whatever the load.

With ``n_local == n_routed_experts`` that is the whole layer. With fewer
it is one chip's part of an expert-parallel layer: what the absent experts
would add is NOT here (no exchange, and nothing that stands in for the
other chips); summed over the shares, with the shared expert and the
identity part (which every chip computes alike for the tokens it holds)
counted once, the parts are the whole layer (``tests/test_deepseek.py``,
``tests/test_longcat.py``).

What a model's description has to answer: ``routing`` (a key of
:data:`ROUTING`) with the sizes that rule reads, ``n_routed_experts``,
``zero_expert_num``, ``first_expert``, ``local_experts``.

Routing (float32, as published), three rules:

- ``sigmoid_group_limited`` (DeepSeek-V3): ``sc = sigmoid(x W_g)``; the
  correction bias moves the CHOICE only (``sc + b``); a group's score is
  the sum of its two best corrected scores, the best ``topk_group`` groups
  stay, the others' scores become 0; the top ``k`` of what is left are
  chosen; the weights are the UNcorrected scores of the chosen, normalised
  to sum to ``routed_scaling_factor``.
- ``softmax_topk`` (LongCat-Flash): ``p = softmax(x W_r)`` over all
  ``n_routed_experts + zero_expert_num`` slots; the correction bias moves
  the choice only; the top ``moe_topk`` of ``p + b``, no groups; the
  weights are ``p`` of the chosen times ``routed_scaling_factor``, NOT
  renormalised.
- ``softmax_topk_renorm`` (Mellum 2, ``norm_topk_prob: true``): ``p =
  softmax(x W_r)`` over all ``n_routed_experts``; no bias, no groups, no
  scaling factor; the top ``num_experts_per_tok`` of ``p``; the weights
  are ``p`` of the chosen divided by their sum.

**Training.** The layer differentiates: to ``x`` (through the gather into
the tile layout, the router's logits and the combine), to the experts
(``ops.grouped_matmul``'s backward) and to the router (through the weights
``w``; the choice ``idx`` carries none). The two gathers' cotangents are
gathers as well (:func:`_take_rows`: the layout is a known permutation with
holes, so nothing is scattered), and the static row bound stays the worst
case, ``t x min(k, n_local)`` rows: nothing is dropped in a step whose
tokens all choose experts held here.

No operation mixes tokens: a token's output row depends on its own input
alone (its position among an expert's rows changes which tile row computes
it, not what is computed), which the serve engine's bit-exact replay rests
on.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.models.deepseek import gated_mlp
from apex_tpu.monitor import profile as _prof
from apex_tpu.ops import grouped_matmul as gmm

#: rows of a tile of the grouped matmul: a decode step brings a handful of
#: rows an expert (weight-streaming-bound whatever the tile: 16 / 32 / 64
#: read 1.39 / 1.35 / 1.34 ms on the chip), a prompt some dozens, a training
#: step's 16,384 tokens some thousands (compute-bound; ``_block_m``)
BLOCK_M_DECODE, BLOCK_M_PREFILL, BLOCK_M_TRAIN = 32, 128, 256


def _block_m(assignments: int) -> int:
    """Rows of a tile for a call of ``assignments`` (token, choice) pairs:
    a decode step's, a prompt's, or a training step's."""
    if assignments <= 4096:
        return BLOCK_M_DECODE
    return BLOCK_M_PREFILL if assignments < 65536 else BLOCK_M_TRAIN


def _router_logits(router, x):
    return jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def _route_sigmoid_group_limited(cfg, router, bias, x):
    t = x.shape[0]
    E, G = cfg.n_routed_experts, cfg.n_group
    sc = jax.nn.sigmoid(_router_logits(router, x))
    cor = sc + bias.astype(jnp.float32)
    best2 = jax.lax.top_k(cor.reshape(t, G, E // G), 2)[0].sum(-1)  # [t, G]
    groups = jax.lax.top_k(best2, cfg.topk_group)[1]                # [t, kg]
    keep = (groups[:, :, None] == jnp.arange(G)[None, None, :]).any(1)
    masked = jnp.where(jnp.repeat(keep, E // G, axis=1), cor, 0.0)
    idx = jax.lax.top_k(masked, cfg.num_experts_per_tok)[1]
    w = jnp.take_along_axis(sc, idx, axis=1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * cfg.routed_scaling_factor


def _route_softmax_topk(cfg, router, bias, x):
    p = jax.nn.softmax(_router_logits(router, x), axis=-1)
    idx = jax.lax.top_k(p + bias.astype(jnp.float32), cfg.moe_topk)[1]
    w = jnp.take_along_axis(p, idx, axis=1)
    return idx.astype(jnp.int32), w * cfg.routed_scaling_factor


def _route_softmax_topk_renorm(cfg, router, bias, x):
    del bias                                    # the rule has none
    p = jax.nn.softmax(_router_logits(router, x), axis=-1)
    idx = jax.lax.top_k(jax.lax.stop_gradient(p),
                        cfg.num_experts_per_tok)[1]
    w = jnp.take_along_axis(p, idx, axis=1)
    return idx.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True)


#: the rules a description can name as its ``routing``
ROUTING = {"sigmoid_group_limited": _route_sigmoid_group_limited,
           "softmax_topk": _route_softmax_topk,
           "softmax_topk_renorm": _route_softmax_topk_renorm}


def route(cfg, router, bias, x):
    """``(idx [t, k] int32, w [t, k] f32)`` over all of the router's slots,
    by the rule ``cfg.routing`` names."""
    return ROUTING[cfg.routing](cfg, router, bias, x)


@jax.custom_vjp
def _take_rows(x, idx, back_idx, back_ok):
    """``x[idx]`` (rows) whose cotangent is GATHERED: row ``j`` of ``x``
    gets the sum over ``c`` of ``dy[back_idx[j, c]]`` where ``back_ok[j,
    c]``. The caller knows where each row of ``x`` went (the tile layout is
    a permutation with holes), which a scatter-add would have to find out
    row by row. Not differentiated, it is ``jnp.take``."""
    del back_idx, back_ok
    return jnp.take(x, idx, axis=0)


def _take_rows_fwd(x, idx, back_idx, back_ok):
    return jnp.take(x, idx, axis=0), (back_idx, back_ok)


def _take_rows_bwd(res, dy):
    back_idx, back_ok = res
    g = jnp.take(dy, back_idx.reshape(-1), axis=0).reshape(
        back_idx.shape + dy.shape[1:])
    g = jnp.where(back_ok[..., None], g.astype(jnp.float32), 0.0).sum(1)
    return g.astype(dy.dtype), None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def expert_layer(cfg, p, x, *, active=None, impl: str = "kernel",
                 interpret: Optional[bool] = None):
    """This share's part of the expert layer for rows ``x`` ``[t, h]``.

    ``p``: a layer's ``moe`` sub-tree (``models.deepseek``,
    ``models.longcat``; the shared expert is there or not). ``active``
    ``[t]`` bool masks rows that carry no token (a decode batch's empty
    slots): they are routed nowhere and counted nowhere. Returns ``(y [t,
    h], stats)`` with ``stats`` = ``{"idx": chosen slots [t, k],
    "assignments_local": [], "expert_load_max": [], "experts_touched": []}``
    (int32 scalars over the active rows: what this share was handed, its
    fullest expert's rows, and how many of its experts got any) and, where
    the router has zero-compute slots, ``"assignments_zero"`` (how many of
    the rows' choices fell on them) and ``"real_experts_per_token_max"``
    (the most real experts, held here or not, that one row chose).
    ``impl``: the grouped matmul's (``ops.grouped_matmul.IMPLS``)."""
    t, h = x.shape
    nl = cfg.local_experts
    with _prof.scope("moe"):
        with _prof.scope("moe_route"):
            idx, w = route(cfg, p["router"], p.get("bias"), x)
            k = idx.shape[1]
            bm = _block_m(t * k)
            max_rows = t * min(k, nl)
            rows_padded = gmm.num_tiles(nl, bm, max_rows) * bm
            local = idx - cfg.first_expert
            here = (local >= 0) & (local < nl)
            if active is not None:
                here = here & active[:, None]
            group = jnp.where(here, local, nl).reshape(-1)       # [t*k]
            onehot = (group[:, None] == jnp.arange(nl)[None, :]
                      ).astype(jnp.int32)                        # [t*k, nl]
            counts = onehot.sum(0)
            rank = jnp.take_along_axis(
                jnp.cumsum(onehot, axis=0), jnp.minimum(group, nl - 1)[:, None],
                axis=1)[:, 0] - 1
            starts, tile_group, used = gmm.tile_layout(counts, bm, max_rows)
            dest = jnp.where(group < nl,
                             starts[jnp.minimum(group, nl - 1)] + rank,
                             rows_padded)                        # [t*k]
            # the token each padded row holds (padding rows: token 0; their
            # results are never read)
            src = jnp.zeros((rows_padded,), jnp.int32).at[dest].set(
                jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
            taken = jnp.minimum(dest, rows_padded - 1)           # [t*k]
            xs = _take_rows(x, src, taken.reshape(t, k), here)
        with _prof.scope("moe_experts"):
            ex = p["experts"]
            kw = dict(block_m=bm, impl=impl, interpret=interpret)
            gu = gmm.grouped_matmul(xs, ex["gate_up"], tile_group, used,
                                    **kw).astype(jnp.float32)
            im = gu.shape[1] // 2
            act = (jax.nn.silu(gu[:, :im]) * gu[:, im:]).astype(x.dtype)
            ys = gmm.grouped_matmul(act, ex["down"], tile_group, used, **kw)
        with _prof.scope("moe_combine"):
            # the assignment each padded row holds, for the way back
            held = jnp.full((rows_padded,), t * k, jnp.int32).at[dest].set(
                jnp.arange(t * k, dtype=jnp.int32), mode="drop")
            rows = _take_rows(ys, taken, jnp.minimum(held, t * k - 1)[:, None],
                              (held < t * k)[:, None]).reshape(t, k, h)
            # an absent expert's row index points at a row nobody wrote
            rows = jnp.where(here[:, :, None], rows.astype(jnp.float32), 0.0)
            y = jnp.einsum("tk,tkh->th", jnp.where(here, w, 0.0), rows)
        stats = {"idx": idx, "assignments_local": counts.sum(),
                 "expert_load_max": counts.max(),
                 "experts_touched": (counts > 0).sum()}
        if "shared" in p:
            with _prof.scope("moe_shared"):
                sh = p["shared"]
                y = y + gated_mlp(x, sh["gate"], sh["up"],
                                  sh["down"]).astype(jnp.float32)
        if cfg.zero_expert_num:
            with _prof.scope("moe_zero"):
                zero = idx >= cfg.n_routed_experts
                y = y + jnp.where(zero, w, 0.0).sum(-1, keepdims=True) \
                    * x.astype(jnp.float32)
                on = True if active is None else active[:, None]
                stats["assignments_zero"] = (zero & on).sum()
                stats["real_experts_per_token_max"] = \
                    (~zero & on).sum(-1).max()
    return y.astype(x.dtype), stats
