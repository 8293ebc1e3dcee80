"""A dropless expert layer that is told which experts it holds.

The capacity-based layer of :mod:`apex_tpu.transformer.moe` (top-1/top-2,
one-hot ``[t, E, C]`` dispatch, dropped tokens) cannot express top-8 of
256. This one routes every token over ALL of the router's slots by the
rule its model names (``cfg.routing``, below), keeps the assignments whose
expert lies in ``[first_expert, first_expert + n_local)``, lays them out by
expert (``ops.grouped_matmul.tile_layout``), runs gate/up and down as two
grouped matmuls, gathers each token's rows back weighted, and adds what
every chip computes alike: the shared expert where the layer has one
(times ``sigmoid(x w)`` where its sub-tree holds an ``out_gate`` ``w`` ``[h,
1]``), and for the zero-compute slots a token chose (slots past
``n_routed_experts``: identity experts that hold no weights) the sum of
their weights times the token's own input. No token is dropped, whatever
the load.

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
gathers as well (the layout is a known permutation with holes, so nothing
is scattered), and the static row bound stays the worst case, ``t x min(k,
n_local)`` rows: nothing is dropped in a step whose tokens all choose
experts held here.

**The bound is the worst case, the traffic is the load.** A step fills
``tiles_used`` of the tiles (a quarter of them where a share holds a
quarter of the experts). A call of :data:`TRAIN_ASSIGNMENTS` (token,
choice) pairs or more, the size from which the tiles are the large ones
(:func:`_block_m`), moves its rows through ``ops.moe_rows``: the dispatch
and the combine's cotangent as ``sorted_rows`` (one program a USED tile; the
cotangent takes each row's weight and hands back its float32 dot with ``ys``
for the router in the same pass), the combine and the dispatch's cotangent
as ``token_rows`` (a row fetched only where its expert is held, float32 sums
in ascending choice, one cast); the two ``custom_vjp`` s :func:`_to_tiles`
and :func:`_from_tiles` pair them, so nothing is differentiated through a
kernel, and the ``[t, k, h]`` array is never built. A smaller call (a
prompt's, a decode step's) keeps ``jnp.take`` over all rows of the buffers
(:func:`_take_rows`, :func:`_combine_taken`): the serve programs move their
rows, and find where they go, as they did. The call's size chooses; no
option does. ``stats["rows_moved"]`` is ``tiles_used x block_m`` on the
first path, the padded buffer's rows on the second: a constant there, which
the serve engine's ``aux`` leaves out (``serve/latent.py:_aux``), so a serve
program is the program it was.

No operation mixes tokens: a token's output row depends on its own input
alone (its position among an expert's rows changes which tile row computes
it, not what is computed), which the serve engine's bit-exact replay rests
on.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.models.deepseek import gated_mlp
from apex_tpu.monitor import profile as _prof
from apex_tpu.ops import grouped_matmul as gmm
from apex_tpu.ops import moe_rows

#: rows of a tile of the grouped matmul: a decode step brings a handful of
#: rows an expert (weight-streaming-bound whatever the tile: 16 / 32 / 64
#: read 1.39 / 1.35 / 1.34 ms on the chip), a prompt some dozens, a training
#: step's 16,384 tokens some thousands (compute-bound; ``_block_m``)
BLOCK_M_DECODE, BLOCK_M_PREFILL, BLOCK_M_TRAIN = 32, 128, 256
#: (token, choice) pairs from which a call is a training step's and not a
#: prompt's: its tiles are the large ones and its rows move through
#: ``ops.moe_rows`` (module doc, "Training")
TRAIN_ASSIGNMENTS = 65536


def _block_m(assignments: int) -> int:
    """Rows of a tile for a call of ``assignments`` (token, choice) pairs:
    a decode step's, a prompt's, or a training step's."""
    if assignments <= 4096:
        return BLOCK_M_DECODE
    return BLOCK_M_PREFILL if assignments < TRAIN_ASSIGNMENTS \
        else BLOCK_M_TRAIN


def _moves_live_rows(t: int, k: int, h: int, dtype) -> bool:
    """Whether a call's rows move through ``ops.moe_rows`` (only the rows
    of the used tiles) and not through ``jnp.take`` (every row of the
    worst-case buffers): a training step's do, where the rows can."""
    return t * k >= TRAIN_ASSIGNMENTS and moe_rows.fits(h, dtype) \
        and t % moe_rows.TOKEN_BLOCK == 0


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


# The same two movements over live rows only (``ops.moe_rows``). ``src``
# [rows_padded]: the token a padded row holds; ``idx`` [t, k]: the padded row
# that holds a token's choice, -1 where no row does; ``used`` []: tiles_used.

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _to_tiles(x, src, idx, used, block_m, interpret):
    """``x[src]`` for the rows of the used tiles; the cotangent of ``x`` is
    the sum of a token's rows."""
    return moe_rows.sorted_rows(x, src, used, block_m=block_m,
                                interpret=interpret)


def _to_tiles_fwd(x, src, idx, used, block_m, interpret):
    return _to_tiles(x, src, idx, used, block_m, interpret), (idx, used)


def _to_tiles_bwd(block_m, interpret, res, dy):
    idx, used = res
    dx = moe_rows.token_rows(dy, idx, (idx >= 0).astype(jnp.float32), used,
                             block_m=block_m, interpret=interpret)
    return dx, None, None, None


_to_tiles.defvjp(_to_tiles_fwd, _to_tiles_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _from_tiles(ys, wm, src, idx, used, block_m, out_dtype, interpret):
    """``sum_c wm[t, c] * ys[idx[t, c]]`` in ``out_dtype`` (float32 sums);
    ``wm`` is 0 where ``idx`` is -1."""
    return moe_rows.token_rows(ys, idx, wm, used, block_m=block_m,
                               out_dtype=out_dtype, interpret=interpret)


def _from_tiles_fwd(ys, wm, src, idx, used, block_m, out_dtype, interpret):
    y = _from_tiles(ys, wm, src, idx, used, block_m, out_dtype, interpret)
    return y, (ys, wm, src, idx, used)


def _from_tiles_bwd(block_m, out_dtype, interpret, res, dy):
    ys, wm, src, idx, used = res
    n = src.shape[0]
    # each row's weight, and on the way each row's <dy, ys> for the router
    scale = jnp.zeros((n,), jnp.float32).at[
        jnp.where(idx >= 0, idx, n).reshape(-1)].set(wm.reshape(-1),
                                                     mode="drop")
    d_ys, dot = moe_rows.sorted_rows(
        dy.astype(ys.dtype), src, used, block_m=block_m, scale=scale,
        dot_with=ys, interpret=interpret)
    dwm = jnp.where(idx >= 0, jnp.take(dot, jnp.maximum(idx, 0)), 0.0)
    return d_ys, dwm, None, None, None


_from_tiles.defvjp(_from_tiles_fwd, _from_tiles_bwd)


def _combine_taken(ys, w, dest, taken, here):
    """``sum_c here * w[t, c] * ys[taken[t, c]]`` in float32 through
    ``jnp.take`` over all ``[t, k]`` rows."""
    t, k = here.shape
    rows_padded = ys.shape[0]
    # the assignment each padded row holds, for the way back
    held = jnp.full((rows_padded,), t * k, jnp.int32).at[dest].set(
        jnp.arange(t * k, dtype=jnp.int32), mode="drop")
    rows = _take_rows(ys, taken, jnp.minimum(held, t * k - 1)[:, None],
                      (held < t * k)[:, None]).reshape(t, k, ys.shape[1])
    # an absent expert's row index points at a row nobody wrote
    rows = jnp.where(here[:, :, None], rows.astype(jnp.float32), 0.0)
    return jnp.einsum("tk,tkh->th", jnp.where(here, w, 0.0), rows)


def expert_layer(cfg, p, x, *, active=None, impl: str = "kernel",
                 interpret: Optional[bool] = None):
    """This share's part of the expert layer for rows ``x`` ``[t, h]``.

    ``p``: a layer's ``moe`` sub-tree (``models.deepseek``,
    ``models.longcat``; the shared expert is there or not). ``active``
    ``[t]`` bool masks rows that carry no token (a decode batch's empty
    slots): they are routed nowhere and counted nowhere. Returns ``(y [t,
    h], stats)`` with ``stats`` = ``{"idx": chosen slots [t, k],
    "assignments_local": [], "expert_load_max": [], "experts_touched": [],
    "rows_moved": []}`` (int32 scalars over the active rows: what this share
    was handed, its fullest expert's rows, how many of its experts got any,
    and the rows of the tile layout the row movement touched) and, where
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
            starts, tile_group, used = gmm.tile_layout(counts, bm, max_rows)
            live = _moves_live_rows(t, k, h, x.dtype)
            # an assignment's row: its group's first + its rank in the group
            ranks = jnp.cumsum(onehot, axis=0)                   # 1-based
            if live:
                # picked out of the nl columns by the one-hot, a sum over
                # lanes: XLA's gather takes t*k indices one by one
                at = ((ranks - 1 + starts[None, :]) * onehot).sum(1)
            else:
                own = jnp.minimum(group, nl - 1)
                at = starts[own] + jnp.take_along_axis(
                    ranks, own[:, None], axis=1)[:, 0] - 1
            dest = jnp.where(group < nl, at, rows_padded)        # [t*k]
            # the token each padded row holds (padding rows: token 0; their
            # results are never read)
            src = jnp.zeros((rows_padded,), jnp.int32).at[dest].set(
                jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
            if live:
                row_of = jnp.where(here, dest.reshape(t, k), -1)
                xs = _to_tiles(x, src, row_of, used, bm, interpret)
            else:
                taken = jnp.minimum(dest, rows_padded - 1)       # [t*k]
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
            if live:
                # cast once: here, unless more is added to the sum below
                more = "shared" in p or cfg.zero_expert_num
                y = _from_tiles(ys, jnp.where(here, w, 0.0), src, row_of,
                                used, bm,
                                jnp.dtype(jnp.float32 if more else x.dtype),
                                interpret)
            else:
                y = _combine_taken(ys, w, dest, taken, here)
        stats = {"idx": idx, "assignments_local": counts.sum(),
                 "expert_load_max": counts.max(),
                 "experts_touched": (counts > 0).sum(),
                 "rows_moved": used * bm if live
                 else jnp.int32(rows_padded)}
        if "shared" in p:
            with _prof.scope("moe_shared"):
                sh = p["shared"]
                s = gated_mlp(x, sh["gate"], sh["up"],
                              sh["down"]).astype(jnp.float32)
                if "out_gate" in sh:    # Qwen3-Next: a sigmoid gate a token
                    s = s * jax.nn.sigmoid(
                        jnp.dot(x, sh["out_gate"]).astype(jnp.float32))
                y = y + s
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
