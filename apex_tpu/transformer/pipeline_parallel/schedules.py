"""SPMD pipeline schedules.

The reference only ships the group topology (SURVEY §2.3: "no schedule
engine"); Megatron's schedules drive per-rank send/recv with 1F1B
bookkeeping. The TPU-native formulation: every stage runs the SAME scanned
program (SPMD), activations move with one ``ppermute`` per tick, microbatch
injection/collection are masked by stage index, and the backward schedule
falls out of ``jax.grad`` of the scan — XLA reverses the pipeline
automatically. Reverse-mode through the scan stashes one stage-input
residual per tick (GPipe's memory profile, linear in microbatch count);
``forward_backward_pipelining_1f1b`` below restores 1F1B's O(P·mb)
bound with explicit in-scan VJP (measured table: docs/perf.md).

``pipeline_apply(stage_fn, stage_params, x, n_microbatches)`` must run
inside ``shard_map`` over the ``pipeline`` mesh axis, with
``stage_params`` already per-stage (each rank holds its stage's weights)
and the stage activation shape uniform across stages (standard for
transformer blocks).
"""

from __future__ import annotations

import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from apex_tpu._compat import axis_size as _axis_size
from apex_tpu.monitor import hooks as _mon
from apex_tpu.monitor import profile as _prof
from apex_tpu.transformer import parallel_state as ps
from apex_tpu.transformer.microbatches import resolve_num_microbatches
from apex_tpu.transformer.pipeline_parallel.backward_split import (
    dgrad_vjp, normalize_wgrad_stash, wgrad, with_remat_policy)
from apex_tpu.transformer.pipeline_parallel.p2p import (
    ring_shift, send_backward_recv_backward, send_forward_recv_forward)
from apex_tpu.utils.remat import resolve_remat_policy


def _scoped_tick(name: str, body: Callable) -> Callable:
    """Wrap a scan tick/flush body in a profile scope
    (``monitor.profile``): every equation the body traces is charged to
    ``name`` in the per-module attribution table. Metadata-only — the
    scan jaxpr is byte-identical with or without the tag."""
    def wrapped(carry, t):
        with _prof.scope(name):
            return body(carry, t)
    return wrapped


def _checkpointed(stage_fn: Callable, remat: bool, remat_policy):
    """``jax.checkpoint`` wrap for the differentiable schedules:
    ``remat=True`` recomputes in backward under the named/callable
    residual policy from ``apex_tpu.utils.remat`` (``None`` = full
    recompute, the historical behavior)."""
    if not remat:
        if remat_policy is not None:
            raise ValueError(
                "remat_policy is a jax.checkpoint residual policy and "
                "has no effect with remat=False; drop the policy or "
                "enable remat")
        return stage_fn
    policy = remat_policy if (remat_policy is None or callable(remat_policy)) \
        else resolve_remat_policy(remat_policy)
    return jax.checkpoint(stage_fn, policy=policy)


def pipeline_apply(stage_fn: Callable, stage_params, x,
                   n_microbatches: int,
                   axis_name: str = ps.PIPELINE_AXIS,
                   remat: bool = True, remat_policy=None):
    """Run microbatched GPipe fill-drain over the pipeline axis.

    ``x``: [n_microbatches, mb, ...] input (consumed by stage 0).
    ``stage_fn(params, h) -> h`` is one stage; output shape == input shape.
    Returns [n_microbatches, mb, ...] final-stage outputs (valid on the
    last stage; replicate/psum externally if every stage needs them).
    ``n_microbatches`` may be an int or a ``NumMicroBatchesCalculator``.
    ``remat_policy``: residual policy name/callable for the ``remat``
    checkpoint (``apex_tpu.utils.remat``; e.g. ``"dots"`` saves matmul
    outputs instead of recomputing them in backward).
    """
    n_microbatches = resolve_num_microbatches(n_microbatches)
    n_stages = _axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    total_ticks = n_microbatches + n_stages - 1
    _mon.pipeline_schedule("fill_drain", n_stages, n_microbatches,
                           total_ticks)
    fn = _checkpointed(stage_fn, remat, remat_policy)

    h_shape = x.shape[1:]
    init_held = jnp.zeros(h_shape, x.dtype)
    init_out = jnp.zeros((n_microbatches,) + h_shape, x.dtype)

    # NB: no per-tick marks here — this scan is differentiated through
    # (fwd/bwd schedules take value_and_grad of it) and partial-eval
    # silently drops debug callbacks from differentiated regions, which
    # would make tick telemetry appear in inference and vanish in
    # training. The 1F1B schedules below build their backward manually
    # in a non-differentiated scan, so THEY carry the tick marks; this
    # schedule records its geometry/bubble estimate only.
    def tick(carry, t):
        held, outputs = carry
        inject_idx = jnp.clip(t, 0, n_microbatches - 1)
        inject = jax.lax.dynamic_index_in_dim(x, inject_idx, keepdims=False)
        use_inject = (rank == 0) & (t < n_microbatches)
        inp = jnp.where(use_inject, inject, held)
        out = fn(stage_params, inp)
        # collect on the last stage: tick t carries microbatch t-(n_stages-1)
        mb = t - (n_stages - 1)
        valid = (rank == n_stages - 1) & (mb >= 0)
        mb_c = jnp.clip(mb, 0, n_microbatches - 1)
        updated = jax.lax.dynamic_update_index_in_dim(outputs, out, mb_c, 0)
        outputs = jnp.where(valid, updated, outputs)
        # move activations one stage forward for the next tick
        held_next = send_forward_recv_forward(out, axis_name)
        return (held_next, outputs), None

    (_, outputs), _ = jax.lax.scan(_scoped_tick("pp_tick", tick),
                                   (init_held, init_out),
                                   jnp.arange(total_ticks))
    return outputs


def forward_backward_no_pipelining(loss_fn: Callable, params, batch,
                                   n_microbatches: int = 1):
    """Megatron's no-pipelining path: grad-accumulate over microbatches.

    ``loss_fn(params, microbatch) -> scalar``. Returns (mean loss, grads).
    """
    n_microbatches = resolve_num_microbatches(n_microbatches)

    def scan_body(acc, mb):
        loss, g = jax.value_and_grad(loss_fn)(params, mb)
        return jax.tree.map(lambda a, b: a + b, acc, (loss, g)), None

    zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
    (loss_sum, grad_sum), _ = jax.lax.scan(scan_body, zero, batch)
    inv = 1.0 / n_microbatches
    return loss_sum * inv, jax.tree.map(lambda g: g * inv, grad_sum)


def forward_backward_pipelining_without_interleaving(
        stage_fn: Callable, loss_head: Callable, stage_params, x,
        n_microbatches: int, axis_name: str = ps.PIPELINE_AXIS):
    """Fill-drain pipeline + loss, returning (loss, stage-param grads).

    ``loss_head(outputs) -> scalar`` applies on the last stage's collected
    outputs (masked to zero elsewhere, so a final ``psum`` of the loss and
    grads is exact). Runs inside shard_map over the pipeline axis.
    """
    n_microbatches = resolve_num_microbatches(n_microbatches)
    n_stages = _axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)

    def full(params):
        outs = pipeline_apply(stage_fn, params, x, n_microbatches, axis_name)
        loss = loss_head(outs)
        return jnp.where(rank == n_stages - 1, loss, 0.0)

    loss, grads = jax.value_and_grad(full)(stage_params)
    return loss, grads



def _mb_slicer(inputs):
    """Per-microbatch slicer over [n_microbatches, ...]-leaved ``inputs``."""
    def slice_mb(m):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, m, keepdims=False),
            inputs)
    return slice_mb


def _probe_h(embed_fn, embed_params, slice_mb):
    probe = jax.eval_shape(lambda p: embed_fn(p, slice_mb(0)), embed_params)
    return probe.shape, probe.dtype


# debug-mode axis-usage probe (the embed_fn/loss_fn collective contract)
_AXIS_PROBE_ENV = "APEX_TPU_PIPELINE_AXIS_PROBE"


def _axis_probe_enabled(flag: Optional[bool]) -> bool:
    if flag is not None:
        return bool(flag)
    return os.environ.get(_AXIS_PROBE_ENV, "0") == "1"


def _probe_no_pipeline_collectives(tag: str, fn, args, axis_name: str):
    """Debug probe behind ``debug_axis_probe=True`` (or env
    ``APEX_TPU_PIPELINE_AXIS_PROBE=1``): abstractly trace ``fn`` (an
    eval_shape-cost trace — no compile, no execution) and fail fast if
    it carries collectives over the *pipeline* axis. The 1F1B tick
    cores run embed_fn/loss_fn under per-rank ``lax.cond`` branches, so
    a pipeline-axis collective inside them would be executed by only
    some pp ranks — a silent deadlock/corruption at runtime; this turns
    it into an immediate, named error at trace time. Group-local
    collectives (e.g. a VocabParallelEmbedding's tensor-axis psum) are
    fine and pass."""
    from apex_tpu.lint.jaxpr_checks import collective_axis_names
    jaxpr = jax.make_jaxpr(fn)(*args)
    used = collective_axis_names(jaxpr.jaxpr)
    if axis_name in used:
        raise ValueError(
            f"{tag} carries a collective over the pipeline axis "
            f"'{axis_name}' (axes seen: {sorted(used)}). The 1F1B "
            f"schedules run {tag} under lax.cond on a per-rank "
            "predicate, so only some pipeline ranks would execute the "
            "collective — a deadlock/corruption. Keep pipeline-axis "
            "reductions (loss/grad psum) OUTSIDE the schedule call; "
            "tensor-axis collectives inside embed/head are fine.")


def _head_seed(loss_fn, pred, head_params, out_b, in_b):
    """Loss + head grads + backward seed under ``lax.cond(pred)`` — ONLY
    the seeding rank pays for the head (its collectives are group-local
    over the tensor axis, so other pp rows skipping is sound). Shared by
    both 1F1B tick cores."""
    def head_branch(hp, h, inb):
        (loss, (dhp, dh)) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(hp, h, inb)
        return loss, dhp, dh.astype(h.dtype)

    def head_skip(hp, h, inb):
        return (jnp.zeros((), jnp.float32),
                jax.tree.map(jnp.zeros_like, hp),
                jnp.zeros_like(h))

    return jax.lax.cond(pred, head_branch, head_skip,
                        head_params, out_b, in_b)


def _embed_inject(embed_fn, pred, embed_params, in_mb, h_shape, h_dtype):
    """Injection embed under ``lax.cond(pred)``: only the rank that will
    actually consume the injection pays the embed compute (advisor r4 —
    previously every rank embedded every tick, nmb + 2(P-1) times per
    rank vs nmb total useful; measurable for large-vocab
    VocabParallelEmbedding). Sound for the same reason as the head/
    embed-pullback conds: embed collectives span the TENSOR axis within
    one pp row, and the predicate is uniform across that row, so pp
    rows that skip are not party to the collective."""
    def do(ep, mb):
        return embed_fn(ep, mb).astype(h_dtype)

    def skip(ep, mb):
        return jnp.zeros(h_shape, h_dtype)

    return jax.lax.cond(pred, do, skip, embed_params, in_mb)


def _embed_pullback(embed_fn, pred, embed_params, in_b, ct):
    """Embedding cotangent pullback under ``lax.cond(pred)`` (rank 0's
    input cotangent pulls back through ``embed_fn`` instead of falling
    off the pipeline edge). Shared by both 1F1B tick cores."""
    def embed_branch(ep, inb, c):
        _, pull = jax.vjp(lambda p: embed_fn(p, inb), ep)
        return pull(c)[0]

    def embed_skip(ep, inb, c):
        return jax.tree.map(jnp.zeros_like, ep)

    return jax.lax.cond(pred, embed_branch, embed_skip,
                        embed_params, in_b, ct)


def forward_backward_pipelining_1f1b(
        stage_fn: Callable, loss_mb: Callable, stage_params, x,
        n_microbatches: int, axis_name: str = ps.PIPELINE_AXIS,
        remat_policy=None):
    """1F1B pipeline: bounded activation memory, O(P·mb) not O(nmb·mb).

    The fill-drain schedule above differentiates *through* the scan, so
    reverse-mode stashes one stage-input residual per tick — peak
    activation memory grows linearly with ``n_microbatches`` (measured:
    `tests/test_transformer.py::test_pipeline_memory_discipline`). This
    schedule is the TPU-native restatement of Megatron 1F1B (the memory
    rationale behind ``apex/transformer/parallel_state.py:252-322``):
    forward and backward units run in the SAME scan, gradients accumulate
    in the carry, and the only cross-tick activation state is a circular
    stash of ``2P-1`` stage inputs per rank — constant in
    ``n_microbatches``.

    Tick ``i`` runs (SPMD, all ranks the same program):

    - forward unit ``m_f = i - rank`` (the fill-drain timeline): consume
      the held activation (or inject ``x[m_f]`` on rank 0), apply
      ``stage_fn``, stash the INPUT, ``ppermute`` the output forward.
    - backward unit ``m_b = i - 2(P-1) + rank`` (the time-reversed
      timeline, delayed so the last rank's backward of microbatch ``m``
      immediately follows its forward): pop the stashed input, replay
      ``stage_fn`` under ``jax.vjp`` (rematerialization — nothing but
      the input survives from the forward pass), seed the cotangent from
      ``loss_mb`` on the last rank or from the next stage's ``ppermute``
      otherwise, accumulate the parameter cotangent, send the input
      cotangent backward.

    The cotangent rank r emits at tick ``i`` is consumed by rank r-1 at
    tick ``i+1`` for the SAME microbatch (both sides compute
    ``m = i - 2(P-1) + r``), so one reverse ``ppermute`` per tick is the
    whole backward transport. Total ticks ``nmb + 2(P-1)`` vs fill-drain's
    ``2(nmb + P - 1)`` forward+backward ticks — same bubble fraction,
    same 2-forwards+1-backward compute per microbatch as remat fill-drain.

    ``loss_mb(out) -> scalar`` applies per microbatch on the last stage;
    the returned loss is the SUM over microbatches (divide inside
    ``loss_mb`` by ``n_microbatches`` for a mean). ``loss_mb`` runs
    under a last-rank-only ``lax.cond`` and therefore MUST NOT carry
    pipeline-axis collectives (tensor-axis ones are fine — see
    ``forward_backward_pipelining_1f1b_model`` for the full contract
    and the ``APEX_TPU_PIPELINE_AXIS_PROBE`` debug check). Returns
    ``(loss, grads)`` with the loss masked to the last rank — ``psum``
    both over the pipeline axis, exactly as with the fill-drain variant.

    This is the headless special case of
    ``forward_backward_pipelining_1f1b_model`` (identity injection from
    ``x``, no embed/head parameters) — one tick core serves both.
    """
    loss, grads = forward_backward_pipelining_1f1b_model(
        lambda _, x_mb: x_mb,                 # injection = x[m] directly
        stage_fn,
        lambda _, h, __: loss_mb(h),          # headless loss seed
        {"embed": {}, "stage": stage_params, "head": {}},
        x, n_microbatches, axis_name, remat_policy=remat_policy)
    return loss, grads["stage"]


def forward_backward_pipelining_1f1b_model(
        embed_fn: Callable, stage_fn: Callable, loss_fn: Callable,
        params, inputs, n_microbatches: int,
        axis_name: str = ps.PIPELINE_AXIS,
        debug_axis_probe: Optional[bool] = None,
        remat_policy=None):
    """1F1B for a FULL model: embed + stages + loss head, flat memory.

    **Contract — embed_fn/loss_fn must carry no pipeline-axis
    collectives.** Both run under ``lax.cond`` branches taken by a
    single pipeline rank (rank 0 for embed, the last rank for the loss
    head), so a collective over ``axis_name`` inside either would be
    entered by only part of the pipeline group: a deadlock on real
    meshes, silent corruption on others. Collectives over *other* axes
    (e.g. VocabParallelEmbedding's tensor-axis psum) are group-local to
    one pp row and are fine. Do pipeline-axis reductions (summing the
    returned loss/grads across ranks) OUTSIDE this call. Set
    ``debug_axis_probe=True`` (or env ``APEX_TPU_PIPELINE_AXIS_PROBE=1``)
    to verify the contract at trace time: an eval_shape-cost abstract
    trace of both functions raises a named error on violation.

    ``forward_backward_pipelining_1f1b`` above handles the stage stack
    only; a real model also needs gradients for the embedding (rank 0)
    and the loss head (last rank). This variant runs the same two-stream
    tick schedule with:

    - ``embed_fn(params['embed'], inputs_mb) -> h``: computes the
      injection for microbatch ``m``, under ``lax.cond`` so only rank 0
      pays for it (advisor r4 — see ``_embed_inject``; sound because
      embed collectives, e.g. VocabParallelEmbedding's tensor-axis
      psum, are group-local to one pp row and the predicate is uniform
      across that row; embed_fn must not carry pipeline-axis
      collectives, which nothing in the repo does).
    - ``loss_fn(params['head'], h_out, inputs_mb) -> scalar``: the loss
      head for one microbatch, run under ``lax.cond`` so ONLY the last
      pipeline rank pays for it (at tp>1 its collectives span the
      tensor axis within that pp row — group-local, so the other rows
      skipping the branch is sound). Its gradient seeds the backward.
    - embedding backward: rank 0's input cotangent, instead of being
      dropped off the pipeline edge, pulls back through ``embed_fn``
      (recomputed — ids index directly into ``inputs``, nothing extra
      is stashed).

    ``params``: dict with keys ``embed`` / ``stage`` / ``head``.
    ``inputs``: pytree with [n_microbatches, ...] leaves (e.g.
    ``(ids, labels)``) — sliced per unit for embed and loss.

    Returns ``(loss_sum, grads)`` where ``grads`` has the same dict
    structure; the loss and the embed/head grads live on their owning
    ranks (zero elsewhere) — ``psum`` them over the pipeline axis, as
    with ``PipelinedGPT.loss_and_grads``. ``loss_sum`` is the SUM of
    per-microbatch losses (divide inside ``loss_fn`` for a mean).
    Memory: the same 2P-1-slot activation stash as the plain 1F1B
    schedule — peak activations constant in ``n_microbatches``.
    """
    n_microbatches = resolve_num_microbatches(n_microbatches)
    stage_fn = with_remat_policy(stage_fn, remat_policy)
    n_stages = _axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    is_last = rank == n_stages - 1
    is_first = rank == 0
    delay = 2 * (n_stages - 1)
    total_ticks = n_microbatches + delay
    _mon.pipeline_schedule("1f1b", n_stages, n_microbatches, total_ticks)
    stash_slots = max(1, 2 * n_stages - 1)

    slice_mb = _mb_slicer(inputs)

    h_shape, h_dtype = _probe_h(embed_fn, params["embed"], slice_mb)

    if _axis_probe_enabled(debug_axis_probe):
        _probe_no_pipeline_collectives(
            "embed_fn", embed_fn, (params["embed"], slice_mb(0)),
            axis_name)
        _probe_no_pipeline_collectives(
            "loss_fn", loss_fn,
            (params["head"], jnp.zeros(h_shape, h_dtype), slice_mb(0)),
            axis_name)

    init = (
        jnp.zeros(h_shape, h_dtype),                      # held_f
        jnp.zeros(h_shape, h_dtype),                      # held_b
        jnp.zeros((stash_slots,) + h_shape, h_dtype),     # input stash
        jax.tree.map(jnp.zeros_like, params),             # grad accumulator
        jnp.zeros((), jnp.float32),                       # loss sum
    )

    def tick(carry, i):
        held_f, held_b, stash, grads, loss_sum = carry
        _mon.traced_tick("pipeline/1f1b/tick", i)

        # -- forward unit ------------------------------------------------
        m_f = i - rank
        valid_f = (m_f >= 0) & (m_f < n_microbatches)
        m_fc = jnp.clip(m_f, 0, n_microbatches - 1)
        use_inject = valid_f & is_first
        inject = _embed_inject(embed_fn, use_inject, params["embed"],
                               slice_mb(m_fc), h_shape, h_dtype)
        inp = jnp.where(use_inject, inject, held_f)
        out = stage_fn(params["stage"], inp)
        slot = m_fc % stash_slots
        cur = jax.lax.dynamic_index_in_dim(stash, slot, keepdims=False)
        stash = jax.lax.dynamic_update_index_in_dim(
            stash, jnp.where(valid_f, inp, cur), slot, 0)
        held_f = send_forward_recv_forward(out, axis_name)

        # -- backward unit ----------------------------------------------
        m_b = i - delay + rank
        valid_b = (m_b >= 0) & (m_b < n_microbatches)
        m_bc = jnp.clip(m_b, 0, n_microbatches - 1)
        in_b = slice_mb(m_bc)
        inp_b = jax.lax.dynamic_index_in_dim(
            stash, m_bc % stash_slots, keepdims=False)
        out_b, pull_stage = jax.vjp(stage_fn, params["stage"], inp_b)

        loss_val, dhead, seed = _head_seed(
            loss_fn, is_last & valid_b, params["head"], out_b, in_b)

        g_out = jnp.where(is_last, seed, held_b)
        dstage, dinp = pull_stage(g_out)

        dembed = _embed_pullback(
            embed_fn, is_first & valid_b, params["embed"], in_b,
            dinp.astype(h_dtype))

        grads = {
            "embed": jax.tree.map(
                lambda a, d: a + jnp.where(valid_b & is_first, d, 0),
                grads["embed"], dembed),
            "stage": jax.tree.map(
                lambda a, d: a + jnp.where(valid_b, d, 0),
                grads["stage"], dstage),
            "head": jax.tree.map(
                lambda a, d: a + jnp.where(valid_b, d, 0),
                grads["head"], dhead),
        }
        loss_sum = loss_sum + loss_val    # zero off the last rank
        held_b = send_backward_recv_backward(dinp, axis_name)
        # measured slot occupancy: the combined-VJP tick executes one
        # forward and one full backward (dgrad AND wgrad) per tick, so
        # the b/w slots share valid_b — the baseline the zero-bubble
        # schedule's table is compared against
        _mon.traced_tick_marks("pipeline/1f1b", i, rank,
                               f=valid_f, b=valid_b, w=valid_b)

        return (held_f, held_b, stash, grads, loss_sum), None

    (_, _, _, grads, loss_sum), _ = jax.lax.scan(
        _scoped_tick("pp_tick", tick), init, jnp.arange(total_ticks))
    return loss_sum, grads


def forward_backward_pipelining_1f1b_interleaved_model(
        embed_fn: Callable, stage_fn: Callable, loss_fn: Callable,
        params, inputs, n_microbatches: int, n_chunks: int,
        axis_name: str = ps.PIPELINE_AXIS,
        debug_axis_probe: Optional[bool] = None,
        remat_policy=None):
    """Interleaved (vpp) 1F1B: Megatron's production schedule — virtual
    chunks AND flat activation memory — as one SPMD scan.

    This closes the gap the staged-grads interleaved path
    (``microbatch_group_size``) leaves open: that path bounds memory by
    paying one extra (P-1)-tick bubble per group, while this schedule
    keeps the single warmup/cooldown bubble and a stash that is constant
    in ``n_microbatches``. It is the schedule the reference's vpp rank
    state exists to serve (``apex/transformer/parallel_state.py:252-322``
    tracks virtual ranks precisely so Megatron's interleaved 1F1B can
    place chunk ``c`` of rank ``r`` at global stage ``g = c*P + r``).

    Timeline (D = V*P global stages; B(m) = (m//P)*V*P):

    - forward of (microbatch m, global stage g) at tick
      ``t_f = B(m) + (m%P) + g`` — the same enumeration as
      ``pipeline_apply_interleaved`` (unit ``u = t - rank``);
    - backward of (m, g) at tick ``t_b = B(m) + (m%P) + 2(D-1) - g`` —
      the exact time-reversal, so on the last global stage the backward
      runs in the same tick as the forward (1F1B's defining property)
      and each cotangent is consumed exactly one tick after it is
      produced by the next-lower global stage.

    Per-rank backward inversion: with ``w = t - 2(D-1) + rank``,
    ``l = w mod P``, ``z = (w - l)/P`` (= qV - c), ``q = ceil(z/V)``:
    chunk ``c_b = q*V - z`` decreasing within each group (chunk V-1
    first), microbatch ``m_b = q*P + l``. Both transports are one
    wrapped ring ``ppermute`` per tick: forward rank P-1 -> 0 feeds the
    next chunk; backward rank 0 -> P-1 feeds the previous chunk (the
    wrapped value landing on the last global stage is overridden by the
    loss-head seed, and rank 0's chunk-0 cotangent pulls back through
    ``embed_fn`` instead of riding the wrap).

    Stash: ``[V, 2P+1]`` slots per rank (slot ``m mod (2P+1)`` of chunk
    ``c``) — at the worst stage (g=0) at most 2P chunk-c forwards fit in
    the ``2(D-1)``-tick forward->backward span, so 2P+1 slots can never
    collide; peak activation memory is O(V·P·mb), CONSTANT in
    ``n_microbatches`` (asserted by
    ``test_pipeline_interleaved_1f1b_memory_flat``).

    Same contracts as ``forward_backward_pipelining_1f1b_model`` —
    including **embed_fn/loss_fn must carry no pipeline-axis
    collectives** (they run under single-rank ``lax.cond`` branches;
    tensor-axis collectives are fine; ``debug_axis_probe=True`` or env
    ``APEX_TPU_PIPELINE_AXIS_PROBE=1`` trace-checks this): ``params`` =
    {embed, stage, head} with ``stage`` leaves stacked [n_chunks, ...];
    returns ``(loss_sum, grads)`` with embed/head grads on their owning
    ranks — psum over the pipeline axis. Requires
    ``n_microbatches % P == 0`` (the Megatron interleaving constraint).
    """
    n_microbatches = resolve_num_microbatches(n_microbatches)
    stage_fn = with_remat_policy(stage_fn, remat_policy)
    n_stages = _axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    V = n_chunks
    P = n_stages
    D = V * P
    lead = {leaf.shape[0]
            for leaf in jax.tree_util.tree_leaves(params["stage"])}
    if lead != {V}:
        raise ValueError(
            f"params['stage'] leaves must be stacked [n_chunks={V}, ...]; "
            f"got leading dims {sorted(lead)}")
    if n_microbatches % n_stages != 0:
        raise ValueError(
            f"interleaved 1F1B needs n_microbatches ({n_microbatches}) "
            f"divisible by pipeline size ({n_stages})")
    is_last = rank == n_stages - 1
    is_first = rank == 0
    # last backward: microbatch nmb-1 at global stage 0
    total_ticks = ((n_microbatches - 1) // P) * D + (n_microbatches - 1) % P \
        + 2 * (D - 1) + 1
    _mon.pipeline_schedule("interleaved_1f1b", n_stages, n_microbatches,
                           total_ticks, useful_ticks=V * n_microbatches)
    stash_slots = 2 * P + 1

    slice_mb = _mb_slicer(inputs)

    def chunk_of(tree, c):
        return jax.tree.map(
            lambda p: jax.lax.dynamic_index_in_dim(p, c, 0, keepdims=False),
            tree)

    h_shape, h_dtype = _probe_h(embed_fn, params["embed"], slice_mb)

    if _axis_probe_enabled(debug_axis_probe):
        _probe_no_pipeline_collectives(
            "embed_fn", embed_fn, (params["embed"], slice_mb(0)),
            axis_name)
        _probe_no_pipeline_collectives(
            "loss_fn", loss_fn,
            (params["head"], jnp.zeros(h_shape, h_dtype), slice_mb(0)),
            axis_name)

    init = (
        jnp.zeros(h_shape, h_dtype),                          # held_f
        jnp.zeros(h_shape, h_dtype),                          # held_b
        jnp.zeros((V, stash_slots) + h_shape, h_dtype),       # input stash
        jax.tree.map(jnp.zeros_like, params),                 # grad acc
        jnp.zeros((), jnp.float32),                           # loss sum
    )

    def tick(carry, i):
        held_f, held_b, stash, grads, loss_sum = carry
        _mon.traced_tick("pipeline/interleaved_1f1b/tick", i)

        # -- forward unit (same enumeration as the fill-drain schedule) --
        u = i - rank
        valid_f = (u >= 0) & (u < V * n_microbatches)
        uc = jnp.clip(u, 0, V * n_microbatches - 1)
        grp, rem = uc // D, uc % D
        c_f = rem // P
        m_f = grp * P + rem % P
        pf = chunk_of(params["stage"], c_f)
        use_inject = valid_f & (c_f == 0) & is_first
        inject = _embed_inject(embed_fn, use_inject, params["embed"],
                               slice_mb(m_f), h_shape, h_dtype)
        inp = jnp.where(use_inject, inject, held_f)
        out = stage_fn(pf, inp)
        slot = m_f % stash_slots
        cur = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(stash, c_f, 0, keepdims=False),
            slot, 0, keepdims=False)
        new_slot = jnp.where(valid_f, inp, cur)
        stash = jax.lax.dynamic_update_slice(
            stash, new_slot[None, None], (c_f, slot) + (0,) * len(h_shape))
        held_f = ring_shift(out, axis_name, wrap=True)

        # -- backward unit (time-reversed enumeration) -------------------
        w = i - 2 * (D - 1) + rank
        l = w % P                                    # nonneg (floor mod)
        z = (w - l) // P                             # = q*V - c_b
        q = (z + V - 1) // V                         # ceil(z / V)
        c_b = q * V - z
        m_b = q * P + l
        valid_b = (q >= 0) & (m_b < n_microbatches)
        m_bc = jnp.clip(m_b, 0, n_microbatches - 1)
        c_bc = jnp.clip(c_b, 0, V - 1)
        in_b = slice_mb(m_bc)
        inp_b = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(stash, c_bc, 0, keepdims=False),
            m_bc % stash_slots, 0, keepdims=False)
        pb = chunk_of(params["stage"], c_bc)
        out_b, pull_stage = jax.vjp(stage_fn, pb, inp_b)

        seed_here = is_last & valid_b & (c_bc == V - 1)
        loss_val, dhead, seed = _head_seed(
            loss_fn, seed_here, params["head"], out_b, in_b)

        g_out = jnp.where(seed_here, seed, held_b)
        dchunk, dinp = pull_stage(g_out)

        dembed = _embed_pullback(
            embed_fn, is_first & valid_b & (c_bc == 0), params["embed"],
            in_b, dinp.astype(h_dtype))

        def scatter_chunk(acc, d):
            cur_c = jax.lax.dynamic_index_in_dim(acc, c_bc, 0,
                                                 keepdims=False)
            upd = cur_c + jnp.where(valid_b, d, 0)
            return jax.lax.dynamic_update_index_in_dim(acc, upd, c_bc, 0)

        grads = {
            "embed": jax.tree.map(
                lambda a, d: a + jnp.where(valid_b & is_first, d, 0),
                grads["embed"], dembed),
            "stage": jax.tree.map(scatter_chunk, grads["stage"], dchunk),
            "head": jax.tree.map(
                lambda a, d: a + jnp.where(valid_b, d, 0),
                grads["head"], dhead),
        }
        loss_sum = loss_sum + loss_val        # zero off the seeding rank
        held_b = ring_shift(dinp, axis_name, reverse=True, wrap=True)
        _mon.traced_tick_marks("pipeline/interleaved_1f1b", i, rank,
                               f=valid_f, b=valid_b, w=valid_b)

        return (held_f, held_b, stash, grads, loss_sum), None

    (_, _, _, grads, loss_sum), _ = jax.lax.scan(
        _scoped_tick("pp_tick", tick), init, jnp.arange(total_ticks))
    return loss_sum, grads


def forward_backward_pipelining_1f1b_interleaved(
        stage_fn: Callable, loss_mb: Callable, chunk_params, x,
        n_microbatches: int, n_chunks: Optional[int] = None,
        axis_name: str = ps.PIPELINE_AXIS, remat_policy=None):
    """Headless interleaved 1F1B (stage stack only) — the vpp analog of
    ``forward_backward_pipelining_1f1b``. ``chunk_params`` leaves stacked
    [n_chunks, ...]; ``loss_mb(out) -> scalar`` per microbatch on the
    last rank's LAST chunk, run under a single-rank ``lax.cond`` — so it
    MUST NOT carry pipeline-axis collectives (the
    ``forward_backward_pipelining_1f1b_model`` contract; verify with
    ``APEX_TPU_PIPELINE_AXIS_PROBE=1``). Returns (loss_sum, chunk
    grads)."""
    if n_chunks is None:
        leaf = jax.tree_util.tree_leaves(chunk_params)[0]
        n_chunks = leaf.shape[0]
    loss, grads = forward_backward_pipelining_1f1b_interleaved_model(
        lambda _, x_mb: x_mb,
        stage_fn,
        lambda _, h, __: loss_mb(h),
        {"embed": {}, "stage": chunk_params, "head": {}},
        x, n_microbatches, n_chunks, axis_name,
        remat_policy=remat_policy)
    return loss, grads["stage"]


def forward_backward_pipelining_zb(
        stage_fn: Callable, loss_mb: Callable, stage_params, x,
        n_microbatches: int, axis_name: str = ps.PIPELINE_AXIS,
        wgrad_stash: Optional[int] = None, remat_policy=None):
    """Zero-bubble (ZB-H1-style) 1F1B: split backward, deferred wgrad.

    Headless special case of
    :func:`forward_backward_pipelining_zb_model` (identity injection
    from ``x``, no embed/head parameters), exactly as
    ``forward_backward_pipelining_1f1b`` is to its ``_model`` form.
    Same contract as 1F1B (``loss_mb`` per microbatch on the last rank,
    loss = SUM over microbatches, psum loss/grads externally); see the
    model variant for the wgrad-deferral semantics and the
    ``wgrad_stash`` knob. Gradients are bitwise the same computation as
    1F1B reordered — parity is pinned in ``tests/test_zero_bubble.py``.
    """
    loss, grads = forward_backward_pipelining_zb_model(
        lambda _, x_mb: x_mb,
        stage_fn,
        lambda _, h, __: loss_mb(h),
        {"embed": {}, "stage": stage_params, "head": {}},
        x, n_microbatches, axis_name,
        wgrad_stash=wgrad_stash, remat_policy=remat_policy)
    return loss, grads["stage"]


def forward_backward_pipelining_zb_model(
        embed_fn: Callable, stage_fn: Callable, loss_fn: Callable,
        params, inputs, n_microbatches: int,
        axis_name: str = ps.PIPELINE_AXIS,
        debug_axis_probe: Optional[bool] = None,
        wgrad_stash: Optional[int] = None, remat_policy=None):
    """Zero-bubble 1F1B for a FULL model: split backward (ZB-H1).

    Zero Bubble Pipeline Parallelism (Qi et al., 2023) factors each
    backward unit into **dgrad** (cotangent w.r.t. the stage input — on
    the pipeline's critical path, feeds the previous stage) and
    **wgrad** (cotangent w.r.t. the stage params — no inter-stage
    consumer, schedulable anywhere after its ``(activation, cotangent)``
    pair exists). This schedule keeps the 1F1B tick grid and ring
    dependency EXACTLY (dgrad runs at the 1F1B "B" tick; the reverse
    ``ppermute`` carries the same cotangents on the same ticks) but
    pulls the wgrad stream out of the tick-synchronous scan:

    - per tick: forward unit (identical to 1F1B) + dgrad-only backward
      (``backward_split.dgrad_vjp`` — the wgrad matmuls are not traced
      into the tick body at all), pushing ``(stage input, output
      cotangent)`` into the deferred-wgrad stash;
    - after the scan: a dense flush scan computes the deferred wgrads —
      every flush step is a real unit of work, no masking.

    Why this beats 1F1B here: the masked SPMD tick executes its full
    slot set on every tick, valid or not, so 1F1B's combined-VJP tick
    burns a full wgrad on each of the ``2(P-1)`` ring warmup/cooldown
    ticks. Splitting removes the wgrad slot from those bubble ticks:
    per-rank executed unit-slots drop from ``3·(nmb + 2(P-1))`` to
    ``2·(nmb + 2(P-1)) + nmb``, an idle-slot fraction of
    ``4(P-1)/(3·nmb + 4(P-1))`` vs 1F1B's
    ``2(P-1)/(nmb + 2(P-1))`` — strictly lower for P > 1 (measured per
    rank by the ``traced_tick_marks`` table, not just this formula).

    ``wgrad_stash`` (the memory knob, ``backward_split.
    normalize_wgrad_stash``): ``None`` = full deferral (stash holds all
    ``nmb`` pairs — peak stash memory ``2·nmb`` microbatch activations
    on top of the 1F1B input stash); ``0`` = eager flush (wgrad at its
    dgrad tick: exact 1F1B compute placement and memory, no stash, no
    flush scan); ``1 <= K < nmb`` = bounded (K pairs; the tick body
    flushes the oldest entry in-scan once full — masked in bubble
    ticks, so bounded mode trades the compute win back for memory).

    ``remat_policy`` wraps ``stage_fn`` in ``jax.checkpoint`` under the
    named policy (``apex_tpu.utils.remat``) so the per-unit pullbacks —
    including the deferred wgrad flush — save policy residuals instead
    of recomputing everything from the stashed input; the stash itself
    never double-saves what the policy would recompute (it holds only
    the ``(input, cotangent)`` pair either way).

    Everything else — the embed/loss contract (**no pipeline-axis
    collectives**, single-rank ``lax.cond`` branches,
    ``debug_axis_probe``/``APEX_TPU_PIPELINE_AXIS_PROBE=1``), the
    ``params`` dict {embed, stage, head}, the masked loss/grads return
    (psum over the pipeline axis outside) — is the
    ``forward_backward_pipelining_1f1b_model`` contract verbatim.
    """
    n_microbatches = resolve_num_microbatches(n_microbatches)
    stage_fn = with_remat_policy(stage_fn, remat_policy)
    n_stages = _axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    is_last = rank == n_stages - 1
    is_first = rank == 0
    delay = 2 * (n_stages - 1)
    total_ticks = n_microbatches + delay
    K = normalize_wgrad_stash(wgrad_stash, n_microbatches)
    eager = K == 0
    in_tick_wgrad = 0 < K < n_microbatches
    # analytic bubble in executed unit-slots (docstring): every tick
    # carries f + b slots, a w slot only in eager/bounded mode, and the
    # flush contributes K fully-valid w slots
    w_tick_slots = total_ticks if (eager or in_tick_wgrad) else 0
    _mon.pipeline_schedule(
        "zb1", n_stages, n_microbatches, total_ticks,
        useful_slots=3 * n_microbatches,
        total_slots=2 * total_ticks + w_tick_slots + K)
    stash_slots = max(1, 2 * n_stages - 1)

    slice_mb = _mb_slicer(inputs)

    h_shape, h_dtype = _probe_h(embed_fn, params["embed"], slice_mb)

    if _axis_probe_enabled(debug_axis_probe):
        _probe_no_pipeline_collectives(
            "embed_fn", embed_fn, (params["embed"], slice_mb(0)),
            axis_name)
        _probe_no_pipeline_collectives(
            "loss_fn", loss_fn,
            (params["head"], jnp.zeros(h_shape, h_dtype), slice_mb(0)),
            axis_name)

    init = (
        jnp.zeros(h_shape, h_dtype),                      # held_f
        jnp.zeros(h_shape, h_dtype),                      # held_b
        jnp.zeros((stash_slots,) + h_shape, h_dtype),     # input stash
        # deferred-wgrad stash: K (activation, cotangent) pairs
        (jnp.zeros((K,) + h_shape, h_dtype),
         jnp.zeros((K,) + h_shape, h_dtype)) if K else None,
        jax.tree.map(jnp.zeros_like, params),             # grad accumulator
        jnp.zeros((), jnp.float32),                       # loss sum
    )

    def tick(carry, i):
        held_f, held_b, stash, wstash, grads, loss_sum = carry
        _mon.traced_tick("pipeline/zb1/tick", i)

        # -- forward unit (identical to 1F1B) ---------------------------
        m_f = i - rank
        valid_f = (m_f >= 0) & (m_f < n_microbatches)
        m_fc = jnp.clip(m_f, 0, n_microbatches - 1)
        use_inject = valid_f & is_first
        inject = _embed_inject(embed_fn, use_inject, params["embed"],
                               slice_mb(m_fc), h_shape, h_dtype)
        inp = jnp.where(use_inject, inject, held_f)
        out = stage_fn(params["stage"], inp)
        slot = m_fc % stash_slots
        cur = jax.lax.dynamic_index_in_dim(stash, slot, keepdims=False)
        stash = jax.lax.dynamic_update_index_in_dim(
            stash, jnp.where(valid_f, inp, cur), slot, 0)
        held_f = send_forward_recv_forward(out, axis_name)

        # -- backward unit: dgrad ONLY on the critical path --------------
        m_b = i - delay + rank
        valid_b = (m_b >= 0) & (m_b < n_microbatches)
        m_bc = jnp.clip(m_b, 0, n_microbatches - 1)
        in_b = slice_mb(m_bc)
        inp_b = jax.lax.dynamic_index_in_dim(
            stash, m_bc % stash_slots, keepdims=False)
        out_b, pull_x = dgrad_vjp(stage_fn, params["stage"], inp_b)

        loss_val, dhead, seed = _head_seed(
            loss_fn, is_last & valid_b, params["head"], out_b, in_b)

        g_out = jnp.where(is_last, seed, held_b)
        dinp = pull_x(g_out)[0]

        dembed = _embed_pullback(
            embed_fn, is_first & valid_b, params["embed"], in_b,
            dinp.astype(h_dtype))

        # -- wgrad placement (the knob) ----------------------------------
        dstage = None
        w_valid = None
        if eager:
            # exact 1F1B placement: wgrad at its dgrad tick
            dstage, w_valid = wgrad(
                stage_fn, params["stage"], inp_b, g_out), valid_b
        if wstash is not None:
            # the incoming pair and the entry it would evict share slot
            # m_bc % K ((m_b - K) % K == m_b % K): ONE read serves both
            # the bounded-mode flush and the masked push fallback, and
            # it must happen before the update overwrites the slot
            w_slot = m_bc % K
            old_in = jax.lax.dynamic_index_in_dim(
                wstash[0], w_slot, keepdims=False)
            old_ct = jax.lax.dynamic_index_in_dim(
                wstash[1], w_slot, keepdims=False)
            if in_tick_wgrad:
                dstage = wgrad(stage_fn, params["stage"], old_in, old_ct)
                w_valid = valid_b & (m_b >= K)
            wstash = (
                jax.lax.dynamic_update_index_in_dim(
                    wstash[0], jnp.where(valid_b, inp_b, old_in),
                    w_slot, 0),
                jax.lax.dynamic_update_index_in_dim(
                    wstash[1], jnp.where(valid_b, g_out, old_ct),
                    w_slot, 0))

        grads = {
            "embed": jax.tree.map(
                lambda a, d: a + jnp.where(valid_b & is_first, d, 0),
                grads["embed"], dembed),
            "stage": grads["stage"] if dstage is None else jax.tree.map(
                lambda a, d: a + jnp.where(w_valid, d, 0),
                grads["stage"], dstage),
            "head": jax.tree.map(
                lambda a, d: a + jnp.where(valid_b, d, 0),
                grads["head"], dhead),
        }
        loss_sum = loss_sum + loss_val    # zero off the last rank
        held_b = send_backward_recv_backward(dinp, axis_name)
        marks = {"f": valid_f, "b": valid_b}
        if w_valid is not None:
            marks["w"] = w_valid
        _mon.traced_tick_marks("pipeline/zb1", i, rank, **marks)

        return (held_f, held_b, stash, wstash, grads, loss_sum), None

    (_, _, _, wstash, grads, loss_sum), _ = jax.lax.scan(
        _scoped_tick("pp_tick", tick), init, jnp.arange(total_ticks))

    if K:
        # -- deferred-wgrad flush: the bubble ticks' wgrad work, run
        # densely — every step is a valid unit (microbatches
        # nmb-K .. nmb-1; every rank owns exactly nmb backward units,
        # so every stashed pair is real)
        def flush(stage_grads, f_idx):
            m = n_microbatches - K + f_idx
            w_slot = m % K
            w_in = jax.lax.dynamic_index_in_dim(
                wstash[0], w_slot, keepdims=False)
            w_ct = jax.lax.dynamic_index_in_dim(
                wstash[1], w_slot, keepdims=False)
            d = wgrad(stage_fn, params["stage"], w_in, w_ct)
            _mon.traced_tick_marks("pipeline/zb1", total_ticks + f_idx,
                                   rank, w=True)
            return jax.tree.map(jnp.add, stage_grads, d), None

        stage_grads, _ = jax.lax.scan(
            _scoped_tick("pp_wgrad_flush", flush), grads["stage"],
            jnp.arange(K))
        grads = dict(grads, stage=stage_grads)
    return loss_sum, grads


def forward_backward_pipelining_zb_interleaved(
        stage_fn: Callable, loss_mb: Callable, chunk_params, x,
        n_microbatches: int, n_chunks: Optional[int] = None,
        axis_name: str = ps.PIPELINE_AXIS,
        wgrad_stash: Optional[int] = None, remat_policy=None):
    """Headless interleaved zero-bubble (stage stack only) — the vpp
    analog of ``forward_backward_pipelining_zb``, same relationship as
    the 1F1B pair. ``chunk_params`` leaves stacked [n_chunks, ...];
    ``wgrad_stash`` supports only full deferral (``None``) and eager
    (``0``) on the interleaved variant."""
    if n_chunks is None:
        leaf = jax.tree_util.tree_leaves(chunk_params)[0]
        n_chunks = leaf.shape[0]
    loss, grads = forward_backward_pipelining_zb_interleaved_model(
        lambda _, x_mb: x_mb,
        stage_fn,
        lambda _, h, __: loss_mb(h),
        {"embed": {}, "stage": chunk_params, "head": {}},
        x, n_microbatches, n_chunks, axis_name,
        wgrad_stash=wgrad_stash, remat_policy=remat_policy)
    return loss, grads["stage"]


def forward_backward_pipelining_zb_interleaved_model(
        embed_fn: Callable, stage_fn: Callable, loss_fn: Callable,
        params, inputs, n_microbatches: int, n_chunks: int,
        axis_name: str = ps.PIPELINE_AXIS,
        debug_axis_probe: Optional[bool] = None,
        wgrad_stash: Optional[int] = None, remat_policy=None):
    """Interleaved (vpp) zero-bubble: the split-backward treatment of
    ``forward_backward_pipelining_1f1b_interleaved_model``.

    The tick grid, both ring transports, the backward enumeration
    (exact time-reversal, chunks descending within each group), the
    embed/head conds, and every contract — including **no pipeline-axis
    collectives in embed_fn/loss_fn** — are the interleaved 1F1B's
    unchanged; only the backward unit is dgrad-only
    (``backward_split.dgrad_vjp``) with the wgrad deferred. The stash
    holds one ``(activation, cotangent)`` pair per executed (chunk,
    microbatch) unit — ``[V, nmb]`` slots — and the post-scan flush
    runs all ``V·nmb`` wgrads densely, selecting chunk params per
    entry and scattering into the ``[V, ...]`` grad leaves exactly as
    the tick body does.

    ``wgrad_stash``: only ``None`` (full deferral) and ``0`` (eager =
    exact interleaved-1F1B placement) — the bounded middle exists only
    on the non-interleaved schedule (a bounded FIFO over the
    chunk-major backward order buys little once V > 1 and complicates
    the slot arithmetic; raise rather than silently reinterpret).
    Executed unit-slots per rank: ``2·T + V·nmb`` (T = total ticks) vs
    the interleaved 1F1B's ``3·T`` — the same strict idle-fraction
    reduction as the plain schedule.
    """
    if wgrad_stash not in (None, 0):
        raise ValueError(
            "the interleaved zero-bubble schedule supports only full "
            "deferral (wgrad_stash=None) or eager flush (0); got "
            f"{wgrad_stash!r}")
    n_microbatches = resolve_num_microbatches(n_microbatches)
    stage_fn = with_remat_policy(stage_fn, remat_policy)
    n_stages = _axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    V = n_chunks
    P = n_stages
    D = V * P
    eager = wgrad_stash == 0
    lead = {leaf.shape[0]
            for leaf in jax.tree_util.tree_leaves(params["stage"])}
    if lead != {V}:
        raise ValueError(
            f"params['stage'] leaves must be stacked [n_chunks={V}, ...]; "
            f"got leading dims {sorted(lead)}")
    if n_microbatches % n_stages != 0:
        raise ValueError(
            f"interleaved zero-bubble needs n_microbatches "
            f"({n_microbatches}) divisible by pipeline size ({n_stages})")
    is_last = rank == n_stages - 1
    is_first = rank == 0
    total_ticks = ((n_microbatches - 1) // P) * D + (n_microbatches - 1) % P \
        + 2 * (D - 1) + 1
    n_units = V * n_microbatches
    _mon.pipeline_schedule(
        "interleaved_zb1", n_stages, n_microbatches, total_ticks,
        useful_slots=3 * n_units,
        total_slots=(3 if eager else 2) * total_ticks
        + (0 if eager else n_units))
    stash_slots = 2 * P + 1

    slice_mb = _mb_slicer(inputs)

    def chunk_of(tree, c):
        return jax.tree.map(
            lambda p: jax.lax.dynamic_index_in_dim(p, c, 0, keepdims=False),
            tree)

    h_shape, h_dtype = _probe_h(embed_fn, params["embed"], slice_mb)

    if _axis_probe_enabled(debug_axis_probe):
        _probe_no_pipeline_collectives(
            "embed_fn", embed_fn, (params["embed"], slice_mb(0)),
            axis_name)
        _probe_no_pipeline_collectives(
            "loss_fn", loss_fn,
            (params["head"], jnp.zeros(h_shape, h_dtype), slice_mb(0)),
            axis_name)

    init = (
        jnp.zeros(h_shape, h_dtype),                          # held_f
        jnp.zeros(h_shape, h_dtype),                          # held_b
        jnp.zeros((V, stash_slots) + h_shape, h_dtype),       # input stash
        # deferred-wgrad stash: one pair per (chunk, microbatch) unit
        None if eager else (
            jnp.zeros((V, n_microbatches) + h_shape, h_dtype),
            jnp.zeros((V, n_microbatches) + h_shape, h_dtype)),
        jax.tree.map(jnp.zeros_like, params),                 # grad acc
        jnp.zeros((), jnp.float32),                           # loss sum
    )

    def scatter_chunk(c, pred, acc, d):
        cur_c = jax.lax.dynamic_index_in_dim(acc, c, 0, keepdims=False)
        upd = cur_c + jnp.where(pred, d, 0)
        return jax.lax.dynamic_update_index_in_dim(acc, upd, c, 0)

    def tick(carry, i):
        held_f, held_b, stash, wstash, grads, loss_sum = carry
        _mon.traced_tick("pipeline/interleaved_zb1/tick", i)

        # -- forward unit (interleaved enumeration, unchanged) -----------
        u = i - rank
        valid_f = (u >= 0) & (u < n_units)
        uc = jnp.clip(u, 0, n_units - 1)
        grp, rem = uc // D, uc % D
        c_f = rem // P
        m_f = grp * P + rem % P
        pf = chunk_of(params["stage"], c_f)
        use_inject = valid_f & (c_f == 0) & is_first
        inject = _embed_inject(embed_fn, use_inject, params["embed"],
                               slice_mb(m_f), h_shape, h_dtype)
        inp = jnp.where(use_inject, inject, held_f)
        out = stage_fn(pf, inp)
        slot = m_f % stash_slots
        cur = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(stash, c_f, 0, keepdims=False),
            slot, 0, keepdims=False)
        new_slot = jnp.where(valid_f, inp, cur)
        stash = jax.lax.dynamic_update_slice(
            stash, new_slot[None, None], (c_f, slot) + (0,) * len(h_shape))
        held_f = ring_shift(out, axis_name, wrap=True)

        # -- backward unit: dgrad only (time-reversed enumeration) -------
        w = i - 2 * (D - 1) + rank
        l = w % P
        z = (w - l) // P
        q = (z + V - 1) // V
        c_b = q * V - z
        m_b = q * P + l
        valid_b = (q >= 0) & (m_b < n_microbatches)
        m_bc = jnp.clip(m_b, 0, n_microbatches - 1)
        c_bc = jnp.clip(c_b, 0, V - 1)
        in_b = slice_mb(m_bc)
        inp_b = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(stash, c_bc, 0, keepdims=False),
            m_bc % stash_slots, 0, keepdims=False)
        pb = chunk_of(params["stage"], c_bc)
        out_b, pull_x = dgrad_vjp(stage_fn, pb, inp_b)

        seed_here = is_last & valid_b & (c_bc == V - 1)
        loss_val, dhead, seed = _head_seed(
            loss_fn, seed_here, params["head"], out_b, in_b)

        g_out = jnp.where(seed_here, seed, held_b)
        dinp = pull_x(g_out)[0]

        dembed = _embed_pullback(
            embed_fn, is_first & valid_b & (c_bc == 0), params["embed"],
            in_b, dinp.astype(h_dtype))

        if eager:
            dchunk = wgrad(stage_fn, pb, inp_b, g_out)
            stage_grads = jax.tree.map(
                lambda a, d: scatter_chunk(c_bc, valid_b, a, d),
                grads["stage"], dchunk)
        else:
            stage_grads = grads["stage"]
            cur_in = jax.lax.dynamic_index_in_dim(
                jax.lax.dynamic_index_in_dim(
                    wstash[0], c_bc, 0, keepdims=False),
                m_bc, 0, keepdims=False)
            cur_ct = jax.lax.dynamic_index_in_dim(
                jax.lax.dynamic_index_in_dim(
                    wstash[1], c_bc, 0, keepdims=False),
                m_bc, 0, keepdims=False)
            idx = (c_bc, m_bc) + (0,) * len(h_shape)
            wstash = (
                jax.lax.dynamic_update_slice(
                    wstash[0], jnp.where(valid_b, inp_b, cur_in)[None, None],
                    idx),
                jax.lax.dynamic_update_slice(
                    wstash[1], jnp.where(valid_b, g_out, cur_ct)[None, None],
                    idx))

        grads = {
            "embed": jax.tree.map(
                lambda a, d: a + jnp.where(valid_b & is_first, d, 0),
                grads["embed"], dembed),
            "stage": stage_grads,
            "head": jax.tree.map(
                lambda a, d: a + jnp.where(valid_b, d, 0),
                grads["head"], dhead),
        }
        loss_sum = loss_sum + loss_val
        held_b = ring_shift(dinp, axis_name, reverse=True, wrap=True)
        marks = {"f": valid_f, "b": valid_b}
        if eager:
            marks["w"] = valid_b
        _mon.traced_tick_marks("pipeline/interleaved_zb1", i, rank,
                               **marks)

        return (held_f, held_b, stash, wstash, grads, loss_sum), None

    (_, _, _, wstash, grads, loss_sum), _ = jax.lax.scan(
        _scoped_tick("pp_tick", tick), init, jnp.arange(total_ticks))

    if not eager:
        # dense flush over every (chunk, microbatch) unit — all valid
        def flush(stage_grads, f_idx):
            c = f_idx // n_microbatches
            m = f_idx % n_microbatches
            w_in = jax.lax.dynamic_index_in_dim(
                jax.lax.dynamic_index_in_dim(
                    wstash[0], c, 0, keepdims=False), m, 0, keepdims=False)
            w_ct = jax.lax.dynamic_index_in_dim(
                jax.lax.dynamic_index_in_dim(
                    wstash[1], c, 0, keepdims=False), m, 0, keepdims=False)
            d = wgrad(stage_fn, chunk_of(params["stage"], c), w_in, w_ct)
            _mon.traced_tick_marks("pipeline/interleaved_zb1",
                                   total_ticks + f_idx, rank, w=True)
            return jax.tree.map(
                lambda a, dd: scatter_chunk(c, True, a, dd),
                stage_grads, d), None

        stage_grads, _ = jax.lax.scan(
            _scoped_tick("pp_wgrad_flush", flush), grads["stage"],
            jnp.arange(n_units))
        grads = dict(grads, stage=stage_grads)
    return loss_sum, grads


def staged_group_scan(grad_of_group: Callable, params, xs,
                      n_microbatches: int, group_size: int, n_stages: int):
    """Shared staged-grads accumulator (the memory lever of
    ``microbatch_group_size`` — see docs/perf.md).

    Splits every leaf of ``xs`` ([n_microbatches, ...]) into
    ``n_microbatches // group_size`` groups and runs
    ``grad_of_group(xs_group) -> (grads, loss)`` over them in an outer
    NON-differentiated ``lax.scan``, accumulating both in the carry —
    peak activation residuals are O(group_size·mb) instead of
    O(n_microbatches·mb). Returns ``(loss_sum, grads_sum, n_groups)``
    with RAW SUMS over groups; the caller owns the normalization (the
    schedule-level API documents the sum, the model-level API divides
    by ``n_groups``).

    On the loss-scale asymmetry between the two public APIs (advisor
    r4): a SUM-over-microbatches ``loss_head`` is the one class for
    which grouping is exact (group sums add to the ungrouped total) —
    so the schedule-level API returns the raw sum and stays exact for
    that class, while ``PipelinedGPT.loss_and_grads`` divides by
    ``n_groups`` because ITS loss is a per-group mean. Normalizing
    inside the schedule would silently break the sum class instead;
    the asymmetry is deliberate and both docstrings state their rule.
    """
    if group_size % n_stages != 0 or n_microbatches % group_size != 0:
        raise ValueError(
            f"microbatch_group_size ({group_size}) must be a multiple of "
            f"the pipeline size ({n_stages}) dividing n_microbatches "
            f"({n_microbatches})")
    n_groups = n_microbatches // group_size
    xg = jax.tree.map(
        lambda a: a.reshape((n_groups, group_size) + a.shape[1:]), xs)

    def group(carry, xs_g):
        loss_sum, gacc = carry
        g, l = grad_of_group(xs_g)
        return (loss_sum + l, jax.tree.map(jnp.add, gacc, g)), None

    zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(group, zero, xg)
    return loss, grads, n_groups


def pipeline_apply_interleaved(stage_fn: Callable, chunk_params, x,
                               n_microbatches: int, n_chunks: int,
                               axis_name: str = ps.PIPELINE_AXIS,
                               remat: bool = True,
                               with_aux: bool = False,
                               remat_policy=None):
    """Interleaved (virtual-pipeline) schedule over the pipeline axis.

    Each rank holds ``n_chunks`` (= vpp) model chunks stacked on the
    leading axis of every leaf of ``chunk_params``; chunk ``c`` of rank
    ``r`` is *global* stage ``c*P + r`` (the Megatron interleaved
    assignment whose rank state the reference tracks,
    ``apex/transformer/parallel_state.py:252-322``).

    Schedule: unit (microbatch m, chunk c) runs on rank r at tick
    ``t = (m//P)*V*P + c*P + (m%P) + r``. Every activation is consumed
    exactly one tick after it is produced, so one held slot and one
    ring ``ppermute`` per tick suffice (same transport as the
    non-interleaved schedule) while each rank time-multiplexes its V
    chunks. Total ticks = ``V*nmb + P - 1`` — the (P-1)-tick bubble of
    GPipe's ``V*(nmb + P - 1)`` shrinks by the factor V that interleaving
    exists to deliver.

    Requires ``n_microbatches % P == 0`` (the Megatron constraint).
    ``x``: [n_microbatches, mb, ...]; returns [n_microbatches, mb, ...]
    final-stage outputs (valid on the last rank).

    ``with_aux``: ``stage_fn`` returns ``(h, aux_scalar)`` and the call
    returns ``(outputs, aux_sum)`` — aux (e.g. the MoE load-balancing
    loss) accumulated over exactly the REAL (mask-valid) units this rank
    executed; bubble ticks contribute nothing. Summing each rank's
    ``aux_sum`` over the pipeline axis gives the total over all stages
    and microbatches.
    """
    n_microbatches = resolve_num_microbatches(n_microbatches)
    n_stages = _axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    V = n_chunks
    lead = {leaf.shape[0] for leaf in jax.tree_util.tree_leaves(chunk_params)}
    if lead != {V}:
        raise ValueError(
            f"chunk_params leaves must be stacked [n_chunks={V}, ...]; got "
            f"leading dims {sorted(lead)}")
    if n_microbatches % n_stages != 0:
        raise ValueError(
            f"interleaved schedule needs n_microbatches ({n_microbatches}) "
            f"divisible by pipeline size ({n_stages})")
    total_ticks = V * n_microbatches + n_stages - 1
    _mon.pipeline_schedule("interleaved", n_stages, n_microbatches,
                           total_ticks, useful_ticks=V * n_microbatches)
    fn = _checkpointed(stage_fn, remat, remat_policy)

    h_shape = x.shape[1:]
    init_held = jnp.zeros(h_shape, x.dtype)
    init_out = jnp.zeros((n_microbatches,) + h_shape, x.dtype)

    def tick(carry, t):
        held, outputs, aux_sum = carry
        u = t - rank                      # unit index in this rank's order
        valid = (u >= 0) & (u < V * n_microbatches)
        uc = jnp.clip(u, 0, V * n_microbatches - 1)
        group, rem = uc // (V * n_stages), uc % (V * n_stages)
        c = rem // n_stages               # chunk to apply this tick
        m = group * n_stages + rem % n_stages  # microbatch of this unit

        params_c = jax.tree.map(
            lambda p: jax.lax.dynamic_index_in_dim(p, c, 0, keepdims=False),
            chunk_params)

        inject = jax.lax.dynamic_index_in_dim(x, m, keepdims=False)
        use_inject = valid & (c == 0) & (rank == 0)
        inp = jnp.where(use_inject, inject, held)
        if with_aux:
            out, aux = fn(params_c, inp)
            aux_sum = aux_sum + jnp.where(valid, aux, 0.0)
        else:
            out = fn(params_c, inp)
        # collect completed microbatches on the last rank's last chunk
        done = valid & (c == V - 1) & (rank == n_stages - 1)
        updated = jax.lax.dynamic_update_index_in_dim(outputs, out, m, 0)
        outputs = jnp.where(done, updated, outputs)
        # cyclic: the last rank's chunk-c output wraps to rank 0, which
        # consumes it next tick as chunk c+1's input
        held_next = ring_shift(out, axis_name, wrap=True)
        return (held_next, outputs, aux_sum), None

    (_, outputs, aux_sum), _ = jax.lax.scan(
        tick, (init_held, init_out, jnp.zeros((), jnp.float32)),
        jnp.arange(total_ticks))
    return (outputs, aux_sum) if with_aux else outputs


def forward_backward_pipelining_with_interleaving(
        stage_fn: Callable, loss_head: Callable, chunk_params, x,
        n_microbatches: int, n_chunks: Optional[int] = None,
        axis_name: str = ps.PIPELINE_AXIS,
        microbatch_group_size: Optional[int] = None):
    """Interleaved pipeline + loss, returning (loss, chunk-param grads).

    ``microbatch_group_size`` (staged grads): differentiating through the
    full schedule stashes one stage-input residual per tick, so peak
    activation memory grows with ``n_microbatches``. Setting a group size
    ``G`` (a multiple of the pipeline size that divides
    ``n_microbatches``) runs the schedule on G microbatches at a time in
    an outer non-differentiated scan, accumulating gradients in the
    carry — peak activation memory becomes O(G·mb) at the cost of one
    extra (P-1)-tick bubble per group. The returned loss is the SUM of
    per-group ``loss_head`` values: a ``loss_head`` that means over its
    microbatch axis needs an external ``/ (n_microbatches // G)``.
    """
    n_microbatches = resolve_num_microbatches(n_microbatches)
    n_stages = _axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    if n_chunks is None:
        n_chunks = ps.get_virtual_pipeline_model_parallel_world_size() or 1
        if n_chunks == 1:
            leaf = jax.tree_util.tree_leaves(chunk_params)[0]
            n_chunks = leaf.shape[0]

    def full(params, xs, nmb):
        outs = pipeline_apply_interleaved(stage_fn, params, xs,
                                          nmb, n_chunks, axis_name)
        loss = loss_head(outs)
        return jnp.where(rank == n_stages - 1, loss, 0.0)

    if microbatch_group_size is None:
        return jax.value_and_grad(full)(chunk_params, x, n_microbatches)

    G = microbatch_group_size

    def grad_of_group(xs):
        loss, g = jax.value_and_grad(full)(chunk_params, xs, G)
        return g, loss

    loss, grads, _ = staged_group_scan(
        grad_of_group, chunk_params, x, n_microbatches, G, n_stages)
    return loss, grads


def get_forward_backward_func(virtual_pipeline_model_parallel_size=None,
                              pipeline_model_parallel_size: int = 1):
    """Dispatch mirroring Megatron's ``get_forward_backward_func``
    (vpp state: ``apex/transformer/parallel_state.py:252-322``)."""
    if pipeline_model_parallel_size > 1:
        if (virtual_pipeline_model_parallel_size is not None
                and virtual_pipeline_model_parallel_size > 1):
            return forward_backward_pipelining_with_interleaving
        return forward_backward_pipelining_without_interleaving
    return forward_backward_no_pipelining
