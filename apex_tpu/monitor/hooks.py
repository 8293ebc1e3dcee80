"""Instrumentation entry points called from inside apex_tpu subsystems.

Contract (the disabled-mode overhead guarantee, docs/observability.md):
every hook's first action is reading the module guard; with no recorder
attached it returns immediately — no jax import, no allocation, no
inserted ops. A jitted function traced while monitoring is disabled
therefore produces a jaxpr byte-identical to the uninstrumented
program (asserted by ``tests/test_monitor.py``).

Two families:

- **host hooks** (``counter``/``gauge``/``timer``): run in ordinary
  Python (data loader threads, eager wrappers). Never traced.
- **traced hooks** (``traced_scalar``/``traced_tick``): called from
  inside code under ``jit``/``shard_map``/``scan``; when enabled they
  insert a ``jax.debug.callback`` carrying the device value to the
  recorder. When disabled they insert nothing. NB: JAX's partial-eval
  drops debug callbacks from program regions that are *differentiated
  through* (e.g. a scan under ``value_and_grad``) — place traced hooks
  after the grad computation or in non-differentiated scans.
- **trace-time hooks** (``collective``/``pipeline_schedule``): run on
  the host *while a program is being traced* and record statically-known
  facts (collective op counts/bytes per axis, schedule geometry). Their
  totals are per traced program: a cached executable re-runs the same
  collectives every step without re-counting, so attach the recorder
  before tracing (the guard static arg in ``amp.make_train_step`` and
  ``FusedOptimizerBase.step`` forces that retrace automatically).
"""

from __future__ import annotations

import contextlib
import functools

from apex_tpu.monitor import _state

_NULL = contextlib.nullcontext()


def enabled() -> bool:
    """True iff a recorder is attached (host hooks are live)."""
    return _state.recorder is not None


def traced_enabled() -> bool:
    """True iff a recorder is attached AND it wants traced-hook
    instrumentation (``Recorder(traced_hooks=True)``, the default).
    Code that *inserts ops or callbacks into traced programs* must gate
    on this, not :func:`enabled` — a host-only observer recorder
    (``traced_hooks=False``, e.g. the bench's) must leave compiled
    programs byte-identical."""
    rec = _state.recorder
    return rec is not None and getattr(rec, "traced_hooks", True)


def epoch() -> int:
    """Monitoring epoch — bumped on every attach/detach (a change
    counter for caches that track recorder identity; the jitted hot
    paths key on :func:`traced_enabled` instead so their caches stay
    bounded at two programs)."""
    return _state.epoch


# -- host hooks --------------------------------------------------------------

def counter(name: str, inc: float = 1, **extra):
    rec = _state.recorder
    if rec is not None:
        rec.counter(name, inc, **extra)


def gauge(name: str, value, **extra):
    rec = _state.recorder
    if rec is not None:
        rec.gauge(name, value, **extra)


def observe(name: str, value, **kw):
    """Record one sample (``n=k``: ``k`` samples of one value) into the
    attached recorder's named log-scale histogram (``Recorder.observe``
    — O(1) memory streaming percentiles; no per-sample event). The
    serve engine's token-latency / TTFT / queue-wait SLO numbers flow
    through here."""
    rec = _state.recorder
    if rec is not None:
        rec.observe(name, value, **kw)


def timer(name: str):
    """Context manager timing a host-side block; null when disabled."""
    rec = _state.recorder
    if rec is None:
        return _NULL
    return rec.timer(name)


def timer_event(name: str, seconds: float, **extra):
    rec = _state.recorder
    if rec is not None:
        rec.timer_event(name, seconds, **extra)


def tune_event(kernel: str, key: str, *, hit: bool, source: str,
               config=None):
    """One autotuner cache resolution (``apex_tpu.tune.runtime``):
    bumps the ``tune/cache_hit``/``tune/cache_miss`` counter, sets the
    ``tune/cache_hit`` gauge (1.0 on a hit — last-resolution-wins),
    and records a typed ``tune``
    event carrying the full cache key and the resolved config."""
    rec = _state.recorder
    if rec is None:
        return
    rec.counter("tune/cache_hit" if hit else "tune/cache_miss")
    rec.gauge("tune/cache_hit", 1.0 if hit else 0.0)
    rec.emit("tune", kernel, key, hit=bool(hit), source=source,
             config=config)


# -- traced hooks (insert a debug callback when enabled) ---------------------
#
# The callback targets resolve the recorder at FIRE time, not at trace
# time: a compiled program that carries instrumentation (because it was
# traced while a recorder was attached) stops emitting the moment the
# recorder is detached, and a later-attached recorder receives the
# events instead — no stale recorder is captured alive inside the
# executable. (Trace-time accounting — collectives, schedules — is by
# definition bound to the recorder attached when the trace ran.)

def _emit_scalar(name: str, value):
    # honor the receiver's traced_hooks opt-out at fire time too: a
    # host-only observer must not collect traced-hook telemetry baked
    # into programs compiled under an earlier instrumented recorder
    rec = _state.recorder
    if rec is not None and getattr(rec, "traced_hooks", True):
        rec._device_scalar(name, value)


def _emit_tick(name: str, tick):
    rec = _state.recorder
    if rec is not None and getattr(rec, "traced_hooks", True):
        rec._device_tick(name, tick)


def traced_scalar(name: str, value):
    """Record a device scalar as a gauge. Call from traced code with a
    jax scalar; inserts a ``jax.debug.callback`` only when enabled."""
    rec = _state.recorder
    if rec is None or not rec.traced_hooks:
        return
    import jax
    jax.debug.callback(
        functools.partial(_emit_scalar, name), value, ordered=False)


def traced_tick(name: str, tick):
    """Record a schedule tick mark (host-arrival timestamped)."""
    rec = _state.recorder
    if rec is None or not rec.traced_hooks:
        return
    import jax
    jax.debug.callback(
        functools.partial(_emit_tick, name), tick, ordered=False)


def _emit_tick_marks(name: str, keys, tick, rank, *vals):
    rec = _state.recorder
    if rec is not None and getattr(rec, "traced_hooks", True):
        rec._device_tick_marks(name, tick, rank, dict(zip(keys, vals)))


def traced_tick_marks(name: str, tick, rank, **slots):
    """Record one MEASURED slot-occupancy mark for a pipeline tick.

    ``slots`` are traced booleans, one per unit slot the tick body
    executes (``f`` = forward unit, ``b`` = backward-input/dgrad unit,
    ``w`` = backward-weight/wgrad unit); a False slot means the
    computation ran masked on padding — an idle slot. ``rank`` is the
    traced pipeline rank, so the aggregated table
    (``report.aggregate()["pipeline_utilization"]``) is per rank.
    Inserts one ``jax.debug.callback`` when enabled, nothing otherwise
    (the disabled-mode purity contract)."""
    rec = _state.recorder
    if rec is None or not rec.traced_hooks:
        return
    import jax
    keys = tuple(sorted(slots))
    jax.debug.callback(
        functools.partial(_emit_tick_marks, name, keys), tick, rank,
        *(slots[k] for k in keys), ordered=False)


# -- trace-time hooks --------------------------------------------------------

def tree_bytes(tree) -> int:
    """Static byte count of a pytree of arrays/tracers (shape/dtype are
    trace-time constants). Only call from an enabled path."""
    import jax
    import numpy as np
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(np.prod(shape or (1,))) * np.dtype(dtype).itemsize
    return total


def collective(op: str, axis_name, operand=None, *, nbytes: int = None,
               count: int = 1):
    """Account one collective call on ``axis_name`` (trace time).

    ``operand`` (a pytree of arrays/tracers) gives the byte volume;
    pass ``nbytes`` directly when the operand is not at hand.
    ``axis_name`` may be a tuple of names (counted once per name).
    """
    rec = _state.recorder
    if rec is None or not rec.traced_hooks:
        return
    if nbytes is None:
        nbytes = tree_bytes(operand) if operand is not None else 0
    names = axis_name if isinstance(axis_name, (tuple, list)) else (axis_name,)
    for ax in names:
        rec.collective(op, str(ax), nbytes=nbytes, count=count)


def pipeline_schedule(schedule: str, n_stages: int, n_microbatches: int,
                      total_ticks: int, useful_ticks: int = None,
                      useful_slots: int = None, total_slots: int = None):
    """Record a pipeline schedule's geometry and its analytic
    bubble-fraction estimate: the fraction of scan ticks a rank spends
    on padding rather than a real microbatch unit,
    ``1 - useful_ticks / total_ticks`` (``useful_ticks`` defaults to
    ``n_microbatches`` — one unit per microbatch per stream). Schedules
    with heterogeneous ticks (zero-bubble: the wgrad stream leaves the
    tick grid) pass ``useful_slots``/``total_slots`` — executed
    unit-slot counts per rank — and the bubble fraction is
    ``1 - useful_slots / total_slots`` instead; for the homogeneous
    schedules the two definitions coincide. Measured per-tick arrivals
    come from ``traced_tick``/``traced_tick_marks`` separately."""
    rec = _state.recorder
    if rec is None or not rec.traced_hooks:
        return
    extra = {}
    if useful_slots is not None and total_slots is not None:
        bubble = 1.0 - (float(useful_slots) / float(total_slots)) \
            if total_slots else 0.0
        extra = {"useful_slots": int(useful_slots),
                 "total_slots": int(total_slots)}
    else:
        useful = n_microbatches if useful_ticks is None else useful_ticks
        bubble = 1.0 - (float(useful) / float(total_ticks)) \
            if total_ticks else 0.0
    rec.gauge(f"pipeline/{schedule}/bubble_fraction", round(bubble, 6))
    rec._emit("schedule", f"pipeline/{schedule}", total_ticks,
              n_stages=int(n_stages), n_microbatches=int(n_microbatches),
              bubble_fraction=round(bubble, 6), **extra)
