"""Training-health watchdog over the Recorder event stream.

The telemetry PR 2 built records what happened; this layer says what is
*wrong*. A :class:`Watchdog` registers as a step observer on a
:class:`~apex_tpu.monitor.recorder.Recorder` and scans every closed
step record on the host for the conditions that actually kill
mixed-precision distributed runs:

- ``nan``                non-finite loss / grad-norm / any step gauge
- ``overflow_storm``     the dynamic loss scale halving (or the
                         overflow flag firing) >= N times in a window —
                         grads are persistently non-finite, the scaler
                         is treading water instead of recovering
- ``loss_divergence``    loss blowing past ``divergence_factor`` x its
                         best value after a grace period
- ``loss_plateau``       loss flat (relative change < rtol) over a full
                         window
- ``loader_starvation``  ``data/host_wait`` eating more than a fraction
                         of the step time for consecutive steps — the
                         chip is waiting on the input pipeline
- ``straggler``          (cross-host, via :meth:`Watchdog.
                         check_cross_host` on a ``merge`` view) a rank
                         whose median step time exceeds the global
                         median by ``straggler_ratio``

Serve-side conditions (the serve engine records its per-round gauges
and counters inside per-step records — ``ServeEngine.step`` — so the
same observer sees them with no serve-specific wiring):

- ``kv_pool_exhaustion``   the page allocator's free list at/below
                           ``kv_pool_min_free_fraction`` of the pool
                           (``serve/pages_free`` vs ``serve/
                           pages_total``) — admission and growth are
                           about to start evicting
- ``eviction_storm``       preemptions in >= ``eviction_trips`` of the
                           last ``eviction_window`` steps (the
                           ``serve/preemptions`` counter per step):
                           the pool is thrashing — every admission
                           evicts someone whose recompute evicts the
                           next
- ``admission_starvation`` the oldest waiting request's age
                           (``serve/queue_wait_oldest_s``), EMA-
                           smoothed, above ``admission_age_s`` — the
                           queue head cannot be admitted (pool or
                           batch slots too small for the traffic)

Memory conditions (the OOM-forecast layer — ``monitor.memory``'s
sampler/snapshot gauges ride ordinary step records, so the same
observer sees them with no memory-specific wiring):

- ``hbm_high_water``       ``memory/hbm_bytes_in_use`` at/above
                           ``hbm_high_water_fraction`` of
                           ``memory/hbm_limit_bytes`` — the allocator
                           is about to OOM on the next spike;
                           hysteresis re-arm below 90% of the bar
- ``memory_leak``          positive least-squares slope of the
                           ``memory/hbm_bytes_in_use`` step gauge over
                           a full ``leak_window``, with predicted
                           growth over the window at/above
                           ``leak_rel_threshold`` of the window mean
                           (a constant footprint NEVER fires — the
                           false-positive guard is tested)
- ``recompile_storm``      backend compiles / jit-cache misses landing
                           in >= ``recompile_trips`` of the last
                           ``recompile_window`` steps after a
                           ``recompile_grace`` warmup — a shape or
                           static-arg churn is retracing every step
                           (and each retrace's executable + buffers
                           inflate HBM: the classic slow-motion OOM)

Each detection emits one typed ``health_event`` record into the
recorder — ``{"kind": "health_event", "name": <condition>, "severity",
"diagnosis", ...}`` — which rides the JSONL dump, shows up in
``python -m apex_tpu.monitor report``, and (when the recorder streams)
is flushed to disk immediately. ``on_event`` lets the training loop
react, e.g. dump :meth:`Watchdog.diagnostics_bundle` and abort.

Everything here is host-side Python over already-recorded events: the
watchdog inserts no ops, forces no retrace, and costs nothing when
monitoring is detached (the disabled-mode purity guarantee of
docs/observability.md is untouched).
"""

from __future__ import annotations

import collections
import math
from typing import Callable, Optional

HEALTH_EVENT_KINDS = (
    "nan", "overflow_storm", "loss_divergence", "loss_plateau",
    "loader_starvation", "straggler",
    "kv_pool_exhaustion", "eviction_storm", "admission_starvation",
    "hbm_high_water", "memory_leak", "recompile_storm",
)

# Conditions fatal enough that the process may not get another chance
# to tell its story: each firing also triggers a flight-recorder dump
# (apex_tpu.monitor.flight — inert unless flight.install() armed it).
FLIGHT_DUMP_EVENTS = ("nan", "hbm_high_water", "memory_leak")


def _finite(v) -> bool:
    try:
        return math.isfinite(float(v))
    except (TypeError, ValueError):
        return True   # non-numeric gauges are not NaN signals


class Watchdog:
    """Online health analysis of a recorder's step stream.

    Usage::

        rec = monitor.Recorder()
        dog = monitor.Watchdog(rec, on_event=my_handler)
        with monitor.attached(rec):
            for batch in loader:
                with rec.step():
                    state = train_step(state, batch)
        # dog.events holds every health_event; they are also in
        # rec.records("health_event") and the rendered report.

    All thresholds are keyword-configurable. ``loss_gauges`` names the
    gauges tried (in order) as "the loss" for plateau/divergence
    tracking; NaN detection scans *every* gauge on the step record.
    """

    def __init__(self, recorder=None, *,
                 on_event: Optional[Callable] = None,
                 loss_gauges=("train/loss", "loss"),
                 overflow_window: int = 20, overflow_trips: int = 3,
                 divergence_factor: float = 3.0,
                 divergence_grace: int = 10,
                 divergence_patience: int = 3,
                 divergence_smoothing: float = 0.2,
                 plateau_window: int = 50, plateau_rtol: float = 1e-3,
                 starvation_fraction: float = 0.5,
                 starvation_window: int = 5,
                 straggler_ratio: float = 1.5,
                 kv_pool_min_free_fraction: float = 0.1,
                 eviction_window: int = 20, eviction_trips: int = 3,
                 admission_age_s: float = 30.0,
                 admission_smoothing: float = 0.3,
                 hbm_high_water_fraction: float = 0.9,
                 leak_window: int = 20,
                 leak_rel_threshold: float = 0.05,
                 recompile_window: int = 10, recompile_trips: int = 3,
                 recompile_grace: int = 3,
                 diagnostics_steps: int = 16,
                 scaler=None):
        self.on_event = on_event
        self.loss_gauges = tuple(loss_gauges)
        self.overflow_window = int(overflow_window)
        self.overflow_trips = int(overflow_trips)
        self.divergence_factor = float(divergence_factor)
        self.divergence_grace = int(divergence_grace)
        self.divergence_patience = int(divergence_patience)
        self.divergence_smoothing = float(divergence_smoothing)
        self.plateau_window = int(plateau_window)
        self.plateau_rtol = float(plateau_rtol)
        self.starvation_fraction = float(starvation_fraction)
        self.starvation_window = int(starvation_window)
        self.straggler_ratio = float(straggler_ratio)
        self.kv_pool_min_free_fraction = float(kv_pool_min_free_fraction)
        self.eviction_window = int(eviction_window)
        self.eviction_trips = int(eviction_trips)
        self.admission_age_s = float(admission_age_s)
        self.admission_smoothing = float(admission_smoothing)
        self.hbm_high_water_fraction = float(hbm_high_water_fraction)
        self.leak_window = int(leak_window)
        self.leak_rel_threshold = float(leak_rel_threshold)
        self.recompile_window = int(recompile_window)
        self.recompile_trips = int(recompile_trips)
        self.recompile_grace = int(recompile_grace)
        self.diagnostics_steps = int(diagnostics_steps)
        self.scaler = scaler            # optional LossScaler for bundles
        self.events: list[dict] = []
        self.recorder = None
        # detection state
        self._nan_seen: set = set()
        self._overflow_hist: collections.deque = collections.deque(
            maxlen=self.overflow_window)
        self._overflow_active = False
        self._prev_scale: Optional[float] = None
        self._loss_hist: collections.deque = collections.deque(
            maxlen=self.plateau_window)
        self._best_loss: Optional[float] = None
        self._loss_ema: Optional[float] = None   # divergence smoother
        self._best_ema: Optional[float] = None
        self._div_run = 0          # consecutive steps above the bar
        self._diverged = False
        self._plateaued = False
        self._starve_hist: collections.deque = collections.deque(
            maxlen=self.starvation_window)
        self._starving = False
        # serve-side detection state
        self._pool_low = False
        self._evict_hist: collections.deque = collections.deque(
            maxlen=self.eviction_window)
        self._evict_active = False
        self._queue_age_ema: Optional[float] = None
        self._admission_starved = False
        # memory detection state
        self._hbm_high = False
        self._leak_hist: collections.deque = collections.deque(
            maxlen=self.leak_window)
        self._leak_fired = False
        self._recompile_hist: collections.deque = collections.deque(
            maxlen=self.recompile_window)
        self._recompile_active = False
        self._n_steps = 0
        if recorder is not None:
            self.watch(recorder)

    # -- wiring -------------------------------------------------------------
    def watch(self, recorder):
        """Register on ``recorder``'s step stream; returns the recorder
        (so ``monitor.attached(dog.watch(rec))`` composes)."""
        recorder.add_observer(self._on_step)
        self.recorder = recorder
        return recorder

    def unwatch(self):
        if self.recorder is not None:
            self.recorder.remove_observer(self._on_step)
            self.recorder = None

    # -- event emission -----------------------------------------------------
    def _fire(self, rec, name: str, value, diagnosis: str,
              severity: str = "warn", **details) -> dict:
        ev = rec.emit("health_event", name, value, severity=severity,
                      diagnosis=diagnosis, **details)
        # shadow counter: health firings become scrapeable
        # (`apex_health_<name>_total` in the Prometheus exposition)
        rec.counter(f"health/{name}")
        self.events.append(ev)
        if self.on_event is not None:
            try:
                self.on_event(ev)
            except Exception:
                pass
        if name in FLIGHT_DUMP_EVENTS:
            # fatal forecast: dump the black box while the process can
            # still write (no-op unless flight.install() armed dumps)
            try:
                from apex_tpu.monitor import flight as _flight
                _flight.trigger(f"health:{name}")
            except Exception:
                pass
        return ev

    # -- per-step analysis --------------------------------------------------
    def _on_step(self, step_ev: dict, rec):
        self._n_steps += 1
        step = step_ev.get("step")
        gauges = step_ev.get("gauges") or {}

        # 1) non-finite values anywhere on the step record (once/gauge)
        for gname, v in gauges.items():
            if not _finite(v) and gname not in self._nan_seen:
                self._nan_seen.add(gname)
                self._fire(
                    rec, "nan", v if isinstance(v, (int, float)) else None,
                    f"non-finite value in gauge '{gname}' at step {step} "
                    f"({v!r}). A NaN/inf loss or grad norm usually means "
                    "optimizer divergence (lr too high / missing warmup) "
                    "or fp16 overflow with loss scaling disabled — check "
                    "the optim/grad_norm trend and the amp/loss_scale "
                    "history leading up to this step.",
                    severity="error", gauge=gname, step=step)

        # 2) overflow storm: scale halvings / overflow flags in a window
        scale = gauges.get("amp/loss_scale")
        overflow = gauges.get("amp/overflow")
        tripped = bool(overflow) and _finite(overflow) and \
            float(overflow) != 0.0
        if not tripped and scale is not None and _finite(scale) \
                and self._prev_scale is not None and _finite(self._prev_scale):
            tripped = float(scale) < float(self._prev_scale)
        if scale is not None:
            self._prev_scale = scale
        if scale is not None or overflow is not None:
            self._overflow_hist.append(1 if tripped else 0)
            trips = sum(self._overflow_hist)
            if trips >= self.overflow_trips and not self._overflow_active:
                self._overflow_active = True
                self._fire(
                    rec, "overflow_storm", trips,
                    f"loss scale tripped {trips}x in the last "
                    f"{len(self._overflow_hist)} steps (scale now "
                    f"{scale}): gradients are persistently non-finite "
                    "and the dynamic scaler is shrinking instead of "
                    "recovering. Typical causes: lr too high for the "
                    "half dtype, a non-finite input batch, or a "
                    "min_loss_scale floor set too high.",
                    severity="error", step=step, loss_scale=scale,
                    window=len(self._overflow_hist))
            elif trips == 0:
                self._overflow_active = False

        # 3) loss divergence / plateau
        loss = None
        loss_name = None
        for cand in self.loss_gauges:
            if cand in gauges:
                loss, loss_name = gauges[cand], cand
                break
        if loss is not None and _finite(loss):
            loss = float(loss)
            if self._best_loss is None or loss < self._best_loss:
                self._best_loss = loss
            # divergence runs on an EMA of the loss, not the raw value:
            # healthy early training with momentum oscillates (a 1.1 ->
            # 7.7 -> falling overshoot was measured on the simple
            # example), and a spike that decays must not page anyone.
            # Genuine divergence moves the EMA orders of magnitude in a
            # step or two and still fires immediately.
            a = self.divergence_smoothing
            self._loss_ema = loss if self._loss_ema is None else \
                (1.0 - a) * self._loss_ema + a * loss
            if self._best_ema is None or self._loss_ema < self._best_ema:
                self._best_ema = self._loss_ema
                self._div_run = 0
            elif (self._n_steps > self.divergence_grace
                  and self._best_ema > 0
                  and self._loss_ema
                  > self.divergence_factor * self._best_ema):
                self._div_run += 1
                if (self._div_run >= self.divergence_patience
                        and not self._diverged):
                    self._diverged = True
                    self._fire(
                        rec, "loss_divergence", loss,
                        f"'{loss_name}' at step {step}: smoothed loss "
                        f"{self._loss_ema:.4g} >= "
                        f"{self.divergence_factor}x its best "
                        f"{self._best_ema:.4g} for {self._div_run} "
                        "consecutive steps: the run is diverging. Lower "
                        "the learning rate, add warmup, or check the "
                        "grad-norm trend for an exploding layer.",
                        severity="error", step=step, gauge=loss_name,
                        best=self._best_ema)
            else:
                self._div_run = 0
            self._loss_hist.append(loss)
            if (len(self._loss_hist) == self.plateau_window
                    and not self._plateaued and not self._diverged):
                half = self.plateau_window // 2
                hist = list(self._loss_hist)
                a = sum(hist[:half]) / half
                b = sum(hist[half:]) / (len(hist) - half)
                denom = max(abs(a), 1e-12)
                if abs(a - b) / denom < self.plateau_rtol:
                    self._plateaued = True
                    self._fire(
                        rec, "loss_plateau", loss,
                        f"'{loss_name}' flat over the last "
                        f"{self.plateau_window} steps "
                        f"({a:.4g} -> {b:.4g}, relative change < "
                        f"{self.plateau_rtol:g}): training has stalled "
                        "— converged, lr decayed to zero, or the "
                        "optimizer is skipping every step (check "
                        "amp/skipped_steps).",
                        severity="info", step=step, gauge=loss_name)

        # 4) data-loader starvation: host wait as a fraction of step time
        step_s = float(step_ev.get("step_time_s") or 0.0)
        wait = (step_ev.get("timers") or {}).get("data/host_wait")
        if wait is not None and step_s > 0:
            frac = float(wait.get("total_s", 0.0)) / step_s
            self._starve_hist.append(frac)
            if (len(self._starve_hist) == self.starvation_window
                    and min(self._starve_hist) >= self.starvation_fraction):
                if not self._starving:
                    self._starving = True
                    self._fire(
                        rec, "loader_starvation", round(frac, 4),
                        f"data/host_wait took {100 * frac:.0f}% of the "
                        f"step for {self.starvation_window} consecutive "
                        "steps: the accelerator is starving on the "
                        "input pipeline. Raise loader workers/prefetch "
                        "or move transforms off the hot path.",
                        severity="warn", step=step,
                        window=self.starvation_window)
            elif self._starve_hist and self._starve_hist[-1] \
                    < self.starvation_fraction:
                self._starving = False

        self._serve_checks(rec, step, step_ev, gauges)
        self._memory_checks(rec, step, step_ev, gauges)

    # -- memory analysis (the OOM-forecast layer) ---------------------------
    def _memory_checks(self, rec, step, step_ev: dict, gauges: dict):
        """``monitor.memory``'s sampler/snapshot gauges ride ordinary
        step records; these three conditions fire BEFORE an OOM does.
        One early-out on a step with no memory signal."""
        in_use = gauges.get("memory/hbm_bytes_in_use")
        limit = gauges.get("memory/hbm_limit_bytes")
        counters = step_ev.get("counters") or {}
        timers = step_ev.get("timers") or {}
        compiled = bool(counters.get("jax/compile/cache_miss")
                        or "jax/compile/backend" in timers)

        # 1) recompile storm: compile events landing step after step
        # once warmup is over — beyond the wall-clock cost, every
        # retrace's executable and its buffers inflate HBM (the
        # slow-motion OOM the two gauges below then confirm). The
        # tracker runs on EVERY step: a quiet step must push a 0, or
        # sparse one-off compiles across a long run would read as
        # consecutive and fire a false storm.
        if self._n_steps > self.recompile_grace:
            self._recompile_hist.append(1 if compiled else 0)
            trips = sum(self._recompile_hist)
            if trips >= self.recompile_trips \
                    and not self._recompile_active:
                self._recompile_active = True
                self._fire(
                    rec, "recompile_storm", trips,
                    f"jit compiles landed in {trips} of the last "
                    f"{len(self._recompile_hist)} steps (step {step}, "
                    f"after a {self.recompile_grace}-step warmup "
                    "grace): a shape, dtype or static-arg is changing "
                    "every step and XLA is retracing instead of "
                    "reusing — pad to fixed shapes or hoist the "
                    "varying value out of the static args. Each "
                    "retrace also leaks executable + buffer HBM "
                    "(watch memory/hbm_bytes_in_use).",
                    severity="warn", step=step,
                    window=len(self._recompile_hist))
            elif trips == 0:
                self._recompile_active = False

        if in_use is None and limit is None:
            return

        # 2) hbm high water: usage at/above the fraction of the limit —
        # the next allocation spike (a retrace, a bigger batch, a
        # fragmentation miss) OOMs. Hysteresis re-arm at 90% of the bar.
        if in_use is not None and limit and _finite(in_use) \
                and _finite(limit):
            frac = float(in_use) / float(limit)
            if frac >= self.hbm_high_water_fraction:
                if not self._hbm_high:
                    self._hbm_high = True
                    self._fire(
                        rec, "hbm_high_water", round(frac, 4),
                        f"HBM at {100 * frac:.0f}% of the device limit "
                        f"at step {step} ({int(in_use)}/{int(limit)} "
                        f"bytes, bar "
                        f"{100 * self.hbm_high_water_fraction:.0f}%): "
                        "the next allocation spike OOMs. Shrink the "
                        "batch/activation footprint (remat, ZeRO "
                        "shard_params, fp8-KV) or move state off-chip "
                        "before the allocator does it for you with a "
                        "crash.",
                        severity="error", step=step,
                        bytes_in_use=int(in_use), limit_bytes=int(limit))
            elif frac < 0.9 * self.hbm_high_water_fraction:
                self._hbm_high = False        # hysteresis: re-arm

        # 3) memory leak: positive least-squares slope over a FULL
        # sliding window of the step byte gauge, with the predicted
        # growth over the window at least ``leak_rel_threshold`` of the
        # window mean — a flat footprint (slope ~0) and ordinary
        # sample noise never fire (the false-positive guard).
        if in_use is not None and _finite(in_use):
            self._leak_hist.append(float(in_use))
            if (len(self._leak_hist) == self.leak_window
                    and not self._leak_fired):
                ys = list(self._leak_hist)
                n = len(ys)
                xbar = (n - 1) / 2.0
                ybar = sum(ys) / n
                denom = sum((i - xbar) ** 2 for i in range(n))
                slope = sum((i - xbar) * (y - ybar)
                            for i, y in enumerate(ys)) / denom
                growth = slope * (n - 1)
                if slope > 0 and ybar > 0 \
                        and growth >= self.leak_rel_threshold * ybar:
                    self._leak_fired = True
                    self._fire(
                        rec, "memory_leak", round(slope, 2),
                        f"memory/hbm_bytes_in_use grew "
                        f"~{int(growth)} bytes over the last {n} steps "
                        f"({100 * growth / ybar:.1f}% of the mean "
                        f"footprint, slope {slope:.0f} B/step) at step "
                        f"{step}: something is accumulating per step — "
                        "a python-side list of device arrays, an "
                        "unbounded cache, or a new executable per step "
                        "(check recompile_storm). At this rate the "
                        "high-water bar is a matter of steps.",
                        severity="warn", step=step,
                        growth_bytes=int(growth), window=n)

    # -- serve-side analysis ------------------------------------------------
    def _serve_checks(self, rec, step, step_ev: dict, gauges: dict):
        """The serve engine's per-round gauges/counters ride ordinary
        step records (``ServeEngine.step``), so serve health reuses the
        training observer verbatim. One early-out on a non-serve step
        record."""
        free = gauges.get("serve/pages_free")
        total = gauges.get("serve/pages_total")
        if free is None and total is None \
                and "serve/preemptions" not in (step_ev.get("counters")
                                                or {}) \
                and "serve/queue_wait_oldest_s" not in gauges:
            return

        # 1) kv pool exhaustion: the free list at/below the threshold
        # fraction of the pool — the allocator is about to start
        # evicting on every growth/admission
        if free is not None and total and _finite(free) and _finite(total):
            frac = float(free) / float(total)
            if frac <= self.kv_pool_min_free_fraction:
                if not self._pool_low:
                    self._pool_low = True
                    self._fire(
                        rec, "kv_pool_exhaustion", round(frac, 4),
                        f"KV page pool nearly exhausted at step {step}: "
                        f"{int(free)}/{int(total)} pages free "
                        f"({100 * frac:.0f}% <= "
                        f"{100 * self.kv_pool_min_free_fraction:.0f}% "
                        "threshold). Growth and admission are about to "
                        "preempt running sequences — grow num_pages, "
                        "shrink page_size tail waste, or enable fp8-KV "
                        "(~2x pages at the same HBM).",
                        severity="warn", step=step,
                        pages_free=int(free), pages_total=int(total))
            elif frac > 2.0 * self.kv_pool_min_free_fraction:
                self._pool_low = False        # hysteresis: re-arm

        # 2) eviction storm: preemptions in too many of the last N
        # steps — the pool thrashes (each admission evicts a sequence
        # whose recompute re-evicts the next; throughput collapses to
        # re-prefill work)
        pre = (step_ev.get("counters") or {}).get("serve/preemptions", 0)
        if free is not None or pre:
            self._evict_hist.append(1 if pre else 0)
            trips = sum(self._evict_hist)
            if trips >= self.eviction_trips and not self._evict_active:
                self._evict_active = True
                self._fire(
                    rec, "eviction_storm", trips,
                    f"preemptions fired in {trips} of the last "
                    f"{len(self._evict_hist)} serve steps (step {step})"
                    ": the page pool is thrashing — evicted sequences "
                    "recompute their caches only to evict the next. "
                    "Tokens/sec is now dominated by re-prefill; grow "
                    "the pool or lower max_batch.",
                    severity="error", step=step,
                    window=len(self._evict_hist))
            elif trips == 0:
                self._evict_active = False

        # 3) admission starvation: the oldest waiting request's age,
        # EMA-smoothed so one slow admission round does not page anyone
        age = gauges.get("serve/queue_wait_oldest_s")
        if age is not None and _finite(age):
            a = self.admission_smoothing
            age = float(age)
            self._queue_age_ema = age if self._queue_age_ema is None \
                else (1.0 - a) * self._queue_age_ema + a * age
            if self._queue_age_ema >= self.admission_age_s:
                if not self._admission_starved:
                    self._admission_starved = True
                    self._fire(
                        rec, "admission_starvation",
                        round(self._queue_age_ema, 3),
                        f"oldest waiting request has been queued "
                        f"~{self._queue_age_ema:.1f}s (EMA) at step "
                        f"{step}, over the {self.admission_age_s:g}s "
                        "bar: FCFS admission cannot place the queue "
                        "head — the pool or the batch slots are too "
                        "small for the offered traffic.",
                        severity="warn", step=step,
                        age_ema_s=round(self._queue_age_ema, 3))
            elif self._queue_age_ema < 0.5 * self.admission_age_s:
                self._admission_starved = False

    # -- cross-host ---------------------------------------------------------
    def check_cross_host(self, merged: dict, recorder=None) -> list[dict]:
        """Scan a ``merge`` cross-host view for straggler ranks: any
        rank whose median step time exceeds ``straggler_ratio`` x the
        global median. Emits one ``straggler`` health_event per flagged
        rank into ``recorder`` (default: the watched recorder) and
        returns the events. Host-wait stragglers (per-timer
        ``max_over_median``) are reported on the same event."""
        rec = recorder if recorder is not None else self.recorder
        events = []
        skew = (merged.get("steps") or {}).get("skew") or {}
        ratios = skew.get("per_rank_ratio") or {}
        waits = (merged.get("timers") or {}).get("data/host_wait") or {}
        for rank, ratio in sorted(ratios.items()):
            if ratio is None or ratio < self.straggler_ratio:
                continue
            diag = (f"rank {rank} median step time is {ratio}x the "
                    f"global median ({skew.get('median_step_time_s')}s)"
                    ": straggler rank — slow host, contended NIC, or an "
                    "input-pipeline stall on that host.")
            wait_row = (waits.get("by_rank") or {}).get(str(rank))
            if wait_row is not None and waits.get("slowest_rank") is not None \
                    and str(waits["slowest_rank"]) == str(rank):
                diag += (" Its data/host_wait mean is also the run's max "
                         f"({wait_row.get('mean_s')}s) — the input "
                         "pipeline is the likely cause.")
            details = {"rank": int(rank), "ratio": ratio, "severity": "warn",
                       "diagnosis": diag}
            if rec is not None:
                events.append(self._fire(
                    rec, "straggler", ratio, diag, severity="warn",
                    rank=int(rank)))
            else:
                ev = {"kind": "health_event", "name": "straggler",
                      "value": ratio, **details}
                self.events.append(ev)
                events.append(ev)
                if self.on_event is not None:
                    try:
                        self.on_event(ev)
                    except Exception:
                        pass
        return events

    # -- diagnostics --------------------------------------------------------
    def diagnostics_bundle(self, k: Optional[int] = None) -> dict:
        """Snapshot for post-mortems: the last-K step records, current
        gauges/counters, every health event so far, the scaler state
        summary (when a scaler was registered), and a per-device memory
        snapshot (best-effort; empty off-accelerator)."""
        k = self.diagnostics_steps if k is None else int(k)
        bundle: dict = {"health_events": list(self.events)}
        rec = self.recorder
        if rec is not None:
            bundle["last_steps"] = rec.steps()[-k:]
            bundle["gauges"] = rec.gauges()
            bundle["counters"] = rec.counters()
        if self.scaler is not None:
            try:
                bundle["scaler"] = self.scaler.state_summary()
            except Exception:
                pass
        try:
            from apex_tpu.monitor import memory as _memory
            bundle["device_memory"] = _memory.device_memory_snapshot()
        except Exception:
            bundle["device_memory"] = []
        return bundle
