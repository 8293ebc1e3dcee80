"""Typed-event ring-buffer recorder.

The Recorder is the single sink for everything the instrumentation
hooks emit: host counters and gauges, timers, trace-time collective
accounting, device scalars arriving through ``jax.debug.callback``, and
per-step records assembled by the ``step()`` context manager. It is
deliberately zero-dependency — pure stdlib, no jax import — so it can
run in data-loader worker threads and in processes that never touch an
accelerator.

Event model (one dict per event, JSONL-serializable):

- ``counter``   {name, value=increment, total}   monotonic accumulators
- ``gauge``     {name, value}                    last-value-wins samples
- ``timer``     {name, value=seconds}            measured durations
- ``collective``{name="op@axis", value=count, bytes} trace-time accounting
- ``step``      {step, value=step_time_s, gauges, counters, collectives,
                 timers}                          one per training step
- ``histogram`` {name, value=count, counts, ...}  cumulative snapshot of
                 a :meth:`observe` log-scale histogram (O(1) memory; no
                 per-sample events)
- ``span_start``/``span_end``/``span_event``      request-level span
                 tracing (:mod:`apex_tpu.monitor.spans`)

Events live in a bounded ring (``capacity`` newest kept; ``dropped``
counts evictions), so a recorder attached for a million steps holds
memory constant. Aggregation (:meth:`aggregate`) and the CLI report
(``python -m apex_tpu.monitor report``) consume the JSONL dump.

Crash resilience: pass ``stream=<path or file>`` and every event is
ALSO appended to that file as one JSON line the moment it is emitted
(write + flush, so the line survives the process being killed). A run
that times out or crashes mid-step leaves a parseable JSONL holding
everything recorded up to the kill — this is what ``dump_shard``
rank-tagged shards use on multi-host runs.

Observers: :meth:`add_observer` registers a host callback invoked with
every closed ``step`` record — the hook :class:`~apex_tpu.monitor.
health.Watchdog` uses to analyze the stream online without polling.
Observer exceptions are swallowed (telemetry must never kill training).

Threading: hooks may fire from loader worker threads and from runtime
callback threads; all mutation happens under one lock.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import sys
import threading
import time
from typing import Any, Callable, Iterable, Optional


def json_safe(obj):
    """Recursively replace non-finite floats with their string form
    ("NaN"/"Infinity"/"-Infinity"). Bare ``json.dumps`` emits literal
    ``NaN`` tokens — invalid strict JSON that jq/JSON.parse-style
    drivers reject — on exactly the runs the watchdog exists for (a
    NaN loss gauge). Strings keep the information and stay parseable;
    ``float("NaN")`` round-trips for consumers that want the value."""
    if isinstance(obj, float):
        if obj != obj:
            return "NaN"
        if obj in (float("inf"), float("-inf")):
            return "Infinity" if obj > 0 else "-Infinity"
        return obj
    if isinstance(obj, dict):
        return {k: json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [json_safe(v) for v in obj]
    return obj


def json_line(obj) -> str:
    """One strict-JSON line for an event dict (non-finite-safe)."""
    return json.dumps(json_safe(obj))


def _effects_barrier():
    """Drain pending jax debug callbacks so device scalars land in the
    step record that produced them. Guarded on jax being imported —
    never the importer of it."""
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            jax.effects_barrier()
        except Exception:
            pass


class Recorder:
    """Collects typed telemetry events into a bounded ring buffer.

    Typical lifecycle::

        rec = monitor.Recorder()
        with monitor.attached(rec):          # enables the package hooks
            for batch in loader:
                with rec.step():             # one per-step record
                    out = train_step(...)
        rec.dump_jsonl("run.jsonl")
        print(monitor.render_report(rec.records()))

    All emit methods are also callable directly (without any hook
    involvement) for user-level metrics.
    """

    def __init__(self, capacity: int = 65536, name: str = "run",
                 meta: Optional[dict] = None, traced_hooks: bool = True,
                 stream=None, stream_mode: str = "w"):
        self.name = name
        self.capacity = int(capacity)
        self.meta = dict(meta or {})
        # traced_hooks=False makes this a host-only observer: the traced
        # hook family (traced_scalar/traced_tick/collective/schedule and
        # the optimizer norm gauges) stays dormant, so compiled programs
        # are untouched while host timers and compile events still land:
        # what a benchmark attaches to time UNperturbed programs.
        self.traced_hooks = bool(traced_hooks)
        self._events: collections.deque = collections.deque(
            maxlen=self.capacity)
        self._emitted = 0              # lifetime count (ring may evict)
        self._counters: dict[str, float] = {}
        self._gauges: dict[str, float] = {}
        self._histograms: dict[str, Any] = {}     # name -> LogHistogram
        self._collectives: dict[str, dict] = {}   # "op@axis" -> {count, bytes}
        self._lock = threading.RLock()
        self._step_idx = 0
        self._open_step: Optional[dict] = None
        self._observers: list[Callable] = []
        self._t0 = time.perf_counter()
        # incremental-flush stream: every event is appended + flushed as
        # it is emitted, so a killed process leaves a parseable JSONL of
        # everything recorded so far (module docstring)
        self._stream = None
        self._stream_owned = False
        if stream is not None:
            if hasattr(stream, "write"):
                self._stream = stream
            else:
                self._stream = open(stream, stream_mode)
                self._stream_owned = True
            self._stream_write({"kind": "header", "name": self.name,
                                "capacity": self.capacity, "dropped": 0,
                                "meta": self.meta})

    # -- internals ---------------------------------------------------------
    def _stream_write(self, ev: dict):
        f = self._stream
        if f is None:
            return
        try:
            f.write(json_line(ev) + "\n")
            f.flush()
        except Exception:
            pass   # telemetry must never kill the run

    def close(self):
        """Close an owned stream file (no-op otherwise)."""
        with self._lock:
            f, self._stream = self._stream, None
            owned, self._stream_owned = self._stream_owned, False
        if f is not None and owned:
            try:
                f.close()
            except Exception:
                pass

    def add_observer(self, fn: Callable) -> Callable:
        """Register ``fn(step_event, recorder)`` to run (on the host, in
        the stepping thread) every time a ``step`` record closes. Errors
        raised by observers are swallowed."""
        with self._lock:
            if fn not in self._observers:
                self._observers.append(fn)
        return fn

    def remove_observer(self, fn: Callable):
        with self._lock:
            if fn in self._observers:
                self._observers.remove(fn)

    def _emit(self, kind: str, name: str, value, **extra) -> dict:
        ev = {"kind": kind, "name": name, "value": value,
              "t": round(time.perf_counter() - self._t0, 6)}
        if extra:
            ev.update(extra)
        with self._lock:
            if self._open_step is not None:
                ev["step"] = self._open_step["step"]
            self._events.append(ev)
            self._emitted += 1
            self._stream_write(ev)
        return ev

    def emit(self, kind: str, name: str, value, **extra) -> dict:
        """Record a custom typed event (user-defined ``kind``). The
        event rides the ring, the JSONL dump, and — when streaming — is
        flushed to disk immediately (health events, a caller's own
        kinds)."""
        return self._emit(kind, name, value, **extra)

    @property
    def dropped(self) -> int:
        """Events evicted from the ring so far."""
        with self._lock:
            return self._emitted - len(self._events)

    # -- host-side primitives ----------------------------------------------
    def counter(self, name: str, inc: float = 1, **extra) -> float:
        with self._lock:
            total = self._counters.get(name, 0) + inc
            self._counters[name] = total
            step = self._open_step
            if step is not None:
                step["counters"][name] = step["counters"].get(name, 0) + inc
        self._emit("counter", name, inc, total=total, **extra)
        return total

    def gauge(self, name: str, value, **extra):
        value = float(value)
        with self._lock:
            self._gauges[name] = value
            step = self._open_step
            if step is not None:
                step["gauges"][name] = value
        self._emit("gauge", name, value, **extra)

    def observe(self, name: str, value, *, n: int = 1, lo: float = None,
                hi: float = None, buckets_per_decade: int = None):
        """Record one sample (``n`` of one value: a decode round's rows)
        into the named fixed-bucket log-scale histogram
        (:class:`~apex_tpu.monitor.spans.LogHistogram`).

        Deliberately NOT one event per sample: the histogram state is
        O(1) memory and the stream stays O(1) traffic under sustained
        serving — percentiles (p50/p95/p99) stay queryable for the
        whole run. Snapshots ride the ring/stream as ``histogram``
        events via :meth:`emit_histograms` (called by the serve engine
        at drain) and are appended automatically by
        :meth:`dump_jsonl`/:meth:`aggregate`. The bucket-range kwargs
        apply only on the FIRST observation of a name."""
        from apex_tpu.monitor.spans import LogHistogram
        value = float(value)
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                kw = {}
                if lo is not None:
                    kw["lo"] = lo
                if hi is not None:
                    kw["hi"] = hi
                if buckets_per_decade is not None:
                    kw["buckets_per_decade"] = buckets_per_decade
                h = self._histograms[name] = LogHistogram(**kw)
            h.record(value, n)

    def histograms(self) -> dict:
        """Live ``name -> LogHistogram`` map (the objects themselves;
        callers wanting a stable view should use their snapshots)."""
        with self._lock:
            return dict(self._histograms)

    def _histogram_events(self) -> list[dict]:
        """Fresh cumulative ``histogram`` snapshot events (not stored
        in the ring) — appended to dumps and aggregates so histograms
        survive the JSONL round trip."""
        with self._lock:
            snaps = {k: h.snapshot() for k, h in self._histograms.items()}
        return [{"kind": "histogram", "name": k, "value": snap["count"],
                 **{kk: vv for kk, vv in snap.items() if kk != "count"}}
                for k, snap in sorted(snaps.items())]

    def emit_histograms(self):
        """Flush one cumulative ``histogram`` snapshot event per
        observed histogram into the ring (and the stream, when
        streaming) — crash-resilient persistence for long runs; safe to
        call repeatedly (snapshots are cumulative, last one wins)."""
        for ev in self._histogram_events():
            self._emit(ev.pop("kind"), ev.pop("name"), ev.pop("value"),
                       **ev)

    def timer_event(self, name: str, seconds: float, **extra):
        with self._lock:
            step = self._open_step
            if step is not None:
                t = step["timers"].setdefault(name, {"n": 0, "total_s": 0.0})
                t["n"] += 1
                t["total_s"] = round(t["total_s"] + seconds, 6)
        with self._lock:
            self._counters[name + "/total_s"] = round(
                self._counters.get(name + "/total_s", 0.0) + seconds, 6)
        self._emit("timer", name, round(seconds, 6), **extra)

    @contextlib.contextmanager
    def timer(self, name: str, **extra):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.timer_event(name, time.perf_counter() - t0, **extra)

    def collective(self, op: str, axis_name: str, nbytes: int = 0,
                   count: int = 1):
        """Trace-time collective accounting: called by the mapping/DDP
        hooks while a program is being traced, so totals are per traced
        program, not per executed step (XLA runs the same collectives
        every step; re-tracing re-counts)."""
        key = f"{op}@{axis_name}"
        with self._lock:
            slot = self._collectives.setdefault(
                key, {"count": 0, "bytes": 0})
            slot["count"] += int(count)
            slot["bytes"] += int(nbytes)
        self._emit("collective", key, int(count), bytes=int(nbytes))

    # -- device-side arrivals (jax.debug.callback target) -------------------
    def _device_scalar(self, name: str, value):
        """Target of the traced-scalar hooks; runs on the host when the
        device value is materialized. Behaves like a gauge."""
        try:
            self.gauge(name, float(value))
        except (TypeError, ValueError):
            pass

    def _device_tick(self, name: str, tick):
        """Target of per-tick schedule marks: records host-arrival time
        of pipeline tick ``tick`` (an ordering/progress signal; device
        step attribution belongs to XProf)."""
        try:
            self._emit("tick", name, int(tick))
        except (TypeError, ValueError):
            pass

    def _device_tick_marks(self, name: str, tick, rank, slots: dict):
        """Target of the measured slot-occupancy marks
        (``hooks.traced_tick_marks``): one event per (tick, rank) with
        the boolean validity of every unit slot the tick executed —
        the raw material of the per-rank pipeline utilization table
        (``report.aggregate()['pipeline_utilization']``)."""
        try:
            self._emit("tick_mark", name, int(tick), rank=int(rank),
                       slots={k: bool(v) for k, v in slots.items()})
        except (TypeError, ValueError):
            pass

    # -- per-step records ---------------------------------------------------
    @contextlib.contextmanager
    def step(self, **meta):
        """Open a per-step record; on exit, drains pending device
        callbacks and appends a ``step`` event carrying the step wall
        time plus every gauge/counter/timer observed during the step and
        the cumulative collective table."""
        with self._lock:
            idx = self._step_idx
            self._step_idx += 1
            self._open_step = {"step": idx, "gauges": {}, "counters": {},
                               "timers": {}}
        t0 = time.perf_counter()
        try:
            yield idx
        finally:
            _effects_barrier()
            dur = time.perf_counter() - t0
            with self._lock:
                rec = self._open_step
                self._open_step = None
                collectives = {k: dict(v)
                               for k, v in self._collectives.items()}
            ev = {"kind": "step", "name": "step", "step": rec["step"],
                  "value": round(dur, 6), "step_time_s": round(dur, 6),
                  "t": round(t0 - self._t0, 6),
                  "gauges": rec["gauges"], "counters": rec["counters"],
                  "timers": rec["timers"], "collectives": collectives}
            if meta:
                ev["meta"] = {k: v for k, v in meta.items()}
            with self._lock:
                self._events.append(ev)
                self._emitted += 1
                self._stream_write(ev)
                observers = list(self._observers)
            for obs in observers:
                try:
                    obs(ev, self)
                except Exception:
                    pass   # a watchdog bug must not kill the training loop

    # -- views ---------------------------------------------------------------
    def records(self, kind: Optional[str] = None) -> list[dict]:
        with self._lock:
            evs = list(self._events)
        if kind is None:
            return evs
        return [e for e in evs if e["kind"] == kind]

    def steps(self) -> list[dict]:
        return self.records("step")

    def counters(self) -> dict[str, float]:
        with self._lock:
            return dict(self._counters)

    def gauges(self) -> dict[str, float]:
        with self._lock:
            return dict(self._gauges)

    def collectives(self) -> dict[str, dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._collectives.items()}

    # -- output --------------------------------------------------------------
    def dump_jsonl(self, path_or_file) -> int:
        """Write one JSON object per event (newest ``capacity`` events);
        first line is a header record. Returns the number of event lines
        written. Path writes are atomic (tmp + fsync + rename): a kill
        arriving mid-dump leaves the previous complete file or none,
        never a torn shard the merge CLI chokes on."""
        _effects_barrier()
        from apex_tpu.monitor.spans import open_spans
        header = {"kind": "header", "name": self.name,
                  "capacity": self.capacity, "dropped": self.dropped,
                  "open_spans": open_spans(), "meta": self.meta}
        evs = self.records() + self._histogram_events()

        def _write(f):
            f.write(json_line(header) + "\n")
            for e in evs:
                f.write(json_line(e) + "\n")

        if hasattr(path_or_file, "write"):
            _write(path_or_file)
            return len(evs)
        path = os.fspath(path_or_file)
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            with open(tmp, "w") as f:
                _write(f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except Exception:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return len(evs)

    def aggregate(self) -> dict:
        """Aggregated summary (the JSON the CLI report renders)."""
        from apex_tpu.monitor.report import aggregate
        _effects_barrier()
        return aggregate(self.records() + self._histogram_events(),
                         header={"name": self.name, "dropped": self.dropped,
                                 "meta": self.meta})
