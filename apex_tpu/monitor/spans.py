"""Request-level span tracing + fixed-bucket log-scale histograms.

The per-request evidence layer for ``apex_tpu.serve`` (and anything
else with a request-shaped lifecycle): typed ``span_start``/``span_end``
events with parent links, plus :class:`LogHistogram` — the O(1)-memory
streaming-percentile structure the serve SLO numbers (p50/p95/p99 token
latency, TTFT, queue wait) are computed from under sustained traffic.

Design rules (the monitor purity contract, serve-grade):

- **host-clock only, zero jax in the hot path**: a span is two
  ``time.perf_counter`` reads and two recorder events; nothing here
  imports jax, inserts ops, or touches traced code. A jitted program
  traced with spans active is byte-identical to one traced without
  (asserted by ``tests/test_serve_telemetry.py``).
- **two sinks, one clock**: with a recorder attached and jax already
  imported, a block-shaped :func:`span` also enters a
  ``jax.profiler.TraceAnnotation`` of the same name carrying the span
  id (stat ``span``), so under a profiler session the span is an event
  on the ``/host:CPU`` plane of the same ``.xplane.pb`` as the device
  operations. The id in both sinks is the clock link: the median of
  (annotation start - recorder start) over the joined spans places any
  recorder event on the profiler's time base. :func:`start`/:func:`end`
  spans that outlive a block (``serve/request``, ``serve/queue_wait``)
  stay recorder-only.
- **detached = free**: every entry point's first action is one global
  read; with no recorder attached :func:`start` returns ``None`` and
  :func:`end`/:func:`annotate` on ``None`` return immediately — no id
  allocation, no event, no lock.
- **parent links, not thread context, carry request identity**: a
  request span outlives any one engine step (queue-wait → prefill →
  decode → preempt → re-admit can spread over thousands of steps), so
  callers hold span ids explicitly (``Sequence.span``) and pass
  ``parent=``. The :func:`span` context manager additionally keeps a
  thread-local stack for implicit nesting of block-shaped spans.

Event schema (one JSONL line each, riding the Recorder ring/stream):

- ``span_start`` {name, value=span_id, parent, **attrs}
- ``span_end``   {name, value=duration_s, span=span_id, parent, **attrs}
  (exception unwind adds ``error=<type name>``)
- ``span_event`` {name, value=span_id-or-None, **attrs} — point
  annotations (preempt/evict/re-admit transitions)

``report.aggregate()`` folds ``serve/request`` span ends into the
per-request table and ``histogram`` snapshot events into the SLO block;
``monitor.export`` renders the same histograms in Prometheus exposition
format.
"""

from __future__ import annotations

import contextlib
import math
import sys
import threading
import time
from typing import Optional

from apex_tpu.monitor import _state

_lock = threading.Lock()
_next_id = 1
# open spans: span_id -> (name, parent, t0). Entries are removed on
# end(); a span whose recorder detached mid-flight is removed silently.
_open: dict = {}
_local = threading.local()


def _nesting_stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def start(name: str, parent: Optional[int] = None, **attrs) -> Optional[int]:
    """Open a span; returns its id, or ``None`` when monitoring is
    detached (making every later ``end(None)`` a free no-op)."""
    rec = _state.recorder
    if rec is None:
        return None
    global _next_id
    with _lock:
        sid = _next_id
        _next_id += 1
        _open[sid] = (name, parent, time.perf_counter())
    rec.emit("span_start", name, sid, parent=parent, **attrs)
    return sid


def end(span_id: Optional[int], **attrs) -> Optional[float]:
    """Close span ``span_id``; emits ``span_end`` with the measured
    duration and returns it (``None`` for a no-op close)."""
    if span_id is None:
        return None
    with _lock:
        entry = _open.pop(span_id, None)
    if entry is None:
        return None
    name, parent, t0 = entry
    dur = time.perf_counter() - t0
    rec = _state.recorder
    if rec is not None:
        rec.emit("span_end", name, round(dur, 6), span=span_id,
                 parent=parent, **attrs)
    return dur


def annotate(name: str, span: Optional[int] = None, **attrs):
    """Point annotation (a state transition, not a duration): one
    ``span_event`` record linked to ``span``."""
    rec = _state.recorder
    if rec is not None:
        rec.emit("span_event", name, span, **attrs)


@contextlib.contextmanager
def span(name: str, parent: Optional[int] = None, **attrs):
    """Block-shaped span. Nests implicitly: with no explicit
    ``parent``, the innermost open :func:`span` on this thread is the
    parent. An exception unwinds the span with ``error=<type name>``
    before re-raising. Attached, the block also runs inside a profiler
    annotation ``name`` with the stat ``span=<id>`` (module docstring);
    detached it is one global read: no id, no event, no annotation."""
    st = _nesting_stack()
    if parent is None and st:
        parent = st[-1]
    sid = start(name, parent=parent, **attrs)
    if sid is None:
        yield None
        return
    jax = sys.modules.get("jax")
    st.append(sid)
    try:
        with (jax.profiler.TraceAnnotation(name, span=sid)
              if jax is not None else contextlib.nullcontext()):
            yield sid
    except BaseException as e:
        end(sid, error=type(e).__name__)
        raise
    else:
        end(sid)
    finally:
        if st and st[-1] == sid:
            st.pop()


def open_spans() -> int:
    """Spans started but not yet ended (leak/debug accessor)."""
    with _lock:
        return len(_open)


# ---------------------------------------------------------------------------
# fixed-bucket log-scale histogram: O(1) memory streaming percentiles
# ---------------------------------------------------------------------------

class LogHistogram:
    """Streaming histogram over geometrically-spaced buckets.

    ``buckets_per_decade`` fixes the resolution: bucket ``i`` covers
    ``[lo * 10^(i/bpd), lo * 10^((i+1)/bpd))``, so a percentile
    estimate (the geometric midpoint of the bucket holding the
    nearest-rank sample) is within a factor ``10^(1/(2*bpd))`` of the
    exact sample — ~12% relative at the default ``bpd=10``, asserted
    by ``tests/test_spans.py``. Memory is the fixed bucket array no
    matter how many samples arrive: the serve engine can observe a
    token latency per generated token for days without growing.

    Values ``<= 0`` or below ``lo`` land in the underflow bin (reported
    at the observed min), values ``>= hi`` in the overflow bin
    (reported at the observed max); exact ``count``/``sum``/``min``/
    ``max`` are tracked alongside. Defaults suit millisecond latencies:
    1e-3 ms (1 us) .. 1e7 ms (~2.8 h).
    """

    def __init__(self, lo: float = 1e-3, hi: float = 1e7,
                 buckets_per_decade: int = 10):
        if not (0 < lo < hi):
            raise ValueError(f"need 0 < lo < hi, got ({lo}, {hi})")
        self.lo = float(lo)
        self.hi = float(hi)
        self.bpd = int(buckets_per_decade)
        if self.bpd < 1:
            raise ValueError("buckets_per_decade must be >= 1")
        self.n_buckets = int(math.ceil(
            round(math.log10(self.hi / self.lo), 9) * self.bpd))
        self._counts = [0] * self.n_buckets
        self.underflow = 0
        self.overflow = 0
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None

    def bucket_bounds(self, i: int) -> tuple:
        return (self.lo * 10.0 ** (i / self.bpd),
                self.lo * 10.0 ** ((i + 1) / self.bpd))

    def record(self, value, n: int = 1) -> None:
        """``n`` samples of one value (a decode round's rows share its
        latency): what ``n`` calls would leave, in one."""
        v = float(value)
        self.count += n
        self.sum += n * v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)
        if v < self.lo:                       # incl. v <= 0
            self.underflow += n
        elif v >= self.hi:
            self.overflow += n
        else:
            i = int(math.log10(v / self.lo) * self.bpd)
            # float rounding at an exact bucket edge can land one off
            i = min(max(i, 0), self.n_buckets - 1)
            blo, bhi = self.bucket_bounds(i)
            if v < blo:
                i -= 1
            elif v >= bhi:
                i += 1
            self._counts[min(max(i, 0), self.n_buckets - 1)] += n

    def percentile(self, p: float) -> Optional[float]:
        """Nearest-rank percentile estimate (geometric bucket midpoint,
        clipped to the exact observed [min, max])."""
        if self.count == 0:
            return None
        rank = max(1, int(math.ceil(p / 100.0 * self.count)))
        cum = self.underflow
        if rank <= cum:
            return self.min
        for i, c in enumerate(self._counts):
            cum += c
            if rank <= cum:
                blo, bhi = self.bucket_bounds(i)
                est = math.sqrt(blo * bhi)
                return min(max(est, self.min), self.max)
        return self.max                        # overflow bin

    @property
    def mean(self) -> Optional[float]:
        return self.sum / self.count if self.count else None

    # -- (de)serialization: the `histogram` event payload -------------
    def snapshot(self) -> dict:
        """Cumulative JSONL-safe snapshot (sparse bucket counts)."""
        return {"lo": self.lo, "hi": self.hi,
                "buckets_per_decade": self.bpd,
                "count": self.count, "sum": round(self.sum, 6),
                "min": self.min, "max": self.max,
                "underflow": self.underflow, "overflow": self.overflow,
                "counts": {str(i): c for i, c in enumerate(self._counts)
                           if c}}

    @classmethod
    def from_snapshot(cls, snap: dict) -> "LogHistogram":
        h = cls(lo=float(snap["lo"]), hi=float(snap["hi"]),
                buckets_per_decade=int(snap["buckets_per_decade"]))
        h.count = int(snap.get("count", 0))
        h.sum = float(snap.get("sum", 0.0))
        h.min = snap.get("min")
        h.max = snap.get("max")
        h.underflow = int(snap.get("underflow", 0))
        h.overflow = int(snap.get("overflow", 0))
        for i, c in (snap.get("counts") or {}).items():
            h._counts[int(i)] = int(c)
        return h


def hist_summary(snap: dict, percentiles=(50, 95, 99)) -> dict:
    """Percentile summary of a :meth:`LogHistogram.snapshot` payload
    (the shape ``report.aggregate()`` embeds per histogram)."""
    h = LogHistogram.from_snapshot(snap)
    out = {"count": h.count, "mean": round(h.mean, 6) if h.count else None,
           "min": h.min, "max": h.max}
    for p in percentiles:
        v = h.percentile(p)
        out[f"p{p}"] = round(v, 6) if v is not None else None
    return out
