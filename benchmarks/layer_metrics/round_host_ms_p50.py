"""Median, over the window's engine rounds, of the host's time outside the
dispatching spans: a ``serve/round`` span's duration minus its
``serve/prefill`` and ``serve/decode_step`` children
(``serve/engine.py:_step_inner``): schedule, batch build and uploads,
per-sequence bookkeeping, gauges. It is the part of the device's idle time
the engine's own host work causes; the rest lies inside the two dispatching
spans, between a span's opening and the dispatch's arrival
(``notes.idle_by_span``), and ``device_idle_share.serve`` is the whole."""

from benchmarks.harness import span_reduce
from benchmarks.harness.stats import median


def compute(run):
    spans = span_reduce.window_spans(run, "serve/round")
    ts = span_reduce.round_host_s(spans) if spans else None
    return 1e3 * median(ts) if ts else None
