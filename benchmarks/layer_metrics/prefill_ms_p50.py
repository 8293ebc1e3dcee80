"""Median of the window's ``serve/prefill`` spans
(``serve/engine.py:_do_prefill``): dispatch of one padded prompt to its
first token on the host. A resumed prefill fetches no token and is left
out."""

from benchmarks.harness import span_reduce
from benchmarks.harness.stats import median


def compute(run):
    spans = span_reduce.window_spans(run, "serve/prefill")
    ts = [s["dur"] for s in spans or []
          if s["name"] == "serve/prefill" and not s.get("resumed")]
    return 1e3 * median(ts) if ts else None
