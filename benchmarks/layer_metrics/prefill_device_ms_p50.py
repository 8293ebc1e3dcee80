"""Median device time of the engine's prefill program (one padded prompt)
in the traced sub-window: the ``XLA Modules`` events the family names under
``programs["prefill"]``. The program's own ``serve/prefill`` span closes at
dispatch, before the work is done, and is not read (PERF.md section 6)."""

from benchmarks.harness import trace_reduce
from benchmarks.harness.stats import median


def compute(run):
    pattern = getattr(run["program"], "programs", {}).get("prefill")
    if run["trace"] is None or not pattern:
        return None
    ts = trace_reduce.module_seconds(run["trace"], pattern)
    return 1e3 * median(ts) if ts else None
