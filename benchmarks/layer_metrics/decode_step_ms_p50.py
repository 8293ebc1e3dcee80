"""Median of ``ServeEngine.decode_step_times`` inside the window: host clock
around one batched decode call and the fetch of its tokens."""

from benchmarks.harness.stats import median


def compute(run):
    ts = run.get("decode_step_times")
    return 1e3 * median(ts) if ts else None
