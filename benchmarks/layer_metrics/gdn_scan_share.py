"""Device time of the gated delta rule itself over the operations' busy
time in the traced steps: operations under ``apx:gdn_scan``
(``ops/gated_delta.py``: the four kernels and the little XLA between them:
the cumulated decays, padding and reshapes), forward, recomputed and
backward, without the projections, the convolution and the norm around
it."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.scope_share(run, "gdn_scan")
