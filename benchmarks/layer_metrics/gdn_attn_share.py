"""Device time of the gated-delta sub-layers over the operations' busy
time in the traced steps: operations under ``apx:attn_gdn``
(``models/qwen3_next.py``: the projections in and out, the convolution,
the scan, the gated norm), forward, recomputed and backward."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.scope_share(run, "attn_gdn")
