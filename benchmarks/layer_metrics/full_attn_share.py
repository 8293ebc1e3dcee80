"""Device time of the full-attention sub-layers over the operations' busy
time in the traced steps: operations under ``apx:attn_full``
(``models/mellum.py``), as ``window_attn_share`` for the window layers."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.scope_share(run, "attn_full")
