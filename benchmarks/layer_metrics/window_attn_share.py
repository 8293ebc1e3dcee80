"""Device time of the sliding-window attention sub-layers over the
operations' busy time in the traced steps: operations whose ``op_name``
lies under ``apx:attn_window`` (``models/mellum.py``: projections, rotation,
the flash kernels and the output projection), forward, recomputed forward
and backward alike."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.scope_share(run, "attn_window")
