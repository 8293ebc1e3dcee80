"""Device time of the backward pass over the operations' busy time in the
traced steps: operations under ``apx:amp_grad`` whose ``op_name`` holds
``transpose(`` (``harness/span_reduce.py``)."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.phase_share(run, "backward")
