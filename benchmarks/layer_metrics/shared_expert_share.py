"""Device time of the shared expert (its gated MLP and, where the layer has
one, its sigmoid gate) over the operations' busy time in the traced steps:
operations under ``apx:moe_shared`` (``transformer/moe_dropless.py``)."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.scope_share(run, "moe_shared")
