"""Device time of the expert layers over the operations' busy time in the
traced rounds: operations whose ``op_name`` lies under ``apx:moe``
(``transformer/moe_dropless.py``: routing, grouped matmuls, combine and the
shared expert), prefill's and decode's alike."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.scope_share(run, "moe")
