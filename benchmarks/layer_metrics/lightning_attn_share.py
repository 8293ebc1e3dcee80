"""Device time of the lightning-attention sub-layers over the operations'
busy time in the traced rounds: operations under ``apx:lightning_attn``
(``serve/minicpm_sala.py``: the q, k, v and gate projections, the head
norms and rotation, the chunk or decode kernel over the recurrent state,
the output norm, gate and projection), prefill chunks' and decode's alike."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.scope_share(run, "lightning_attn")
