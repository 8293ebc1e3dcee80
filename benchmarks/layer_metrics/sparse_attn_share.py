"""Device time of the block-sparse attention sub-layers over the
operations' busy time in the traced rounds: operations under
``apx:sparse_attn`` (``serve/minicpm_sala.py``: the projections and head
norms, the K|V write, the compressed keys and the choice of blocks, the
attention over the chosen pages, the gate and output projection), prefill
chunks' and decode's alike."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.scope_share(run, "sparse_attn")
