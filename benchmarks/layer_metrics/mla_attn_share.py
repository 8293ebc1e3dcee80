"""Device time of the latent attention sub-block over the operations' busy
time in the traced rounds: operations under ``apx:mla_attn``
(``serve/deepseek.py``: the low-rank projections, the rotary rotation, the
latent row's write, the attention kernel, the absorbed or expanded
``W_kvb`` products and the output projection)."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.scope_share(run, "mla_attn")
