"""Device time of the dense feed-forwards over the operations' busy time in
the traced rounds: operations under ``apx:dense_ffn``
(``serve/longcat.py``: the two gated-SiLU feed-forwards of every layer,
which run beside the expert layer on its shortcut), prefill's and decode's
alike."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.scope_share(run, "dense_ffn")
