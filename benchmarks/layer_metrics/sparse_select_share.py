"""Device time of the block selection over the operations' busy time in the
traced rounds: operations under ``apx:sparse_select`` (inside
``apx:sparse_attn``: completing and gathering the compressed keys, their
scores, the group sum, the block maximum and the top-k)."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.scope_share(run, "sparse_select")
