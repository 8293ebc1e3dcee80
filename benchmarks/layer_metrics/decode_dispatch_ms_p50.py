"""Median of the window's ``serve/decode_dispatch`` spans
(``serve/engine.py:_decode_round``): the host's cost of sending one decode
round out, from just before the call into the runtime to the start of the
tokens' asynchronous copies (``harness/host_round.py``)."""

from benchmarks.harness import host_round


def compute(run):
    return host_round.decode_dispatch_ms_p50(run)
