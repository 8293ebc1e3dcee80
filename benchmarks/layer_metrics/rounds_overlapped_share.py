"""The share of the window's decode rounds that went out while the previous
round's tokens were still unread: ``serve/rounds_overlapped`` counter events
over ``serve/decode_step`` spans (``serve/engine.py:_decode_round``). Under
100% the round ahead does not engage; ``notes.pipeline_drains`` says why, by
``reason`` (``harness/host_round.py``)."""

from benchmarks.harness import host_round


def compute(run):
    return host_round.rounds_overlapped_share(run)
