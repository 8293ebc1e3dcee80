"""The share of a serve round's period in which the host is busy: over the
``serve/round`` spans of the profiler session, on the device's clock, the
period (next round's start minus this round's) minus the
``serve/token_wait`` time that began in it (``serve/engine.py:_take``), over
the period; the median. At 100% the host sets the pace; period x (1 - share)
is the slack a faster device round can still use. ``notes.token_wait`` has
the waits themselves, ``notes.host_round`` the period
(``harness/host_round.py``)."""

from benchmarks.harness import host_round


def compute(run):
    return host_round.host_busy_share(run)
