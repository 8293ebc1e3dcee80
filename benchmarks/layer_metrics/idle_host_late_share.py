"""The device's idle time that the host's lateness causes, over the traced
window: ``notes.idle_by_span`` under every name but ``serve/token_wait``
(the device idles while the host waits for it: the runtime's latency) and
the gaps under 20 us. Under ``serve/decode_dispatch`` or ``serve/prefill``
it is the in-span dispatch gap (``harness/host_round.py``)."""

from benchmarks.harness import host_round


def compute(run):
    return host_round.idle_host_late_share(run)
