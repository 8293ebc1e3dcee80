"""Device time of the update (``apx:amp_unscale`` + ``amp_optimizer`` +
``amp_scaler``) over the operations' busy time in the traced steps
(``harness/span_reduce.py``). The same pass over the trace writes the
shares of all phases, the idle gaps by span and, where the harness's
``dispatch`` annotation is in the trace, its split by the runtime's nested
host events to the ``trace`` line's notes."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.phase_share(run, "update")
