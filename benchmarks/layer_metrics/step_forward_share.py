"""Device time of the forward pass over the operations' busy time in the
traced steps: operations whose ``op_name`` lies under ``apx:amp_grad``
(``amp/frontend.py:make_train_step``, ``examples/gpt/main_gpt.py``) without
``transpose(``, joined by ``harness/span_reduce.py``."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.phase_share(run, "forward")
