"""Backend compilations between the window's first and last instant, as
JAX's own monitoring events count them (``run.py:CompileWatch``, which
also decides ``correct``). Has to be 0."""


def compute(run):
    return run["compiles_in_window"]
