"""1 - the union of device-op intervals / the traced window, where the
window is whole periods: from the start of the chip's first executed
program to the start of its last one (``harness/trace_reduce.py``). Mean
over the chips; each chip is on the ``trace`` detail line."""


def compute(run):
    t = run["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
