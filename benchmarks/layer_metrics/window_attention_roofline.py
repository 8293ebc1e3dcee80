"""The flash kernels of the SLIDING-WINDOW layers (forward and the two
backward kernels; instructions ``apx_flash_attention_window_*``) against
their roofline in the traced train steps: the products over the BAND (a
query's last ``window`` keys: what the mathematics needs, not the whole
blocks the grid multiplies) and q, o, do, dq a query head, k, v, dk, dv ONCE
a key/value head (``harness/counts_mellum.py``), over the device time of
those instructions. A recomputed forward (the step rematerialises each
block) is time that is not counted as work."""

from benchmarks.harness import counts_mellum as counts


def compute(run):
    return counts.attention_roofline(run, "window")
