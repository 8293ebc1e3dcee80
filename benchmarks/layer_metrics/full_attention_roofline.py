"""The flash kernels of the FULL-attention layers (grouped key/value heads,
no window; instructions ``apx_flash_attention_fwd`` / ``_bwd``) against
their roofline in the traced train steps: the causal half at the timed
length, K and V counted once a key/value head
(``harness/counts_mellum.py:attention_roofline``)."""

from benchmarks.harness import counts_mellum as counts


def compute(run):
    return counts.attention_roofline(run, "full")
