"""The forward flash attention kernel's share of its roofline in the traced
train steps: device time of the instructions ``apx_flash_attention_fwd``
(named by the scope in ``ops/flash_attention.py:_fa_fwd``) against
``harness/flops.py:flash_fwd_flops`` and ``bytes.py:flash_fwd_bytes``."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.flash_roofline(run, "fwd")
