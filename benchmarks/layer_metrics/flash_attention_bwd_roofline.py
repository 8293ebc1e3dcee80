"""The backward flash attention kernels' share of their roofline in the
traced train steps: device time of the instructions
``apx_flash_attention_bwd`` (the fused kernel, or ``dkdv`` + ``dq``; named by
the scope in ``ops/flash_attention.py:_fa_bwd``) against
``harness/flops.py:flash_bwd_flops`` and ``bytes.py:flash_bwd_bytes``."""

from benchmarks.harness import span_reduce


def compute(run):
    return span_reduce.flash_roofline(run, "bwd")
