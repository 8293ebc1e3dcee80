"""The backward kernels of the gated delta rule (the walk over the chunks in
reverse, instructions ``apx_gdn_scan_bwd``, and what a chunk knows alone,
``apx_gdn_chunk_bwd``) against their roofline in the traced train steps
(``harness/counts_qwen3_next.py:scan_roofline``)."""

from benchmarks.harness import counts_qwen3_next as counts


def compute(run):
    return counts.scan_roofline(run, "bwd")
