"""The forward kernels of the gated delta rule (what a chunk knows alone,
instructions ``apx_gdn_chunk_fwd``, and the walk over the chunks,
``apx_gdn_scan_fwd``) against their roofline in the traced train steps
(``harness/counts_qwen3_next.py:scan_roofline``): one pass a gated-delta
layer a step is work; the second run of both in a recomputed block is
time."""

from benchmarks.harness import counts_qwen3_next as counts


def compute(run):
    return counts.scan_roofline(run, "fwd")
