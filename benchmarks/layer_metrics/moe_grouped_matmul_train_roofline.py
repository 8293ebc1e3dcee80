"""The expert layers' grouped matmuls in the traced TRAIN steps (forward,
dx and dw: instructions ``apx_moe_grouped_matmul`` and ``_dw``) against
their roofline: for the rows each layer was handed in those steps (the
program's ``moe/assignments_local`` counters), three products of both
matmuls, the held experts' weights read twice and their gradients written
once (``harness/counts_mellum.py``), over the device time of those
instructions. The forward that the step recomputes is time, not work; so
are the rows of tiles that are part padding."""

from benchmarks.harness import bytes as bytes_mod
from benchmarks.harness import counts_mellum as counts
from benchmarks.harness import trace_reduce


def compute(run):
    moe = (getattr(run["program"], "info", None) or {}).get("moe")
    if run["trace"] is None or not moe:
        return None
    took = trace_reduce.kernel_seconds(run["trace"], moe["kernel"])
    steps = counts.traced_steps(run)
    if not took or not steps:
        return None
    least, bounds = 0.0, set()
    for step in steps:
        for rows in step["assignments_local"]:
            t, bound = bytes_mod.roofline_seconds(
                counts.moe_train_flops(rows, moe["hidden"], moe["inter"]),
                counts.moe_train_bytes(rows, moe["experts_held"],
                                       moe["hidden"], moe["inter"]),
                run["peak"])
            least += t
            bounds.add(bound)
    run["notes"]["moe_grouped_matmul_train_roofline"] = {
        "steps": len(steps), "kernel_s": took, "least_s": least,
        "bound": sorted(bounds),
        "rows_a_layer_a_step": steps[-1]["assignments_local"]}
    return 100.0 * least / took
