"""The routed experts' grouped matmuls in the traced DECODE rounds against
their roofline: the weights of the experts that got a row, read once, plus
the rows' activations, and the rows' FLOPs (``harness/counts_deepseek.py``),
from the program's own counters of what each round was handed. Decode
brings a handful of rows an expert, so the bound is the weight stream. The
kernel's calls inside prefill (other row count, hence another instruction
shape) are left out on both sides."""

from benchmarks.harness import bytes as bytes_mod
from benchmarks.harness import counts_deepseek as counts


def compute(run):
    moe = (run["program"].info or {}).get("moe")
    if run["trace"] is None or not moe:
        return None
    mark = f"{moe['kernel']} bf16[{moe['decode_rows']},"
    took = sum(v for k, v in run["trace"].get("op_s", {}).items()
               if k.startswith(mark))
    handed = counts.traced(run, "assignments_local")
    touched = counts.traced(run, "experts_touched")
    if not took or not handed or len(handed) != len(touched):
        return None
    least = 0.0
    for rows, experts in zip(handed, touched):
        for n, e in zip(rows, experts):
            least += bytes_mod.roofline_seconds(
                counts.moe_expert_flops(n, moe["hidden"], moe["inter"]),
                counts.moe_expert_bytes(n, e, moe["hidden"], moe["inter"]),
                run["peak"])[0]
    run["notes"]["moe_grouped_matmul_roofline"] = {
        "decode_rounds": len(handed), "kernel_s": took, "least_s": least}
    return 100.0 * least / took
