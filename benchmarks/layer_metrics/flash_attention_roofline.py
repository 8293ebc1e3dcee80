"""The flash attention kernels' (forward + backward) share of their
roofline in the traced train steps: the least time the chip could take for
them (``harness/flops.py``, ``bytes.py``, ``peaks.py``) over the device
time they took. Which peak bounds each direction goes into the notes."""

from benchmarks.harness import bytes as bytes_mod
from benchmarks.harness import flops as flops_mod
from benchmarks.harness import trace_reduce


def compute(run):
    a = run["program"].attention
    if run["trace"] is None or a.get("kind") != "flash":
        return None
    took = trace_reduce.kernel_seconds(run["trace"], a["kernel"])
    if not took:
        return None
    shape = (a["batch"], a["heads"], a["seq"], a["head_dim"])
    t_f, b_f = bytes_mod.roofline_seconds(
        flops_mod.flash_fwd_flops(*shape, a["causal"]),
        bytes_mod.flash_fwd_bytes(*shape), run["peak"])
    t_b, b_b = bytes_mod.roofline_seconds(
        flops_mod.flash_bwd_flops(*shape, a["causal"]),
        bytes_mod.flash_bwd_bytes(*shape), run["peak"])
    least = (t_f + t_b) * a["layers"] * run["traced"]["steps"]
    run["notes"]["flash_attention_roofline_bound"] = \
        {"forward": b_f, "backward": b_b}
    return 100.0 * least / took
