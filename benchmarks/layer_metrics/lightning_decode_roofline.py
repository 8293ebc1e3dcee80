"""The lightning decode kernel's share of its roofline over the traced
serving rounds: every live sequence's float32 state has to be read and
written once a layer (memory-bound), against the device time of
``apx_lightning_decode``. Live rows from the program's ``state/rows_live``
counter; the kernel also moves the rows no sequence holds, which shows as
lost share."""

from benchmarks.harness import bytes as bytes_mod
from benchmarks.harness import counts_minicpm_sala as counts
from benchmarks.harness import trace_reduce


def compute(run):
    lt = (run["program"].info or {}).get("lightning")
    if run["trace"] is None or not lt:
        return None
    took = trace_reduce.kernel_seconds(run["trace"], lt["decode_kernel"])
    rows = sum(counts.traced(run, "state/rows_live"))
    if not took or not rows:
        return None
    shape = (lt["heads"], lt["head_dim"])
    least, bound = bytes_mod.roofline_seconds(
        counts.lightning_decode_flops(rows, *shape),
        counts.lightning_decode_bytes(rows, *shape), run["peak"])
    run["notes"]["lightning_decode_roofline_bound"] = bound
    return 100.0 * least * lt["layers"] / took
