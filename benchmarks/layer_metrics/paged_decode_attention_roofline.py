"""The paged decode attention kernel's share of its roofline over the
traced serving rounds: every cached K and V row of every running sequence
has to be read once per layer (memory-bound)."""

from benchmarks.harness import bytes as bytes_mod
from benchmarks.harness import flops as flops_mod
from benchmarks.harness import trace_reduce


def compute(run):
    a = run["program"].attention
    tr = run["traced"]
    if run["trace"] is None or a.get("kind") != "paged_decode" \
            or not tr.get("rounds"):
        return None
    took = trace_reduce.kernel_seconds(run["trace"], a["kernel"])
    if not took:
        return None
    least, bound = bytes_mod.roofline_seconds(
        flops_mod.paged_decode_flops(tr["context_tokens"], a["heads"],
                                     a["head_dim"]),
        bytes_mod.paged_decode_bytes(tr["context_tokens"], a["heads"],
                                     a["head_dim"], tr["batch_rows"]),
        run["peak"])
    run["notes"]["paged_decode_attention_roofline_bound"] = bound
    return 100.0 * least * a["layers"] / took
