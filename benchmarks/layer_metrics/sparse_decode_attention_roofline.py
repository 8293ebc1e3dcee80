"""The sparse decode attention kernel's share of its roofline over the
traced serving rounds: the K and V rows of the tokens the program's
``sparse/tokens_attended`` counter says each round attended have to be read
once a sparse layer a K|V head (memory-bound), against the device time of
``apx_sparse_decode_attention``."""

from benchmarks.harness import bytes as bytes_mod
from benchmarks.harness import counts_minicpm_sala as counts
from benchmarks.harness import trace_reduce


def compute(run):
    a = run["program"].attention
    tr = run["traced"]
    if run["trace"] is None or a.get("kind") != "sparse_decode" \
            or not (tr or {}).get("rounds"):
        return None
    took = trace_reduce.kernel_seconds(run["trace"], a["kernel"])
    attended = sum(counts.traced(run, "sparse/tokens_attended"))
    if not took or not attended:
        return None
    least, bound = bytes_mod.roofline_seconds(
        counts.sparse_decode_flops(attended, a["heads"], a["head_dim"]),
        counts.sparse_decode_bytes(attended, a["kv_heads"], a["head_dim"],
                                   a["heads"], tr["batch_rows"]),
        run["peak"])
    run["notes"]["sparse_decode_attention_roofline_bound"] = bound
    return 100.0 * least * a["layers"] / took
