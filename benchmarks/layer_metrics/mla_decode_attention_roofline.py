"""The latent decode attention kernel's share of its roofline over the
traced serving rounds: every cached latent row of every running sequence
has to be read once a layer, and serves all heads as key and as value (121
FLOP/B at the published 64 heads x (576 + 512): memory-bound on a v5e, but
only by a factor of two). Counted at the published row (576 lanes): the
lanes a row is padded with show as lost share."""

from benchmarks.harness import bytes as bytes_mod
from benchmarks.harness import counts_deepseek as counts
from benchmarks.harness import trace_reduce


def compute(run):
    a = run["program"].attention
    tr = run["traced"]
    if run["trace"] is None or a.get("kind") != "mla_decode" \
            or not (tr or {}).get("rounds"):
        return None
    took = trace_reduce.kernel_seconds(run["trace"], a["kernel"])
    if not took:
        return None
    shape = (a["heads"], a["latent_dim"], a["value_dim"])
    least, bound = bytes_mod.roofline_seconds(
        counts.mla_decode_flops(tr["context_tokens"], *shape),
        counts.mla_decode_bytes(tr["context_tokens"], *shape,
                                tr["batch_rows"]),
        run["peak"])
    run["notes"]["mla_decode_attention_roofline_bound"] = bound
    return 100.0 * least * a["layers"] / took
