"""The lightning prefill (chunk) kernel's share of its roofline over the
traced serving rounds: the chunk form's products and its q, k, v, o and
state bytes for the chunks the trace holds (the executions of the engine's
prefill program), against the device time of ``apx_lightning_prefill``. A
chunk counts the tokens it was given, not its padded shape: the mean
``n_tokens`` of the window's ``serve/prefill`` spans (a prompt's last chunk
is part padding, which the kernel runs all the same and which so shows as
lost share); a program whose spans do not say counts the chunk whole."""

from benchmarks.harness import bytes as bytes_mod
from benchmarks.harness import counts_minicpm_sala as counts
from benchmarks.harness import span_reduce
from benchmarks.harness import trace_reduce


def compute(run):
    lt = (run["program"].info or {}).get("lightning")
    pattern = getattr(run["program"], "programs", {}).get("prefill")
    if run["trace"] is None or not lt or not pattern:
        return None
    took = trace_reduce.kernel_seconds(run["trace"], lt["prefill_kernel"])
    chunks = len(trace_reduce.module_seconds(run["trace"], pattern))
    if not took or not chunks:
        return None
    live = [s["n_tokens"] for s in span_reduce.window_spans(
        run, "serve/prefill") or []
        if s["name"] == "serve/prefill" and "n_tokens" in s]
    tokens = chunks * (sum(live) / len(live) if live else lt["chunk"])
    least, bound = bytes_mod.roofline_seconds(
        counts.lightning_prefill_flops(tokens, lt["heads"], lt["head_dim"],
                                       lt["sub_chunk"]),
        counts.lightning_prefill_bytes(tokens, lt["heads"], lt["head_dim"],
                                       chunks), run["peak"])
    run["notes"]["lightning_prefill_roofline_bound"] = bound
    return 100.0 * least * lt["layers"] / took
