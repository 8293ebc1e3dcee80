"""Mean occupancy of the window's batched decodes: ``n_active`` of each
``serve/decode_step`` span (``serve/engine.py:_decode_round``) over the
engine's ``max_batch``."""

from benchmarks.harness import span_reduce


def compute(run):
    spans = span_reduce.window_spans(run, "serve/decode_step")
    fill = [s["n_active"] for s in spans or []
            if s["name"] == "serve/decode_step"]
    if not fill:
        return None
    return 100.0 * sum(fill) / (len(fill) * run["program"].engine.max_batch)
