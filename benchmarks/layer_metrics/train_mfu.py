"""Model FLOP/s utilisation: the operations forward and backward need per
token (``harness/flops.py``, recompute not counted) x the window's tokens/s
over chips x the bf16 peak."""


def compute(run):
    if run["kind"] != "train" or run["window_s"] <= 0:
        return None
    return 100.0 * run["program"].flops_per_token * run["tokens"] / (
        run["window_s"] * run["chips"] * run["peak"].bf16_flops)
