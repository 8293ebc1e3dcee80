"""Device time inside Pallas kernels (``tpu_custom_call`` events) over the
device's busy time, over every traced operation."""


def compute(run):
    t = run["trace"]
    if t is None or t["ops_busy_s"] <= 0:
        return None
    return 100.0 * t["pallas_s"] / t["ops_busy_s"]
