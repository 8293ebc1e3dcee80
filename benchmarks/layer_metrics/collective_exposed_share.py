"""The part of the collective time during which no compute operation runs
on that chip, over the traced window of whole periods; mean over the
chips."""


def compute(run):
    t = run["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
