"""Time in which a collective operation is in flight on a chip (sync ops and
the start-to-done span of async ones) over the traced window of whole
periods; mean over the chips."""


def compute(run):
    t = run["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * t["collective_s"] / t["window_s"]
