"""Share of the tuner's lookups that found a tuned entry, over the whole
process (the lookups happen while the programs are traced, in set-up)."""


def compute(run):
    hit = run["counters"].get("tune/cache_hit", 0)
    miss = run["counters"].get("tune/cache_miss", 0)
    return 100.0 * hit / (hit + miss) if hit + miss else None
