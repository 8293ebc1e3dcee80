"""How unevenly a train step's tokens fall on the experts held here: the
fullest expert's rows over the mean of the held experts' (``moe/
expert_load_max`` x experts held / ``moe/assignments_local``), averaged
over the expert layers of a step; the median of the traced steps. 1 is
even; the fullest expert's run of tiles is the longest."""

from benchmarks.harness import counts_mellum as counts
from benchmarks.harness.stats import median


def compute(run):
    moe = (getattr(run["program"], "info", None) or {}).get("moe")
    steps = counts.traced_steps(run) if moe else None
    if not steps:
        return None
    per_step = []
    for step in steps:
        ratios = [top * moe["experts_held"] / rows for rows, top in
                  zip(step["assignments_local"], step["expert_load_max"])
                  if rows]
        if ratios:
            per_step.append(sum(ratios) / len(ratios))
    return median(per_step) if per_step else None
