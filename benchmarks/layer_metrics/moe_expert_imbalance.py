"""How unevenly a decode round's tokens fall on the experts held here: the
fullest expert's rows over the mean of the held experts' (``moe/
expert_load_max`` x experts held / ``moe/assignments_local``), averaged
over the expert layers of a round; the median of the window's decode
rounds. 1 is even; the grouped matmul's tiles are padded to the fullest."""

from benchmarks.harness import counts_deepseek as counts
from benchmarks.harness.stats import median


def compute(run):
    moe = (run["program"].info or {}).get("moe")
    handed = counts.per_round(run, "assignments_local")
    fullest = counts.per_round(run, "expert_load_max")
    if not moe or not handed or len(handed) != len(fullest):
        return None
    per_round = []
    for rows, top in zip(handed, fullest):
        ratios = [t * moe["experts_held"] / n for n, t in zip(rows, top)
                  if n]
        if ratios:
            per_round.append(sum(ratios) / len(ratios))
    return median(per_round) if per_round else None
