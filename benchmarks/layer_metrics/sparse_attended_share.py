"""The share of its context a decode round's sparse attention read:
``sparse/tokens_attended`` over ``sparse/context_tokens``
(``serve/minicpm_sala.py:record_round``, one event each a decode round,
summed over the round's rows), the median of the window's decode rounds.
About a fifth says the selection engages (64 blocks of ~300); 100 that
every row fell back to dense attention."""

from benchmarks.harness import counts_minicpm_sala as counts
from benchmarks.harness.stats import median


def compute(run):
    read = counts.per_round(run, "sparse/tokens_attended")
    held = counts.per_round(run, "sparse/context_tokens")
    shares = [100.0 * r / h for r, h in zip(read, held) if h]
    return median(shares) if shares else None
