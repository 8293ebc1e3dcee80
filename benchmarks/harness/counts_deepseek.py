"""Operations and bytes the mathematics of a DeepSeek-V3-shaped model's own
kernels needs, from shapes (beside ``flops.py`` and ``bytes.py``, which hold
the other kernels'). Multiply-adds count 2; bf16 unless said. And what the
decode rounds of a window were handed, from the program's own counters
(``serve/deepseek.py:record_round``: one ``moe/<name>`` counter event an
expert layer a decode round, in order), for the readers that need it."""

from __future__ import annotations


def mla_decode_flops(context_tokens: int, heads: int, latent_dim: int,
                     value_dim: int) -> float:
    """One decode step of one layer, absorbed form: every head scores each
    cached row over its ``latent_dim`` lanes (latent + shared key head, the
    published row: padding lanes are not counted) and sums values over the
    row's first ``value_dim``. ``context_tokens`` is the sum of context
    lengths over the batch."""
    return 2.0 * context_tokens * heads * (latent_dim + value_dim)


def mla_decode_bytes(context_tokens: int, heads: int, latent_dim: int,
                     value_dim: int, batch: int, itemsize: int = 2) -> float:
    """One decode step of one layer: every cached row of every sequence is
    read ONCE (it serves as key and as value, for all heads); q in
    (``latent_dim`` a head), o out (``value_dim`` a head)."""
    rows = float(context_tokens) * latent_dim * itemsize
    qo = float(batch) * heads * (latent_dim + value_dim) * itemsize
    return rows + qo


def moe_expert_flops(assignments: int, hidden: int, inter: int) -> float:
    """The routed experts' two matmuls of one layer for ``assignments``
    (token, expert) pairs: gate and up (hidden x 2 inter), down (inter x
    hidden)."""
    return 2.0 * assignments * (hidden * 2 * inter + inter * hidden)


def moe_expert_bytes(assignments: int, experts_touched: int, hidden: int,
                     inter: int, itemsize: int = 2) -> float:
    """One layer: the weights of the experts that got a row, read once, and
    each pair's activations: x in and gate|up out, the product in and y
    out."""
    weights = float(experts_touched) * 3 * hidden * inter * itemsize
    acts = float(assignments) * (hidden + 2 * inter + inter + hidden) \
        * itemsize
    return weights + acts


# -- the program's counters of a window's decode rounds ------------------------

def per_round(run, name):
    """``[[value of layer 0, layer 1, ...], ...]``, one list a decode round
    of the window; ``[]`` where the program has no such counter."""
    rounds = []
    for e in run.get("window_events") or []:
        if e.get("kind") == "counter" and e.get("name") == f"moe/{name}":
            if e.get("layer", 0) == 0:
                rounds.append([])
            if rounds:
                rounds[-1].append(float(e["value"]))
    return rounds


def traced(run, name):
    """``per_round`` cut to the rounds the profiler session saw (every
    round of a closed loop at capacity holds one decode step); the whole
    window's where the cut is empty."""
    rounds = per_round(run, name)
    tr = run.get("traced") or {}
    cut = rounds[tr.get("step_lo", 0):tr.get("step_hi", len(rounds))]
    return cut or rounds
