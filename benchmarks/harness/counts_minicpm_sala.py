"""Operations and bytes the mathematics of MiniCPM-SALA's own kernels
needs, from shapes (beside ``counts_deepseek.py``), and what the decode
rounds of a window were handed, from the program's own counters
(``serve/minicpm_sala.py:record_round``: one event of each name a decode
round, in order). Multiply-adds count 2."""

from __future__ import annotations


def lightning_decode_bytes(rows: int, heads: int, head_dim: int) -> float:
    """One decode step of one layer over ``rows`` live sequences: each
    head's float32 state is read once and written once; q, k, v in, o out
    (float32)."""
    state = 2.0 * rows * heads * head_dim * head_dim * 4
    return state + 4.0 * rows * heads * head_dim * 4


def lightning_decode_flops(rows: int, heads: int, head_dim: int) -> float:
    """Decay, rank-1 update and projection: three passes over the state."""
    return 5.0 * rows * heads * head_dim * head_dim


def lightning_prefill_flops(tokens: int, heads: int, head_dim: int,
                            sub_chunk: int) -> float:
    """One layer's chunk form over ``tokens`` tokens in sub-chunks of
    ``sub_chunk``: a head's ``Q K^T`` and ``P V`` over the sub-chunk's
    square, ``Q S`` and ``K^T V`` against the state."""
    c, d = sub_chunk, head_dim
    per_sub = 2.0 * (2 * c * c * d + 2 * c * d * d)
    return heads * (tokens / c) * per_sub


def lightning_prefill_bytes(tokens: int, heads: int, head_dim: int,
                            chunks: int) -> float:
    """q, k, v in (bf16), o out (float32), and a head's state read and
    written once a chunk."""
    rows = float(tokens) * heads * head_dim * (3 * 2 + 4)
    return rows + 2.0 * chunks * heads * head_dim * head_dim * 4


def sparse_decode_flops(tokens_attended: int, heads: int,
                        head_dim: int) -> float:
    """One decode step of one layer: every query head scores and sums the
    values of each attended token (``tokens_attended``: summed over the
    rows; the K|V heads attend equally many)."""
    return 4.0 * tokens_attended * heads * head_dim


def sparse_decode_bytes(tokens_attended: int, kv_heads: int, head_dim: int,
                        heads: int, batch: int, itemsize: int = 2) -> float:
    """The attended tokens' K and V rows of every K|V head, read once; q
    in, o out."""
    kv = 2.0 * tokens_attended * kv_heads * head_dim * itemsize
    return kv + 2.0 * batch * heads * head_dim * itemsize


# -- the program's counters of a window's decode rounds ------------------------

def per_round(run, name):
    """One value a decode round of the window; ``[]`` where the program has
    no such counter."""
    return [float(e["value"]) for e in run.get("window_events") or []
            if e.get("kind") == "counter" and e.get("name") == name]


def traced(run, name):
    """``per_round`` cut to the rounds the profiler session saw (every
    round of a closed loop at capacity holds one decode step); the whole
    window's where the cut is empty."""
    rounds = per_round(run, name)
    tr = run.get("traced") or {}
    cut = rounds[tr.get("step_lo", 0):tr.get("step_hi", len(rounds))]
    return cut or rounds
