"""Bytes the mathematics has to move through HBM, from shapes."""

from __future__ import annotations


def flash_fwd_bytes(batch: int, heads: int, seq: int, head_dim: int,
                    itemsize: int = 2) -> float:
    """Read q, k, v; write o (the f32 log-sum-exp row is 1/head_dim of
    that and is counted)."""
    tensor = batch * heads * seq * head_dim * itemsize
    return 4.0 * tensor + batch * heads * seq * 4


def flash_bwd_bytes(batch: int, heads: int, seq: int, head_dim: int,
                    itemsize: int = 2) -> float:
    """Read q, k, v, o, do and the log-sum-exp; write dq, dk, dv."""
    tensor = batch * heads * seq * head_dim * itemsize
    return 8.0 * tensor + batch * heads * seq * 4


def paged_decode_bytes(context_tokens: int, heads: int, head_dim: int,
                       batch: int, itemsize: int = 2) -> float:
    """One decode step of one layer: every cached K and V row of every
    sequence is read once; q in, o out."""
    kv = 2.0 * context_tokens * heads * head_dim * itemsize
    qo = 2.0 * batch * heads * head_dim * itemsize
    return kv + qo


def roofline_seconds(flops: float, nbytes: float, peak) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / peak.bf16_flops
    t_m = nbytes / peak.hbm_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
