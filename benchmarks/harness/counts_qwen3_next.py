"""Operations and bytes the mathematics of a Qwen3-Next-shaped model's own
kernels needs, from shapes (beside ``counts_mellum.py``, whose banded
attention and trained expert layer this family reuses): the gated delta
rule in chunks, forward and backward (``apex_tpu/ops/gated_delta.py``: what a
chunk knows alone, kernels ``apx_gdn_chunk_fwd`` / ``_bwd``, and the walk
over the chunks, ``apx_gdn_scan_fwd`` / ``_bwd``), and a token's forward for
``train_mfu``. Multiply-adds count 2; bf16 unless said; recomputation is
never counted (the backward kernels' rebuilt intermediates, the forward
kernels' second run in a recomputed block), and the triangular solve counts
as a substitution (``c^3 / 3`` multiply-adds a chunk), not as the ten
products the kernel spends on it."""

from __future__ import annotations

from . import counts_mellum

LINEAR, FULL = "linear_attention", "full_attention"


# -- the rule in chunks --------------------------------------------------------------

def _chunks(seq, chunk):
    return -(-seq // chunk)


def scan_fwd_flops(heads, seq, chunk, dk, dv) -> float:
    """A chunk of a head. Alone: ``K K^T``, ``Q K^T``, ``T K`` (``c x c x
    d_k``), ``T V`` (``c x c x d_v``) and the solve. The walk: ``W_k S``,
    ``Q_g S``, ``K_d^T U`` (``c x d_k x d_v`` each) and ``P U`` (``c x c x
    d_v``)."""
    c = chunk
    alone = 2.0 * (3 * c * c * dk + c * c * dv + c ** 3 / 3.0)
    walk = 2.0 * (3 * c * dk * dv + c * c * dv)
    return heads * _chunks(seq, c) * (alone + walk)


def scan_bwd_flops(heads, seq, chunk, dk, dv) -> float:
    """A chunk of a head. The walk: ``P^T dO`` and ``dO U^T`` (``c x c x
    d_v``), and ``K_d G``, ``dO S^T``, ``U G^T``, ``dU S^T``, ``Q_g^T dO``,
    ``W_k^T dU`` (``c x d_k x d_v`` each). Alone: ``dW_v V^T``, ``T^T dW_v``
    (``c x c x d_v``), ``dW_k K^T``, ``T^T dW_k``, the four products of
    ``dK`` and ``dQ`` from the two pair products (``c x c x d_k``), and
    ``X^T dX X^T`` (two of ``c^3``)."""
    c = chunk
    walk = 2.0 * (6 * c * dk * dv + 2 * c * c * dv)
    alone = 2.0 * (2 * c * c * dv + 6 * c * c * dk + 2 * c ** 3)
    return heads * _chunks(seq, c) * (alone + walk)


def scan_fwd_bytes(heads, seq, chunk, dk, dv, itemsize: int = 2) -> float:
    """Alone: read q, k, v and the two float32 rows; write ``W_v``, ``W_k``,
    ``Q_g``, ``K_d``, ``P`` and a chunk's decay (float32 along the state's
    lanes). The walk: read those six; write ``O`` and the last state."""
    chunks = _chunks(seq, chunk)
    rows = heads * chunks * chunk
    operands = rows * (dv + 3 * dk + chunk) * itemsize \
        + heads * chunks * dv * 4
    return rows * ((2 * dk + dv) * itemsize + 8) + 2 * operands \
        + rows * dv * itemsize + heads * dk * dv * 4


def scan_bwd_bytes(heads, seq, chunk, dk, dv, itemsize: int = 2) -> float:
    """The walk: read the forward's six operands, the float32 state each
    chunk met and ``dO``; write the six cotangents. Alone: read q, k, v, the
    two rows and the six cotangents; write dq, dk, dv and two rows."""
    chunks = _chunks(seq, chunk)
    rows = heads * chunks * chunk
    operands = rows * (dv + 3 * dk + chunk) * itemsize \
        + heads * chunks * dv * 4
    walk = 2 * operands + rows * dv * itemsize \
        + heads * chunks * dk * dv * 4 + heads * dk * dv * 4
    alone = operands + 2 * rows * ((2 * dk + dv) * itemsize + 8)
    return walk + alone


def scan_roofline(run, direction: str):
    """Percent of their roofline that the rule's two kernels of one
    ``direction`` (``fwd`` | ``bwd``) reach in the traced train steps: the
    least time the chip could take for one pass a gated-delta layer a step
    over the device time of the instructions the family names; None where
    the run has no such layers, kernels or trace."""
    from . import bytes as bytes_mod
    from . import trace_reduce
    g = (getattr(run["program"], "info", None) or {}).get("gdn")
    if run.get("trace") is None or not g or not g["layers"]:
        return None
    took = trace_reduce.kernel_seconds(run["trace"],
                                       g[f"{direction}_kernel"])
    if not took:
        return None
    shape = (g["batch"] * g["heads"], g["seq"], g["chunk"], g["d_k"],
             g["d_v"])
    flops, nbytes = {"fwd": (scan_fwd_flops, scan_fwd_bytes),
                     "bwd": (scan_bwd_flops, scan_bwd_bytes)}[direction]
    least, bound = bytes_mod.roofline_seconds(flops(*shape), nbytes(*shape),
                                              run["peak"])
    least *= g["layers"] * run["traced"]["steps"]
    run["notes"][f"gdn_scan_{direction}_roofline"] = {
        "bound": bound, "kernel_s": took, "least_s": least}
    return 100.0 * least / took


# -- the whole step ------------------------------------------------------------------

def gated_delta_flops_per_token(pub: dict, chunk: int) -> float:
    """One gated-delta sub-layer, a token: the two projections in, the
    convolution, the chunked rule's eight products a value head (``K K^T``,
    ``Q K^T``, ``T V``, ``T K`` over a chunk's ``c`` tokens; ``W_k S``, ``Q_g
    S``, ``P U``, ``K_d^T U``) and the projection out. The triangular solve
    (``c^2 / 3`` multiply-adds a token a head by substitution) is left out."""
    h = pub["hidden_size"]
    nk, nv = pub["linear_num_key_heads"], pub["linear_num_value_heads"]
    dk, dv = pub["linear_key_head_dim"], pub["linear_value_head_dim"]
    kd, vd = nk * dk, nv * dv
    proj = 2.0 * h * (2 * kd + 2 * vd + 2 * nv) + 2.0 * vd * h
    conv = 2.0 * pub["linear_conv_kernel_dim"] * (2 * kd + vd)
    rule = nv * 2.0 * (3 * chunk * dk + 2 * chunk * dv + 3 * dk * dv)
    return proj + conv + rule


def forward_flops_per_token(pub: dict, layer_types, seq: int, *, chunk: int,
                            experts_held: int, vocab_held: int) -> float:
    """The matmul work of one token's forward on this chip's share: each
    layer's mixer by its kind (full: projections with the gate's, the
    causal triangle's mean keys a query), the router, the ``k x held / E``
    experts a token meets here on average, the shared expert with its gate,
    and the head's held columns. ``pub``: the published keys."""
    h, d = pub["hidden_size"], pub["head_dim"]
    n, m = pub["num_attention_heads"], pub["num_key_value_heads"]
    E, k = pub["num_experts"], pub["num_experts_per_tok"]
    im, ish = (pub["moe_intermediate_size"],
               pub["shared_expert_intermediate_size"])
    total = 0.0
    for kind in layer_types:
        if kind == FULL:
            total += 2.0 * h * (3 * n * d + 2 * m * d)      # q|gate, o; k, v
            total += 4.0 * counts_mellum.band_pairs(seq) / seq * n * d
        else:
            total += gated_delta_flops_per_token(pub, chunk)
        total += 2.0 * h * E                                # the router
        total += k * experts_held / E * 2.0 * 3 * h * im
        total += 2.0 * 3 * h * ish + 2.0 * h                # shared, its gate
    return total + 2.0 * h * vocab_held
