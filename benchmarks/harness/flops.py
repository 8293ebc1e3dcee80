"""Operations the mathematics needs, from shapes. Multiply-adds count 2.

``*_train_flops_per_token`` is the numerator of model FLOP/s utilisation:
forward + backward (3x the forward matmul work) of one token, recompute not
counted, element-wise work (LayerNorm, GELU, softmax, optimizer) not
counted. Checked once against ``cost_analysis()`` of the unfused program at
a small size in ``benchmarks/tests/test_flops_bytes.py``.
"""

from __future__ import annotations


def _block_matmul_flops(h: int, ffn: int) -> int:
    # qkv (h x 3h) + proj (h x h) + fc1 (h x ffn) + fc2 (ffn x h)
    return 2 * h * (4 * h + 2 * ffn)


def attention_matmul_flops_per_token(h: int, seq: int, causal: bool,
                                     causal_skip: bool = True) -> float:
    """QK^T and PV for one query token over ``seq`` keys, all heads. A
    causal kernel needs only the lower triangle ((seq+1)/2 keys on
    average); ``causal_skip=False`` counts the full square, which is what
    an unfused masked softmax executes."""
    keys = (seq + 1) / 2 if (causal and causal_skip) else seq
    return 4.0 * keys * h


def gpt_forward_flops_per_token(cfg: dict, seq: int,
                                causal_skip: bool = True) -> float:
    h, L, V = cfg["n_embd"], cfg["n_layer"], cfg["padded_vocab_size"]
    ffn = cfg.get("n_inner") or 4 * h
    per_layer = _block_matmul_flops(h, ffn) + \
        attention_matmul_flops_per_token(h, seq, True, causal_skip)
    return L * per_layer + 2.0 * h * V          # + tied LM head


def bert_forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Every position goes through the MLM head, as the model computes it
    (and as the published implementation does); only the loss is masked."""
    h, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    V, ffn = cfg["padded_vocab_size"], cfg["intermediate_size"]
    per_layer = _block_matmul_flops(h, ffn) + \
        attention_matmul_flops_per_token(h, seq, False)
    return L * per_layer + 2.0 * h * h + 2.0 * h * V   # mlm_dense + decoder


def train_flops_per_token(forward_flops_per_token: float) -> float:
    """Backward costs twice the forward's matmul work."""
    return 3.0 * forward_flops_per_token


# -- attention kernels -------------------------------------------------------

def flash_fwd_flops(batch: int, heads: int, seq: int, head_dim: int,
                    causal: bool) -> float:
    """QK^T and PV: 2 matmuls of 2*s*s*d each per head (half when causal)."""
    full = 4.0 * batch * heads * seq * seq * head_dim
    return full * ((seq + 1) / (2 * seq) if causal else 1.0)


def flash_bwd_flops(batch: int, heads: int, seq: int, head_dim: int,
                    causal: bool) -> float:
    """dV, dP, dQ, dK: four matmuls the mathematics needs. The kernel also
    recomputes QK^T; recompute is not counted, so a share of 100% is not
    reachable by a kernel that does not store the scores."""
    return 2.0 * flash_fwd_flops(batch, heads, seq, head_dim, causal)


def paged_decode_flops(context_tokens: int, heads: int,
                       head_dim: int) -> float:
    """One decode step of one layer: each query attends its own context;
    ``context_tokens`` is the sum of context lengths over the batch."""
    return 4.0 * context_tokens * heads * head_dim
