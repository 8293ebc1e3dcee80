"""The analytic operation counts against XLA's own ``cost_analysis()`` of
the unfused program at a small size, and the peaks table."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import bytes as bytes_mod
from benchmarks.harness import flops as flops_mod
from benchmarks.harness import peaks

GPT_SIZES = {"n_embd": 128, "n_head": 4, "n_layer": 2, "n_inner": None,
             "padded_vocab_size": 512, "n_positions": 64}
BERT_SIZES = {"hidden_size": 128, "num_attention_heads": 4,
              "num_hidden_layers": 2, "intermediate_size": 512,
              "padded_vocab_size": 512, "max_position_embeddings": 64,
              "type_vocab_size": 2}
B, S = 4, 64


def _xla_flops(fn, *args):
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    return float(ca["flops"])


def test_gpt_forward_flops_match_the_unfused_program():
    from apex_tpu.models import GPT, GPTConfig
    model = GPT(GPTConfig(vocab_size=512, max_seq_len=64, hidden_size=128,
                          num_layers=2, num_heads=4, dtype=jnp.float32,
                          attention_impl="fused_softmax",
                          fused_lm_head=False))
    ids = jnp.zeros((B, S), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    xla = _xla_flops(lambda p, i: model.apply(p, i), params, ids)
    # the unfused softmax computes the whole score square, so compare with
    # causal_skip=False; XLA also counts element-wise work (a few %)
    want = B * S * flops_mod.gpt_forward_flops_per_token(
        GPT_SIZES, S, causal_skip=False)
    assert xla == pytest.approx(want, rel=0.06), (xla, want)
    # a causal kernel needs (S+1)/2 of the S keys
    skip = flops_mod.gpt_forward_flops_per_token(GPT_SIZES, S)
    full = flops_mod.gpt_forward_flops_per_token(GPT_SIZES, S,
                                                 causal_skip=False)
    assert full - skip == pytest.approx(
        2 * 4.0 * 128 * (S - (S + 1) / 2), rel=1e-9)


def test_gpt_train_flops_are_three_forwards():
    from apex_tpu.models import GPT, GPTConfig
    model = GPT(GPTConfig(vocab_size=512, max_seq_len=64, hidden_size=128,
                          num_layers=2, num_heads=4, dtype=jnp.float32,
                          attention_impl="fused_softmax",
                          fused_lm_head=False))
    ids = jnp.zeros((B, S), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    xla = _xla_flops(jax.grad(lambda p: model.loss(p, ids, ids)), params)
    want = B * S * flops_mod.train_flops_per_token(
        flops_mod.gpt_forward_flops_per_token(GPT_SIZES, S,
                                              causal_skip=False))
    # the embedding rows need no input gradient: XLA's count is a little
    # under 3x; element-wise work is a little over
    assert xla == pytest.approx(want, rel=0.12), (xla, want)


def test_bert_forward_flops_match_the_unfused_program():
    from apex_tpu.models.bert import Bert, BertConfig
    model = Bert(BertConfig(vocab_size=512, max_seq_len=64, hidden_size=128,
                            num_layers=2, num_heads=4, dtype=jnp.float32,
                            use_flash=False))
    ids = jnp.zeros((B, S), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)
    xla = _xla_flops(lambda p, i: model.apply(p, i), params, ids)
    want = B * S * flops_mod.bert_forward_flops_per_token(BERT_SIZES, S)
    assert xla == pytest.approx(want, rel=0.06), (xla, want)


@pytest.mark.parametrize("causal", [False, True])
def test_attention_kernel_flops_match_plain_attention(causal):
    b, h, s, d = 2, 4, 64, 32

    def attn(q, k, v):
        sc = jnp.einsum("bhsd,bhtd->bhst", q, k)
        return jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(sc, -1), v)

    x = jnp.zeros((b, h, s, d), jnp.float32)
    xla = _xla_flops(attn, x, x, x)
    full = flops_mod.flash_fwd_flops(b, h, s, d, causal=False)
    assert xla == pytest.approx(full, rel=0.08)
    if causal:
        assert flops_mod.flash_fwd_flops(b, h, s, d, True) == \
            pytest.approx(full * (s + 1) / (2 * s))
    assert flops_mod.flash_bwd_flops(b, h, s, d, causal) == \
        2 * flops_mod.flash_fwd_flops(b, h, s, d, causal)


def test_attention_bytes_and_bounds():
    peak = peaks.peak_for("TPU v5 lite")
    b, h, s, d = 8, 16, 1024, 64
    tensor = b * h * s * d * 2
    assert bytes_mod.flash_fwd_bytes(b, h, s, d) == 4 * tensor + b * h * s * 4
    assert bytes_mod.flash_bwd_bytes(b, h, s, d) == 8 * tensor + b * h * s * 4
    # training attention at s=1024, d=64 is bound by compute, non-causal
    _, bound = bytes_mod.roofline_seconds(
        flops_mod.flash_fwd_flops(b, h, s, d, False),
        bytes_mod.flash_fwd_bytes(b, h, s, d), peak)
    assert bound == "compute"
    # decode attention reads each cached row once: bound by memory
    ctx = 128 * 400
    t, bound = bytes_mod.roofline_seconds(
        flops_mod.paged_decode_flops(ctx, h, d),
        bytes_mod.paged_decode_bytes(ctx, h, d, 128), peak)
    assert bound == "memory"
    assert t == pytest.approx((2 * ctx * h * d * 2 + 2 * 128 * h * d * 2)
                              / 819e9)


def test_peaks_table_has_the_v5e_and_refuses_the_rest():
    p = peaks.peak_for("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s, p.hbm_bytes) == \
        (197e12, 819e9, 16 * 10 ** 9)
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(peaks.UnknownDevice):
            peaks.peak_for(kind)
