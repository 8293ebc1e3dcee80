"""PR 50 added a gated shared expert to ``transformer/moe_dropless.py`` and a
model that calls the flash kernels at a head size of 256. The programs of
the cells that share that code lower to what they lowered to at the PARENT
commit (405b61f): cell 8's step (``models/mellum.py`` through the expert
layer's training path), cells 5 and 6's decode and prefill programs (the
``ServeEngine``'s own, through the expert layer with its shared expert and
its zero-compute slots), at tiny sizes, by the digest of their StableHLO
text (no locations, so a scope's name is not in it). The digests were read
from a ``git archive`` of the parent by this file's own ``_digests()``; they
are a JAX version's, so another version skips. And a flash call at head size
256 with grouped key/value heads traces its kernels once a program, however
many layers call it (the ``setup_s`` lesson of PRs 44 and 48)."""

import hashlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import pytest

#: the JAX the digests were read under
JAX_VERSION = "0.9.0"
AT_THE_PARENT = {
    "cell 5 decode": "f8ac0c4ae2df0e4a",
    "cell 5 prefill": "7ff5d17af395cd52",
    "cell 6 decode": "df8cfe0f24841457",
    "cell 6 prefill": "ca954d6905835f51",
    "cell 8 expert layer, training size": "adc2468a022479f7",
    "cell 8 step": "01959504186bfc40",
}


def _sha(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def _mellum_step():
    """Cell 8's loss and gradient at its layer kinds, widths of tens."""
    from apex_tpu.models import mellum as ml
    cfg = ml.MellumConfig(
        vocab_size=96, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=32, moe_intermediate_size=128, n_routed_experts=8,
        num_experts_per_tok=2, layer_types=(ml.SLIDING, ml.FULL),
        sliding_window=48, first_expert=2, n_local_experts=4,
        rope_theta=10000.0)
    params = jax.eval_shape(lambda: ml.init_params(cfg,
                                                   jax.random.PRNGKey(0)))
    ids = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    return jax.jit(jax.value_and_grad(
        lambda p, i: ml.loss(cfg, p, i, i, interpret=True)[0])).lower(
            params, ids)


def _expert_layer_at_training_size():
    """The expert layer's training path (rows through ``ops.moe_rows``,
    tiles of 256): 32,768 tokens x top 2, forward and backward."""
    from apex_tpu.models import mellum as ml
    from apex_tpu.transformer import moe_dropless
    cfg = ml.MellumConfig(
        vocab_size=96, hidden_size=128, num_heads=4, num_kv_heads=2,
        head_dim=32, moe_intermediate_size=128, n_routed_experts=8,
        num_experts_per_tok=2, layer_types=(ml.FULL,), sliding_window=48,
        first_expert=2, n_local_experts=4)
    p = jax.eval_shape(lambda: ml.init_params(
        cfg, jax.random.PRNGKey(0)))["layer_0"]["moe"]
    x = jax.ShapeDtypeStruct((32768, 128), jnp.bfloat16)
    return jax.jit(jax.grad(lambda p, x: jnp.sum(moe_dropless.expert_layer(
        cfg, p, x, interpret=True)[0].astype(jnp.float32)),
        argnums=(0, 1))).lower(p, x)


def _serve_programs(served, params):
    import chip_smoke
    from apex_tpu import serve
    eng = serve.ServeEngine(
        served, params, num_pages=24, max_seq_len=32, max_prompt_len=16,
        page_size=8, max_batch=4, paged_impl="kernel",
        attention_impl="flash", interpret=True)
    return chip_smoke._serve_programs(eng)


def _deepseek_programs():
    """Cell 5's model (a dense layer, then expert layers with a shared
    expert, group-limited sigmoid routing) at widths of tens."""
    from apex_tpu.models import deepseek as ds
    from apex_tpu.serve.deepseek import DeepseekServed
    cfg = ds.DeepseekConfig(
        vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=24, intermediate_size=160,
        moe_intermediate_size=32, n_routed_experts=32,
        num_experts_per_tok=4, n_group=4, topk_group=2,
        first_k_dense_replace=1, routed_scaling_factor=2.5,
        first_expert=8, n_local_experts=8, max_seq_len=64)
    return _serve_programs(DeepseekServed(cfg),
                           ds.init_params(cfg, jax.random.PRNGKey(0)))


def _longcat_programs():
    """Cell 6's model (softmax top-k over real and zero-compute slots, a
    shared expert, the expert layer on a shortcut) at widths of tens."""
    from apex_tpu.models import longcat as lc
    from apex_tpu.serve.longcat import LongcatServed
    cfg = lc.LongcatConfig(
        vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=12, ffn_hidden_size=160,
        expert_ffn_hidden_size=32, n_routed_experts=16, zero_expert_num=8,
        moe_topk=4, routed_scaling_factor=6.0, first_expert=4,
        n_local_experts=4, max_seq_len=64)
    return _serve_programs(LongcatServed(cfg),
                           lc.init_params(cfg, jax.random.PRNGKey(0)))


def _digests() -> dict:
    # conftest pins "highest" for the CPU's accuracy, and a dot's precision
    # is in the text: lower as a program on the chip would
    with jax.default_matmul_precision("default"):
        out = {"cell 8 step": _sha(_mellum_step()),
               "cell 8 expert layer, training size":
                   _sha(_expert_layer_at_training_size())}
        for cell, programs in (("cell 5", _deepseek_programs),
                               ("cell 6", _longcat_programs)):
            decode, prefill = programs()
            out[f"{cell} decode"] = _sha(decode)
            out[f"{cell} prefill"] = _sha(prefill)
    return out


@pytest.fixture(scope="module")
def digests():
    if jax.__version__ != JAX_VERSION:
        pytest.skip(f"the digests are JAX {JAX_VERSION}'s, this is "
                    f"{jax.__version__}")
    return _digests()


@pytest.mark.parametrize("program", sorted(AT_THE_PARENT))
def test_a_neighbours_program_lowers_as_at_the_parent(digests, program):
    assert digests[program] == AT_THE_PARENT[program]


def test_a_gated_shared_expert_is_the_trees_choice():
    """The contrast: the same expert layer with an ``out_gate`` in its
    shared sub-tree lowers to another program (one product more: ``x w``)."""
    from apex_tpu.models import deepseek as ds
    from apex_tpu.transformer import moe_dropless
    cfg = ds.DeepseekConfig(
        vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=24, intermediate_size=160,
        moe_intermediate_size=32, n_routed_experts=32,
        num_experts_per_tok=4, n_group=4, topk_group=2,
        first_k_dense_replace=1, max_seq_len=64)
    p = ds.init_params(cfg, jax.random.PRNGKey(0))["layer_1"]["moe"]
    x = jnp.ones((8, 64), cfg.dtype)

    def text(p):
        return jax.jit(lambda p, x: moe_dropless.expert_layer(
            cfg, p, x, impl="reference")[0]).lower(p, x).as_text()

    gated = {**p, "shared": {**p["shared"],
                             "out_gate": jnp.ones((64, 1), cfg.dtype)}}
    assert text(gated).count("dot_general") == \
        text(p).count("dot_general") + 1


def test_layers_share_one_trace_of_the_flash_kernels_at_head_size_256():
    """Three layers of grouped causal attention at d = 256 (16 query heads
    over 2 key/value heads), forward and backward: ONE lowered function a
    direction that the layers call, its kernel bodies traced once."""
    import importlib
    fa = importlib.import_module("apex_tpu.ops.flash_attention")
    fa._flash_fwd_impl.clear_cache()
    fa._flash_bwd_impl.clear_cache()
    q = jax.ShapeDtypeStruct((1, 16, 256, 256), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2, 256, 256), jnp.bfloat16)

    def three(q, k, v):
        for _ in range(3):
            q = fa.flash_attention(q, k, v, causal=True, scale=256 ** -0.5,
                                   interpret=True)
        return jnp.sum(q.astype(jnp.float32))

    try:
        text = jax.jit(jax.grad(three, argnums=(0, 1, 2))).lower(
            q, kv, kv).as_text()
    finally:
        fa._flash_fwd_impl.clear_cache()
        fa._flash_bwd_impl.clear_cache()
    for fn in ("_flash_fwd_impl", "_flash_bwd_impl"):
        assert text.count(f"func.func private @{fn}(") == 1
        assert text.count(f"call @{fn}(") == 3


if __name__ == "__main__":          # python tests/<this file>: the digests
    for k, v in sorted(_digests().items()):
        print(f'    "{k}": "{v}",')
