"""The repo's models agree with the plain references at a tiny size on the
CPU, so that the on-chip check compares against something already proven."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import bert as ref_bert
from benchmarks.reference import gpt as ref_gpt

from . import _tiny


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 250,
                              jnp.int32)


def _perturb(params, key):
    """Non-trivial biases and LayerNorm weights (the initialisers leave
    them at 0 and 1, which would hide a dropped bias)."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        x + 0.05 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


@pytest.mark.parametrize("impl", ["fused_softmax", "flash"])
def test_gpt_model_agrees_with_reference_in_float32(ids, impl):
    from apex_tpu.models import GPT, GPTConfig
    model = GPT(GPTConfig(vocab_size=256, max_seq_len=32, hidden_size=64,
                          num_layers=2, num_heads=4, dtype=jnp.float32,
                          attention_impl=impl))
    params = _perturb(model.init(jax.random.PRNGKey(0), ids)["params"],
                      jax.random.PRNGKey(2))
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids)
    want = ref_gpt.forward(params, ids, n_head=4)
    assert _rel(got, want) < 1e-4
    labels = jnp.roll(ids, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        loss = model.loss({"params": params}, ids, labels)
    assert float(loss) == pytest.approx(
        float(ref_gpt.loss(params, ids, labels, n_head=4)), rel=1e-4)


def test_gpt_bf16_program_is_inside_the_tolerance(ids):
    """What the chip run compares: bf16 program vs float32 reference."""
    from apex_tpu.models import GPT, GPTConfig
    model = GPT(GPTConfig(vocab_size=256, max_seq_len=32, hidden_size=64,
                          num_layers=2, num_heads=4, dtype=jnp.bfloat16))
    p32 = model.init(jax.random.PRNGKey(0), ids)["params"]
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), p32)
    err = _rel(model.apply({"params": params}, ids),
               ref_gpt.forward(params, ids, n_head=4))
    assert 1e-4 < err < _tiny.TINY_GPT["logit_tolerance"]


@pytest.mark.parametrize("engine,seed,admitted", [
    ({}, 0, True), ({}, 1, True),
    ({"fp8_kv": True}, 0, False), ({"fp8_kv": True}, 1, False),
    ({"fp8_weights": True}, 0, False), ({"fp8_weights": True}, 1, False)])
def test_serve_check_admits_bf16_and_refuses_8_bit(engine, seed, admitted):
    """The serve cell's own check (prefill + decode steps through the paged
    cache vs the float32 reference) with the tolerance rule of the real
    configurations, twice the largest bf16 error: an 8-bit KV cache reads
    4-7x the bf16 error here and 8-bit block weights 9-12x, and neither may
    pass as the same result. chip_smoke.py's 5e-2 admits the fp8 cache at
    this size (0.046 and 0.037 at seeds 0 and 2)."""
    from benchmarks.families import gpt as fam
    config = copy.deepcopy(_tiny.TINY_GPT)
    config["sizes"] = {**config["published"], **config["assumed"]}
    traffic = copy.deepcopy(_tiny.TINY_SERVE)
    traffic["engine"].update(engine)
    traffic["check"] = {"shape": [4, 5]}
    r = fam.build_serve(config, traffic, seed).check()
    assert r["finite"] and r["tolerance"] == config["logit_tolerance"]
    assert (r["rel_err"] <= r["tolerance"]) is admitted, r
    if not admitted:        # not by a hair: every request is over it
        assert min(r["rel_err_by_request"]) > 1.3 * r["tolerance"], r


def test_bert_model_agrees_with_reference_in_float32(ids):
    """The model's tanh GELU and eps 1e-5 against the source's erf GELU and
    1e-12, which the reference follows: inside 5e-3, five times under the
    chip tolerance of bert-large."""
    from apex_tpu.models.bert import Bert, BertConfig
    model = Bert(BertConfig(vocab_size=256, max_seq_len=32, hidden_size=64,
                            num_layers=2, num_heads=4, dtype=jnp.float32))
    params = _perturb(model.init(jax.random.PRNGKey(0), ids)["params"],
                      jax.random.PRNGKey(3))
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, ids)
    assert _rel(got, ref_bert.forward(params, ids, n_head=4)) < 5e-3
    mask = jax.random.uniform(jax.random.PRNGKey(4), ids.shape) < 0.15
    with jax.default_matmul_precision("highest"):
        loss = model.loss({"params": params}, ids, ids, loss_mask=mask)
    assert float(loss) == pytest.approx(
        float(ref_bert.loss(params, ids, ids, mask, n_head=4)), rel=5e-3)
