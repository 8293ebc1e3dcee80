"""The Qwen3-Next model (``models/qwen3_next.py``: gated-delta-rule linear
attention through the chunked scan and its backward, a gated full attention
at a partial rotary embedding, a softmax top-k renormalised expert layer
beside a gated shared expert) against the plain reference the benchmark
keeps (``benchmarks/reference/qwen3_next.py``: the recurrence token by
token), at a tiny size that keeps every structure: one key head under two
value heads of 128 lanes, a group of two query heads a key/value head, a
rotary over 32 of 128 lanes, three gated-delta layers beside a full one,
sequences of 192 (a chunk and a padded one), 8 experts top 2 of which a
share holds 4.
Kernels interpreted, on the CPU."""

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp, monitor
from apex_tpu.models import qwen3_next as qn
from apex_tpu.optimizers import FusedAdam
from apex_tpu.transformer import moe_dropless
from benchmarks.reference import qwen3_next as ref

#: the reference reads the published key names
SIZES = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=128,
             linear_num_key_heads=1, linear_num_value_heads=2,
             linear_key_head_dim=128, linear_value_head_dim=128,
             partial_rotary_factor=0.25, rope_theta=1e7, rms_norm_eps=1e-6,
             num_experts_per_tok=2, norm_topk_prob=True)
S = 192


def _cfg(**kw):
    base = dict(
        vocab_size=96, hidden_size=128, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=128, linear_num_key_heads=1,
        linear_num_value_heads=2, linear_key_head_dim=128,
        linear_value_head_dim=128, moe_intermediate_size=64,
        shared_expert_intermediate_size=64, n_routed_experts=8,
        num_experts_per_tok=2, dtype=jnp.float32, init_std=0.1)
    return qn.Qwen3NextConfig(**{**base, **kw})


CFG = _cfg(n_local_experts=4, first_expert=2)


def _params(cfg=CFG, seed=0):
    """Random weights with the norms OFF their identity, so that a weight
    read as ``w`` where it is ``1 + w`` shows."""
    params = qn.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def nudge(path, x):
        if x.ndim == 1 and path[-1].key not in qn.FP32_LEAVES:
            return x + 0.1 * jax.random.normal(next(keys), x.shape, x.dtype)
        return x
    return jax.tree_util.tree_map_with_path(nudge, params)


@pytest.fixture(scope="module")
def params():
    return _params()


@pytest.fixture(scope="module")
def ids():
    return jax.random.randint(jax.random.PRNGKey(2), (1, S), 0, 96)


@pytest.fixture(scope="module")
def program(params, ids):
    """The program's logits, its choices, its loss and every gradient."""
    got, aux = qn.forward(CFG, params, ids, interpret=True)
    labels = jnp.roll(ids, -1, 1)
    loss, grads = jax.value_and_grad(
        lambda p: qn.loss(CFG, p, ids, labels, interpret=True)[0])(params)
    return got, aux, labels, loss, grads


def _paths(tree):
    return [tuple(k.key for k in path) for path, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


def _at(tree, path):
    for k in path:
        tree = tree[k]
    return tree


# -- against the reference ---------------------------------------------------------

def test_logits_are_the_references(params, ids, program):
    got, aux, *_ = program
    assert CFG.layer_types == (qn.LINEAR,) * 3 + (qn.FULL,)
    forced = aux["moe_idx"].reshape(4, 1, S, -1)
    want, theirs, _ = ref.forward(params, ids, SIZES, first_expert=2,
                                  routing=True, forced=forced)
    assert got.shape == (1, S, 96)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    # in float32 the program chooses what the reference chooses
    assert np.array_equal(np.sort(np.asarray(theirs), -1),
                          np.sort(np.asarray(forced), -1))
    # what the share was handed, a layer: at most S tokens x top 2
    handed = np.asarray(aux["moe"]["assignments_local"])
    assert handed.shape == (4,) and (handed > 0).all() \
        and (handed < 2 * S).all()


def test_loss_and_every_leafs_gradient_are_the_references(params, ids,
                                                          program):
    _, aux, labels, loss, grads = program
    forced = aux["moe_idx"].reshape(4, 1, S, -1)
    want = ref.loss(params, ids, labels, SIZES, first_expert=2,
                    forced=forced)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    paths = _paths(params)
    assert len(paths) == 3 * (2 + 7 + 7) + (2 + 6 + 7) + 3
    theirs = ref.grads(params, ids, labels, SIZES, paths, first_expert=2,
                       forced=forced)
    for path in paths:
        a, b = _at(grads, path), theirs[path]
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, path
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale, path


def test_the_scan_in_jax_numpy_is_the_kernel(params, ids, program):
    got = qn.forward(CFG, params, ids, scan_impl="reference",
                     impl="reference", interpret=True)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(program[0]),
                               rtol=2e-4, atol=2e-5)


def test_the_bf16_model_is_near_the_reference(ids):
    """bf16 weights and activations, float32 decay leaves: logits within
    0.06 of the largest reference logit (read: 0.030 at these widths of
    128, where a logit is a sum of few terms), the loss to 1%."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16, init_std=0.05)
    params = qn.init_params(cfg, jax.random.PRNGKey(3))
    gdn = params["layer_0"]["gdn"]
    assert gdn["A_log"].dtype == gdn["dt_bias"].dtype == jnp.float32
    assert gdn["qkvz"].dtype == jnp.bfloat16
    got, aux = qn.forward(cfg, params, ids, interpret=True)
    forced = aux["moe_idx"].reshape(4, 1, S, -1)
    want = ref.forward(params, ids, SIZES, first_expert=2, forced=forced)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                / jnp.max(jnp.abs(want)))
    assert err < 0.06
    labels = jnp.roll(ids, -1, 1)
    loss = qn.loss(cfg, params, ids, labels, interpret=True)[0]
    assert float(loss) == pytest.approx(float(ref.loss(
        params, ids, labels, SIZES, first_expert=2, forced=forced)),
        rel=0.01)


# -- the pieces, on hand-made cases --------------------------------------------------

def test_partial_rotary_turns_the_first_lanes_only():
    cfg = _cfg()
    assert cfg.rotary_dim == 32
    x = jnp.ones((1, 1, 3, 128))
    got = np.asarray(qn.partial_rope(x, jnp.arange(3), cfg))
    np.testing.assert_array_equal(got[..., 32:], 1.0)       # 96 lanes pass
    np.testing.assert_allclose(got[0, 0, 0], 1.0)           # position 0
    # pair (0, 16) at position 2, frequency theta^0 = 1: (cos 2 - sin 2,
    # cos 2 + sin 2); pair (15, 31) at frequency theta^(-30/32)
    np.testing.assert_allclose(got[0, 0, 2, [0, 16]],
                               [np.cos(2) - np.sin(2), np.cos(2) + np.sin(2)],
                               rtol=1e-6)
    a = 2 * 1e7 ** (-30 / 32)
    np.testing.assert_allclose(got[0, 0, 2, [15, 31]],
                               [np.cos(a) - np.sin(a), np.cos(a) + np.sin(a)],
                               rtol=1e-6)
    # the published model: 64 of 256 lanes
    assert _cfg(head_dim=256).rotary_dim == 64


def test_norm_weights_are_zero_centred():
    x = jnp.asarray([[3.0, 4.0]])
    rms = np.sqrt(12.5)
    np.testing.assert_allclose(
        np.asarray(qn.rms_norm(x, jnp.zeros(2), 0.0)), [[3 / rms, 4 / rms]],
        rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(qn.rms_norm(x, jnp.asarray([1.0, -1.0]), 0.0)),
        [[6 / rms, 0.0]], rtol=1e-6, atol=1e-7)


def test_the_convolution_is_causal_and_a_channels_own():
    x = jnp.arange(1.0, 6.0).reshape(1, 5, 1) * jnp.asarray([1.0, 10.0])
    w = jnp.asarray([[1.0, 0.0], [0.0, 0.0], [0.0, 1.0], [2.0, 0.0]])
    got = np.asarray(qn.causal_conv(x, w))[0]
    # channel 0: x[t - 3] + 2 x[t]; channel 1: x[t - 1]
    np.testing.assert_allclose(got[:, 0], [2, 4, 6, 9, 12])
    np.testing.assert_allclose(got[:, 1], [0, 10, 20, 30, 40])


# -- a chip's share ---------------------------------------------------------------

def _moe_inputs(cfg, t=40):
    p = _params(cfg, seed=7)["layer_0"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(8), (t, cfg.hidden_size))
    return p, x


def test_the_shares_add_up():
    """Four shares of 2 experts each: their expert-layer outputs, with the
    gated shared expert (which every chip computes whole) counted once,
    are the uncut reference layer; the router keeps its width in each."""
    whole = _cfg()
    p, x = _moe_inputs(whole)
    want, idx, _ = ref._moe(x[None], p, SIZES, 0)
    sh = p["shared"]
    shared = np.asarray(jax.nn.sigmoid(x @ sh["out_gate"]) * (
        (jax.nn.silu(x @ sh["gate"]) * (x @ sh["up"])) @ sh["down"]),
        np.float64)
    total, handed = shared, 0
    for first in range(0, 8, 2):
        share = _cfg(first_expert=first, n_local_experts=2)
        mine = {**p, "experts": jax.tree.map(lambda w: w[first:first + 2],
                                             p["experts"])}
        y, st = moe_dropless.expert_layer(share, mine, x, interpret=True)
        assert st["idx"].shape == (40, 2)
        # the reference, given the same share, leaves out the same experts
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref._moe(x[None], mine, SIZES,
                                               first)[0][0]),
            rtol=2e-5, atol=2e-6)
        total = total + np.asarray(y, np.float64) - shared
        handed += int(st["assignments_local"])
    assert handed == 40 * 2                       # each choice, once
    np.testing.assert_allclose(total, np.asarray(want[0]), rtol=2e-5,
                               atol=5e-6)
    assert np.abs(shared).max() > 0.01 * np.abs(total).max()


def test_dropless_when_every_token_picks_held_experts():
    """A router that sends every token to experts 0 and 1, a share that
    holds exactly those: 2 rows a token, none dropped, and the output is
    the reference's."""
    share = _cfg(n_local_experts=2)
    p, x = _moe_inputs(share)
    router = p["router"].at[:, :2].add(50.0 * jnp.sign(
        x.mean(0))[:, None] / share.hidden_size)
    x = jnp.abs(x) * jnp.sign(x.mean(0)) + 0.1 * jnp.sign(x.mean(0))
    p = {**p, "router": router}
    y, st = moe_dropless.expert_layer(share, p, x, interpret=True)
    assert np.array_equal(np.sort(np.asarray(st["idx"]), -1),
                          np.tile([0, 1], (40, 1)))
    assert int(st["assignments_local"]) == 80
    assert int(st["experts_touched"]) == 2
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref._moe(x[None], p, SIZES, 0)[0][0]),
        rtol=2e-5, atol=2e-6)


# -- the description ----------------------------------------------------------------

@pytest.mark.parametrize("kw,match", [
    (dict(num_heads=3), "multiple of num_kv_heads"),
    (dict(linear_num_value_heads=3, linear_num_key_heads=2),
     "multiple of linear_num_key_heads"),
    (dict(partial_rotary_factor=0.01), "no even number of lanes"),
    (dict(partial_rotary_factor=1.5), "no even number of lanes"),
    (dict(shared_expert_intermediate_size=0), "one shared expert"),
    (dict(first_expert=6, n_local_experts=4), "not among the 8 routed"),
])
def test_config_refuses_what_it_cannot_mean(kw, match):
    with pytest.raises(ValueError, match=match):
        _cfg(**kw)


def test_layer_kinds_follow_the_interval():
    assert _cfg(num_layers=8).layer_types == (
        (qn.LINEAR,) * 3 + (qn.FULL,)) * 2
    assert _cfg(num_layers=3, full_attention_interval=2).layer_types == (
        qn.LINEAR, qn.FULL, qn.LINEAR)


# -- training -------------------------------------------------------------------------

def test_amp_step_keeps_the_decay_leaves_float32_and_learns():
    """``amp`` O2 + FusedAdam through ``make_train_step(has_aux=True)`` with
    the family's ``keep_fp32``: the model's copies of ``A_log`` and
    ``dt_bias`` stay float32, everything else is bf16, and the loss falls
    on a batch it sees again."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16, init_std=0.05)
    amp_model, opt = amp.initialize(
        lambda p, i: qn.forward(cfg, p, i, interpret=True)[0],
        FusedAdam(lr=3e-3), opt_level="O2", verbosity=0,
        keep_fp32_predicate=qn.keep_fp32)
    params = amp_model.cast_params(qn.init_params(cfg, jax.random.PRNGKey(5)))
    gdn = params["layer_1"]["gdn"]
    assert gdn["A_log"].dtype == gdn["dt_bias"].dtype == jnp.float32
    assert {x.dtype for x in jax.tree.leaves(
        {**params, "layer_1": {**params["layer_1"], "gdn": {
            k: v for k, v in gdn.items() if k not in qn.FP32_LEAVES}}}
        )} - {jnp.dtype(jnp.float32)} == {jnp.dtype(jnp.bfloat16)}
    state = (params, opt.init(params), opt._amp_stash.loss_scalers[0].state)
    step = amp.make_train_step(
        lambda p, i, l: qn.loss(cfg, p, i, l, interpret=True), opt,
        has_aux=True)
    ids = jax.random.randint(jax.random.PRNGKey(6), (1, 128), 0, 96)
    labels = jnp.roll(ids, -1, 1)
    losses = []
    for _ in range(4):
        *state, loss, aux = step(*state, ids, labels)
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.05
    assert state[0]["layer_1"]["gdn"]["A_log"].dtype == jnp.float32
    assert set(aux["moe"]) == set(qn.MOE_COUNTS)


def test_record_step_emits_a_layers_counters(program):
    aux = jax.device_get(program[1])
    rec = monitor.Recorder(name="qwen3-next", traced_hooks=False)
    monitor.attach(rec)
    try:
        qn.record_step(aux)
    finally:
        monitor.detach()
    events = [e for e in rec.records() if e["name"].startswith("moe/")]
    assert [e["layer"] for e in events
            if e["name"] == "moe/assignments_local"] == [0, 1, 2, 3]
    assert {e["name"] for e in events} == {f"moe/{k}"
                                           for k in qn.MOE_COUNTS}
