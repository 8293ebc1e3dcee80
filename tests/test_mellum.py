"""The Mellum 2 model (``models/mellum.py``: sliding-window and full
grouped-query attention through the flash kernels, a softmax top-k
renormalised expert layer through the grouped matmul and its backward)
against the plain reference the benchmark keeps (``benchmarks/reference/
mellum.py``), at a tiny size that keeps every structure: 2 key/value heads
with groups of 2, a window of 48 at sequences of 256 in 64-blocks (so the
band binds and whole blocks fall below it), three window layers beside a
full one with YaRN, 8 experts top 2 of which a share holds 4. Kernels
interpreted, on the CPU."""

import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import monitor
from apex_tpu.models import mellum as ml
from apex_tpu.transformer import moe_dropless
from benchmarks.reference import mellum as ref

YARN = {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4,
        "original_max_position_embeddings": 64, "beta_fast": 32,
        "beta_slow": 1}
LAYER_TYPES = (ml.SLIDING, ml.SLIDING, ml.SLIDING, ml.FULL)
#: the reference reads the published key names
SIZES = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             rms_norm_eps=1e-6, sliding_window=48, num_experts_per_tok=2,
             norm_topk_prob=True, layer_types=list(LAYER_TYPES),
             rope_parameters={
                 "full_attention": YARN,
                 "sliding_attention": {"rope_type": "default",
                                       "rope_theta": 10000.0}})
#: Mellum2-12B-A2.5B-Instruct's own keys, for the YaRN constants
PUBLISHED_FULL = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                  "original_max_position_embeddings": 8192, "beta_fast": 32,
                  "beta_slow": 1, "attention_factor": 1.2772588722239782}


def _cfg(**kw):
    base = dict(
        vocab_size=96, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=32, moe_intermediate_size=128, n_routed_experts=8,
        num_experts_per_tok=2, layer_types=LAYER_TYPES, sliding_window=48,
        rope_theta=10000.0, dtype=jnp.float32,
        rope_scaling=tuple(sorted((k, v) for k, v in YARN.items()
                                  if k not in ("rope_type", "rope_theta"))))
    return ml.MellumConfig(**{**base, **kw})


CFG = _cfg(n_local_experts=4, first_expert=2)
S = 256


@pytest.fixture(scope="module")
def params():
    return ml.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def batch():
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0,
                             CFG.vocab_size)
    return ids, jnp.roll(ids, -1, axis=1)


def _small_blocks(monkeypatch):
    """64-blocks, so that at 256 tokens whole blocks lie below the band."""
    import importlib
    fa = importlib.import_module("apex_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_BAND_BLOCK", 64)
    monkeypatch.setattr(fa, "_BAND_BLOCK_BWD_WINDOW", 64)
    fa._flash_fwd_impl.clear_cache()
    fa._flash_bwd_impl.clear_cache()


# -- the constants ------------------------------------------------------------

def test_yarn_constants_at_the_published_keys():
    """``models/deepseek.py``'s YaRN functions, which the model uses, give
    the reference's frequencies and the config's own ``attention_factor``
    at the published keys; a sliding layer rotates plainly."""
    from apex_tpu.models import deepseek as ds
    cfg = _cfg(head_dim=128, rope_theta=500000.0, rope_scaling=tuple(sorted(
        (k, v) for k, v in PUBLISHED_FULL.items()
        if k not in ("rope_type", "rope_theta", "attention_factor"))))
    want, factor = ref.inv_freq_and_factor(PUBLISHED_FULL, 128)
    np.testing.assert_allclose(np.asarray(ds.yarn_inv_freq(
        ml._rope_of(cfg, ml.FULL))), np.asarray(want), rtol=1e-6)
    assert factor == PUBLISHED_FULL["attention_factor"]
    assert ds.rope_factor(ml._rope_of(cfg, ml.FULL)) == pytest.approx(
        PUBLISHED_FULL["attention_factor"], rel=1e-12)
    plain, one = ref.inv_freq_and_factor(
        {"rope_type": "default", "rope_theta": 500000}, 128)
    np.testing.assert_allclose(np.asarray(ds.yarn_inv_freq(
        ml._rope_of(cfg, ml.SLIDING))), np.asarray(plain), rtol=1e-6)
    assert one == 1.0 and ds.rope_factor(ml._rope_of(cfg, ml.SLIDING)) == 1.0
    assert not np.allclose(np.asarray(want), np.asarray(plain))


# -- the routing rule -----------------------------------------------------------

def test_softmax_topk_renorm_on_a_hand_made_case():
    cfg = _cfg(n_routed_experts=4, num_experts_per_tok=2)
    x = jnp.eye(2, 3, dtype=jnp.float32)
    router = jnp.log(jnp.asarray([[1.0, 2.0, 3.0, 4.0],
                                  [4.0, 1.0, 1.0, 4.0],
                                  [1.0, 1.0, 1.0, 1.0]]))
    idx, w = moe_dropless.route(cfg, router, None, x)
    assert np.asarray(idx).tolist() == [[3, 2], [0, 3]]
    np.testing.assert_allclose(np.asarray(w), [[4 / 7, 3 / 7], [0.5, 0.5]],
                               rtol=1e-6)


def test_weights_sum_to_one_and_are_the_references():
    x = jax.random.normal(jax.random.PRNGKey(3), (64, CFG.hidden_size))
    router = jax.random.normal(jax.random.PRNGKey(4),
                               (CFG.hidden_size, CFG.n_routed_experts))
    idx, w = moe_dropless.route(CFG, router, None, x)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    with jax.default_matmul_precision("highest"):
        idx_ref, w_ref = ref.route(x, router, SIZES)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), rtol=1e-5)
    # LongCat's rule on the same scores does NOT renormalise
    lc = type("C", (), dict(routing="softmax_topk", moe_topk=2,
                            routed_scaling_factor=1.0))
    _, w_lc = moe_dropless.route(lc, router, jnp.zeros((8,)), x)
    assert float(jnp.max(w_lc.sum(-1))) < 1.0
    assert set(moe_dropless.ROUTING) == {
        "sigmoid_group_limited", "softmax_topk", "softmax_topk_renorm"}


def test_tie_distance_of_a_choice():
    z = jnp.asarray([[3.0, 2.0, 1.9, 0.0]])
    assert float(ref.tie_distance(z, jnp.asarray([[0, 1]]))[0]) == 0.0
    assert float(ref.tie_distance(z, jnp.asarray([[0, 2]]))[0]) == \
        pytest.approx(0.05)
    assert float(ref.tie_distance(z, jnp.asarray([[2, 3]]))[0]) == \
        pytest.approx(1.5)


# -- the model against the reference ---------------------------------------------

def test_logits_loss_and_every_gradient_are_the_references(
        params, batch, monkeypatch):
    """The program (float32 weights, kernels interpreted: the flash kernels
    over the band, the grouped matmul forward, dx and dw, the fused head)
    against the reference, summed over the experts the PROGRAM chose (in
    float32 on the same weights the two choices are the same)."""
    _small_blocks(monkeypatch)
    ids, labels = batch
    logits, aux = ml.forward(CFG, params, ids, interpret=True)
    forced = aux["moe_idx"].reshape(len(LAYER_TYPES), 1, S, -1)
    want, chosen, _ = ref.forward(params, ids, SIZES,
                                  first_expert=CFG.first_expert,
                                  routing=True, forced=forced)
    np.testing.assert_array_equal(np.sort(np.asarray(forced), -1),
                                  np.sort(np.asarray(chosen), -1))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    (loss, aux), grads = jax.value_and_grad(
        lambda p: ml.loss(CFG, p, ids, labels, interpret=True),
        has_aux=True)(params)
    kw = dict(first_expert=CFG.first_expert, forced=forced)
    assert float(loss) == pytest.approx(
        float(ref.loss(params, ids, labels, SIZES, **kw)), rel=1e-5)
    leaves = [tuple(k.key for k in path) for path, _ in
              jax.tree_util.tree_flatten_with_path(params)[0]]
    want = ref.grads(params, ids, labels, SIZES, leaves, **kw)
    for path in leaves:
        got = functools.reduce(lambda t, k: t[k], path, grads)
        scale = float(jnp.max(jnp.abs(want[path]))) + 1e-12
        assert float(jnp.max(jnp.abs(got - want[path]))) / scale < 2e-3, path
    # what the share was handed, a layer: S tokens x top 2, 4 of 8 held
    assert aux["moe"]["assignments_local"].shape == (len(LAYER_TYPES),)
    assert int(aux["moe"]["experts_touched"].max()) <= 4
    assert 0 < int(aux["moe"]["assignments_local"][0]) < 2 * S


def test_bf16_model_is_within_a_bf16_tolerance(monkeypatch):
    _small_blocks(monkeypatch)
    cfg = _cfg(dtype=jnp.bfloat16)
    params = ml.init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, S), 0, 96)
    logits, aux = ml.forward(cfg, params, ids, interpret=True)
    want = ref.forward(params, ids, SIZES, forced=aux["moe_idx"].reshape(
        len(LAYER_TYPES), 1, S, -1))
    err = float(jnp.max(jnp.abs(logits.astype(jnp.float32) - want))
                / jnp.max(jnp.abs(want)))
    assert err < 0.03, err


# -- what a recomputed block keeps ----------------------------------------------------

#: the layers of a case: two or more, one of them sliding
KEPT_CASES = {"sliding_full": (ml.SLIDING, ml.FULL),
              "sliding_sliding": (ml.SLIDING, ml.SLIDING),
              "full_sliding_full": (ml.FULL, ml.SLIDING, ml.FULL)}


def _kernel_calls(jaxpr):
    """``{(jitted flash call, its window): count}`` in a jaxpr, and under
    ``"dot_general"`` / ``"concatenate"`` its matmuls and the rotations'
    joins of their two halves."""
    from collections import Counter
    from apex_tpu.lint.jaxpr_checks import iter_eqns
    found = Counter()
    for e in iter_eqns(jaxpr, skip_kernel_bodies=True):
        if e.primitive.name in ("dot_general", "concatenate"):
            found[e.primitive.name] += 1
        if e.primitive.name in ("pjit", "jit") and \
                e.params["name"].startswith("_flash_"):
            windowed = any(
                "flash_attention_window" in str(i.source_info.name_stack)
                for i in iter_eqns(e.params["jaxpr"].jaxpr))
            found[e.params["name"], windowed] += 1
    return found


@pytest.mark.parametrize("case", list(KEPT_CASES))
def test_a_block_keeps_the_flash_kernels_operands_and_results(case,
                                                              monkeypatch):
    """``hidden`` names what its recomputed blocks keep: the gradient of
    the loss calls the forward flash kernel ONCE a layer (twice under a
    bare ``jax.checkpoint``: the test fails if the names stop being
    honoured) and each backward kernel once a layer, it multiplies three
    projections and rotates twice a layer less than the bare block's, and
    the loss and every gradient leaf are bit-equal to the bare block's."""
    _small_blocks(monkeypatch)
    kinds = KEPT_CASES[case]
    cfg = _cfg(n_local_experts=4, first_expert=2, layer_types=kinds)
    params = ml.init_params(cfg, jax.random.PRNGKey(0))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0,
                             cfg.vocab_size)
    labels = jnp.roll(ids, -1, axis=1)

    def run(p):
        return ml.loss(cfg, p, ids, labels, interpret=True)[0]

    def both():
        return (_kernel_calls(jax.make_jaxpr(jax.grad(run))(params).jaxpr),
                jax.value_and_grad(run)(params))

    calls, (loss, grads) = both()
    with monkeypatch.context() as m:
        real = jax.checkpoint
        m.setattr(jax, "checkpoint", lambda fn, **kw: real(fn))
        bare_calls, (bare_loss, bare_grads) = both()

    n_window = kinds.count(ml.SLIDING)
    n_full = kinds.count(ml.FULL)
    want = {("_flash_fwd_impl", True): n_window,
            ("_flash_fwd_impl", False): n_full,
            ("_flash_bwd_impl", True): n_window,
            ("_flash_bwd_impl", False): n_full}
    projections, rotations = (
        bare_calls.pop(k) - calls.pop(k) for k in ("dot_general",
                                                   "concatenate"))
    assert (projections, rotations) == (3 * len(kinds), 2 * len(kinds))
    assert calls == {k: n for k, n in want.items() if n}
    assert bare_calls == {(name, w): n * (2 if "fwd" in name else 1)
                          for (name, w), n in want.items() if n}
    assert float(loss) == float(bare_loss)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(bare_grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(path))


# -- a chip's share ---------------------------------------------------------------

def test_the_shares_add_up():
    """The four shares' expert-layer outputs (experts 0-1, 2-3, 4-5, 6-7 of
    8) sum to the uncut reference's whole layer; the router keeps its
    width in every share."""
    whole = _cfg()
    p = ml.init_params(whole, jax.random.PRNGKey(5))["layer_0"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (48, whole.hidden_size))
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref._moe(x[None], p, SIZES, 0)
        total, handed = 0.0, 0
        for first in (0, 2, 4, 6):
            share = _cfg(first_expert=first, n_local_experts=2)
            mine = {"router": p["router"], "experts": jax.tree.map(
                lambda a: a[first:first + 2], p["experts"])}
            y, st = moe_dropless.expert_layer(share, mine, x, interpret=True)
            total = total + y
            handed += int(st["assignments_local"])
    assert handed == 48 * 2                 # no token dropped, none twice
    np.testing.assert_allclose(np.asarray(total), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-5)


def test_dropless_when_every_token_picks_the_held_experts():
    """All 40 tokens choose experts 0 and 1, both held: 80 rows, the static
    bound (``t x min(k, n_local)``), and every one is computed."""
    share = _cfg(n_local_experts=2)
    p = ml.init_params(share, jax.random.PRNGKey(7))["layer_0"]["moe"]
    router = jnp.zeros_like(p["router"]).at[:, :2].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8),
                                  (40, share.hidden_size))) + 0.1
    y, st = moe_dropless.expert_layer(share, {**p, "router": router}, x,
                                      interpret=True)
    assert int(st["assignments_local"]) == 80
    assert int(st["expert_load_max"]) == 40
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref._moe(x[None], {**p, "router": router}, SIZES, 0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-5)


# -- counters and refusals ---------------------------------------------------------

def test_record_step_emits_a_counter_a_layer():
    aux = {"moe": {"assignments_local": np.asarray([7, 9]),
                   "expert_load_max": np.asarray([4, 5]),
                   "experts_touched": np.asarray([2, 3])}}
    rec = monitor.Recorder(name="t", traced_hooks=False)
    monitor.attach(rec)
    try:
        ml.record_step(aux)
    finally:
        monitor.detach()
    got = [(e["name"], e["layer"], e["value"]) for e in rec.records()
           if e["kind"] == "counter"]
    assert got == [("moe/assignments_local", 0, 7),
                   ("moe/expert_load_max", 0, 4),
                   ("moe/experts_touched", 0, 2),
                   ("moe/assignments_local", 1, 9),
                   ("moe/expert_load_max", 1, 5),
                   ("moe/experts_touched", 1, 3)]
    assert rec.counters()["moe/assignments_local"] == 16


@pytest.mark.parametrize("kw", [dict(num_heads=3), dict(first_expert=6,
                                                        n_local_experts=4),
                                dict(layer_types=("windowed",))])
def test_config_refuses_what_it_cannot_mean(kw):
    with pytest.raises(ValueError):
        _cfg(**kw)


def test_the_module_is_imported_on_demand_only():
    import subprocess
    code = ("import sys, apex_tpu, apex_tpu.models\n"
            "assert 'apex_tpu.models.mellum' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT,
                   env={**os.environ, "JAX_PLATFORMS": "cpu"})
