"""The DeepSeek-V3-shaped model (``models/deepseek.py``), its dropless
expert layer, its kernels and its path through the serve engine, against
the plain reference the benchmark keeps (``benchmarks/reference/
deepseek.py``), at tiny sizes that keep every structure: three kinds of
layer, q_lora / kv_lora / nope / rope / v_head_dim all different, 32
experts in 4 groups, top 4 of 2 groups, one leading dense layer."""

import dataclasses
import math
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import monitor, serve
from apex_tpu.models import deepseek as ds
from apex_tpu.ops import grouped_matmul as gmm
from apex_tpu.serve.deepseek import DeepseekServed, latent_row_width
from apex_tpu.transformer import moe_dropless
from benchmarks.reference import deepseek as ref

ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "rope_type": "yarn"}
#: the reference reads the published key names
SIZES = dict(num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=24, kv_lora_rank=32, rms_norm_eps=1e-6,
             rope_theta=10000.0, rope_scaling=ROPE, n_routed_experts=32,
             num_experts_per_tok=4, n_group=4, topk_group=2,
             norm_topk_prob=True, routed_scaling_factor=2.5)
#: GigaChat3.1-702B-A36B's own keys, for the YaRN constants
PUBLISHED = dict(qk_nope_head_dim=128, qk_rope_head_dim=64, rope_theta=100000,
                 rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64,
                               "mscale": 1, "mscale_all_dim": 1,
                               "original_max_position_embeddings": 4096,
                               "rope_type": "yarn"})


def _cfg(dtype=jnp.float32, **kw):
    base = dict(vocab_size=96, hidden_size=64, num_layers=3, num_heads=4,
                q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=24, intermediate_size=160,
                moe_intermediate_size=32, n_routed_experts=32,
                num_experts_per_tok=4, n_group=4, topk_group=2,
                first_k_dense_replace=1, routed_scaling_factor=2.5,
                rope_theta=10000.0, rope_scaling=tuple(sorted(ROPE.items())),
                max_seq_len=64, dtype=dtype)
    return ds.DeepseekConfig(**{**base, **kw})


CFG = _cfg()
PROMPTS = [list(range(3, 8)), list(range(20, 31)), list(range(40, 56))]
N_NEW = 5
REFERENCE = dict(paged_impl="reference", attention_impl="reference")
KERNELS = dict(paged_impl="kernel", attention_impl="flash", interpret=True)


@pytest.fixture(scope="module")
def params():
    return ds.init_params(CFG, jax.random.PRNGKey(0))


def _engine(params, cfg=CFG, num_pages=24, **kw):
    kw = {**REFERENCE, **kw}
    return serve.ServeEngine(DeepseekServed(cfg), params,
                             num_pages=num_pages, max_seq_len=32,
                             max_prompt_len=16, page_size=8, max_batch=4,
                             record_logits=True, **kw)


def _serve(params, prompts=PROMPTS, n_new=N_NEW, **kw):
    eng = _engine(params, **kw)
    ids = [eng.add_request(p, n_new) for p in prompts]
    eng.run()
    return eng, ids


def _want(params, tokens, sizes=SIZES, **kw):
    return np.asarray(ref.forward(params, jnp.asarray([tokens]), sizes,
                                  **kw)[0])


def _rel(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# -- the constants ---------------------------------------------------------------

def test_yarn_constants_at_the_published_keys():
    rs = PUBLISHED["rope_scaling"]
    cfg = _cfg(qk_nope_head_dim=128, qk_rope_head_dim=64, rope_theta=1e5,
               rope_scaling=tuple(sorted(rs.items())))
    assert ds.softmax_scale(cfg) == pytest.approx(0.14468, abs=5e-6)
    assert ds.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
    assert ds.rope_factor(cfg) == 1.0
    f = np.asarray(ds.yarn_inv_freq(cfg))
    plain = 1e5 ** (-np.arange(32) * 2.0 / 64)
    # correction dims of beta_fast / beta_slow: 64 ln(4096 / (b 2 pi)) /
    # (2 ln 1e5) = 8.38 and 18.01, so dims 0..8 are kept, 19.. are
    # interpolated (divided by 64), a linear ramp over (8, 19) between
    np.testing.assert_allclose(f[:9], plain[:9], rtol=1e-6)
    np.testing.assert_allclose(f[19:], plain[19:] / 64, rtol=1e-6)
    ramp = (np.arange(9, 19) - 8) / 11.0
    np.testing.assert_allclose(
        f[9:19], plain[9:19] * (1 - ramp) + plain[9:19] / 64 * ramp,
        rtol=1e-6)
    np.testing.assert_allclose(f, np.asarray(ref.yarn_inv_freq(PUBLISHED)),
                               rtol=1e-7)
    assert ref.softmax_scale(PUBLISHED) == pytest.approx(
        ds.softmax_scale(cfg))


def test_no_rope_scaling_is_plain_rope():
    cfg = _cfg(rope_scaling=())
    np.testing.assert_allclose(
        np.asarray(ds.yarn_inv_freq(cfg)), 1e4 ** (-np.arange(4) / 4.0),
        rtol=1e-6)
    assert ds.softmax_scale(cfg) == pytest.approx(24 ** -0.5)


# -- the routing rule --------------------------------------------------------------

def _route_on(scores, bias=None, **kw):
    """Route one token whose sigmoid scores are ``scores`` [E]."""
    cfg = _cfg(hidden_size=1, **kw)
    logits = np.log(scores / (1 - scores))[None, :]       # x = [[1.0]]
    bias = np.zeros_like(scores) if bias is None else bias
    idx, w = moe_dropless.route(cfg, jnp.asarray(logits, jnp.float32),
                                jnp.asarray(bias, jnp.float32),
                                jnp.ones((1, 1), jnp.float32))
    r_idx, r_w = ref.route(jnp.ones((1, 1), jnp.float32),
                           jnp.asarray(logits, jnp.float32),
                           jnp.asarray(bias, jnp.float32),
                           {**SIZES, "n_routed_experts": scores.size})
    assert sorted(np.asarray(idx)[0]) == sorted(np.asarray(r_idx)[0])
    return np.asarray(idx)[0], np.asarray(w)[0]


def test_routing_rule_on_a_hand_made_case():
    """32 experts in 4 groups of 8, top 2 groups, top 4 experts."""
    sc = np.full(32, 0.10)
    sc[0] = 0.95                    # the best single score: group 0 ...
    sc[8:10] = 0.60, 0.58           # ... loses to groups 1 and 2 on their
    sc[16:18] = 0.55, 0.54          # top-two sums (1.18, 1.09 against 1.05)
    sc[10], sc[18] = 0.30, 0.20
    idx, w = _route_on(sc)
    assert sorted(idx) == [8, 9, 16, 17]
    assert 0 not in idx
    assert w.sum() == pytest.approx(2.5, rel=1e-6)
    by = dict(zip(idx, w))
    assert by[8] == pytest.approx(2.5 * 0.60 / (0.60 + 0.58 + 0.55 + 0.54),
                                  rel=1e-5)


def test_bias_changes_the_choice_and_not_the_weight():
    sc = np.full(32, 0.10)
    sc[8:10], sc[16:18] = (0.60, 0.58), (0.55, 0.54)
    sc[10] = 0.50                                # fifth: just misses ...
    bias = np.zeros(32)
    idx0, _ = _route_on(sc)
    assert sorted(idx0) == [8, 9, 16, 17]
    bias[10] = 0.06                              # ... until its bias lifts it
    idx, w = _route_on(sc, bias)                 # over expert 17 (0.54)
    assert sorted(idx) == [8, 9, 10, 16]
    by = dict(zip(idx, w))
    # the weight is from the UNcorrected score: 0.50, not 0.56
    assert by[10] == pytest.approx(2.5 * 0.50 / (0.60 + 0.58 + 0.55 + 0.50),
                                   rel=1e-5)
    assert w.sum() == pytest.approx(2.5, rel=1e-6)


def test_tie_distance_of_a_choice():
    """0 where the rule picks the choice, the nearest rung of the ladder
    where a small move of the scores would, inf where none would."""
    sc = np.full((3, 32), 0.10, np.float32)
    sc[:, 8:10], sc[:, 16:18] = (0.60, 0.58), (0.55, 0.54)
    sc[:, 10] = 0.533                  # 0.007 under the fourth (0.54)
    theirs = np.asarray([[8, 9, 16, 17], [8, 9, 10, 16], [0, 1, 2, 3]])
    need = ref.tie_distance(jnp.asarray(sc), theirs, SIZES)
    assert need[0] == 0.0
    assert need[1] == pytest.approx(5e-3)        # each moves 0.0035
    assert np.isinf(need[2])


def test_a_wrong_rule_is_far_from_every_tie():
    """What the benchmark's routing limit has to refuse: three wrong rules
    on random scores are past the ladder's end in some row of a batch, as
    they are in every batch of the chip's check."""
    cor = jax.nn.sigmoid(1.7 * jax.random.normal(jax.random.PRNGKey(0),
                                                 (256, 32)))
    right = ref.choose(cor, SIZES)
    assert ref.tie_distance(cor, right, SIZES).max() == 0.0
    no_groups = jax.lax.top_k(cor, 4)[1]
    c = np.asarray(cor)                    # groups scored by their best alone
    keep = np.argsort(-c.reshape(256, 4, 8).max(-1), -1)[:, :2]
    mask = np.zeros((256, 4), bool)
    np.put_along_axis(mask, keep, True, -1)
    by_best = np.argsort(-np.where(np.repeat(mask, 8, 1), c, 0.0), -1)[:, :4]
    two_of_four = ref.choose(cor, {**SIZES, "topk_group": 1})
    for wrong in (no_groups, by_best, two_of_four):
        assert np.isinf(ref.tie_distance(cor, wrong, SIZES).max())


def test_forced_choice_is_summed_with_the_layers_own_scores():
    p, x = _layer_inputs()
    free, idx, _ = ref._moe(x[None], p, SIZES, 0)
    same = ref._moe(x[None], p, SIZES, 0, forced=idx)[0]
    np.testing.assert_allclose(np.asarray(same), np.asarray(free),
                               rtol=1e-6, atol=1e-7)
    other = ref._moe(x[None], p, SIZES, 0, forced=(idx + 1) % 32)[0]
    assert not np.allclose(np.asarray(other), np.asarray(free), atol=1e-3)


# -- the grouped matmul ---------------------------------------------------------------

@pytest.mark.parametrize("impl", ["kernel", "reference"])
@pytest.mark.parametrize("counts", [[5, 0, 17, 1, 0, 9], [0, 0, 0, 0, 0, 3],
                                    [0, 0, 0, 0, 0, 0], [8, 8, 8, 8, 8, 8]])
def test_grouped_matmul_uneven_and_empty_groups(impl, counts):
    bm, k, n, g = 8, 32, 128, len(counts)
    max_rows = 48
    starts, tile_group, used = gmm.tile_layout(
        jnp.asarray(counts, jnp.int32), bm, max_rows)
    assert int(used) == sum(-(-c // bm) for c in counts)
    rows = gmm.num_tiles(g, bm, max_rows) * bm
    rng = np.random.RandomState(0)
    x = rng.randn(rows, k).astype(np.float32)
    w = rng.randn(g, k, n).astype(np.float32)
    out = np.asarray(gmm.grouped_matmul(
        jnp.asarray(x), jnp.asarray(w), tile_group, used, block_m=bm,
        impl=impl, interpret=True))
    for e, (c, s) in enumerate(zip(counts, np.asarray(starts))):
        want = np.einsum("mk,kn->mn", x[s:s + c], w[e])
        np.testing.assert_allclose(out[s:s + c], want, rtol=2e-5, atol=2e-5)
    # every tile that holds rows belongs to the group whose rows they are
    tg = np.asarray(tile_group)
    for e, (c, s) in enumerate(zip(counts, np.asarray(starts))):
        assert all(tg[t] == e for t in range(s // bm, (s + c + bm - 1) // bm))


@pytest.mark.parametrize("counts", [[5, 0, 17, 1, 0, 9], [0, 0, 0, 0, 0, 3],
                                    [0, 0, 0, 0, 0, 0], [8, 8, 8, 8, 8, 8],
                                    [1, 1, 1, 1, 1, 1]])
def test_grouped_matmul_differentiates_like_the_einsum(counts):
    """The kernel path's ``custom_vjp`` (dx: the forward's kernel against
    the transposed weight block; dw: a kernel of its own) against
    ``jax.grad`` of the ``impl="reference"`` einsum, for uneven, empty and
    single-row groups. The cotangent is zero outside the rows that hold a
    token (what the expert layer's gathers hand back); dx is compared in the
    used tiles (past them it is undefined, as the forward's rows are)."""
    bm, k, n, g = 8, 128, 256, len(counts)
    max_rows = 48
    starts, tile_group, used = gmm.tile_layout(
        jnp.asarray(counts, jnp.int32), bm, max_rows)
    rows = gmm.num_tiles(g, bm, max_rows) * bm
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(rows, k), jnp.float32)
    w = jnp.asarray(rng.randn(g, k, n) * 0.1, jnp.float32)
    real = np.zeros((rows, 1), np.float32)
    for c, s in zip(counts, np.asarray(starts)):
        real[s:s + c] = 1.0
    ct = jnp.asarray(rng.randn(rows, n).astype(np.float32) * real)

    def grads(impl):
        def f(x, w):
            y = gmm.grouped_matmul(x, w, tile_group, used, block_m=bm,
                                   impl=impl, interpret=True)
            return jnp.sum(jnp.where(real > 0, y, 0.0) * ct)
        return jax.grad(f, (0, 1))(x, w)

    (dx, dw), (dx_ref, dw_ref) = grads("kernel"), grads("reference")
    live = np.arange(rows) // bm < int(used)
    np.testing.assert_allclose(np.asarray(dx)[live], np.asarray(dx_ref)[live],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               rtol=1e-4, atol=1e-4)
    for e, c in enumerate(counts):      # a group without a row: exact zeros
        if c == 0:
            assert not np.asarray(dw[e]).any()


def test_tiles_past_the_used_ones_give_dw_nothing():
    """Rows that lie in tiles past ``tiles_used`` hold whatever the caller
    left there (here: large numbers, in ``x`` and in the cotangent): the dw
    kernel skips those tiles as the forward does."""
    bm, k, n = 8, 128, 128
    counts = jnp.asarray([3, 9], jnp.int32)
    starts, tile_group, used = gmm.tile_layout(counts, bm, 40)
    rows = tile_group.shape[0] * bm
    live = (np.arange(rows) // bm < int(used))[:, None]
    rng = np.random.RandomState(1)
    x = np.where(live, rng.randn(rows, k), 1e6).astype(np.float32)
    ct = np.where(live, rng.randn(rows, n), 1e6).astype(np.float32)
    w = jnp.asarray(rng.randn(2, k, n), jnp.float32)

    def dw_of(x, ct):
        return jax.grad(lambda w: jnp.sum(gmm.grouped_matmul(
            jnp.asarray(x), w, tile_group, used, block_m=bm,
            interpret=True) * jnp.asarray(ct)))(w)

    np.testing.assert_array_equal(
        np.asarray(dw_of(x, ct)),
        np.asarray(dw_of(np.where(live, x, 0.0), np.where(live, ct, 0.0))))


def test_a_forward_that_is_not_differentiated_traces_the_forward_kernel_alone(
        monkeypatch):
    """The ``custom_vjp`` costs a serving program nothing: no backward
    kernel is traced where nobody takes a derivative."""
    seen = []
    real = gmm.pl.pallas_call

    def spy(kernel, **kw):
        seen.append(getattr(kernel, "func", kernel).__name__)
        return real(kernel, **kw)

    monkeypatch.setattr(gmm.pl, "pallas_call", spy)
    starts, tile_group, used = gmm.tile_layout(
        jnp.asarray([3, 9], jnp.int32), 8, 40)
    x = jnp.zeros((tile_group.shape[0] * 8, 128), jnp.float32)
    w = jnp.zeros((2, 128, 128), jnp.float32)

    def f(x, w):
        return gmm.grouped_matmul(x, w, tile_group, used, block_m=8,
                                  interpret=True)

    jax.eval_shape(f, x, w)
    assert seen == ["_kernel"]
    jax.eval_shape(jax.grad(lambda x, w: jnp.sum(f(x, w)), (0, 1)), x, w)
    assert seen == ["_kernel", "_kernel", "_kernel", "_dw_kernel"]


# -- the expert layer -------------------------------------------------------------------

def _layer_inputs(seed=1, t=24):
    p = ds.init_params(CFG, jax.random.PRNGKey(seed))["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (t, CFG.hidden_size),
                          jnp.float32)
    return p, x


@pytest.mark.parametrize("impl", ["kernel", "reference"])
def test_expert_layer_is_the_reference_layer(impl):
    p, x = _layer_inputs()
    got, stats = moe_dropless.expert_layer(CFG, p, x, impl=impl,
                                           interpret=True)
    want, idx, _ = ref._moe(x[None], p, SIZES, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-6)
    assert int(stats["assignments_local"]) == 24 * 4
    assert np.array_equal(np.sort(np.asarray(stats["idx"]), -1),
                          np.sort(np.asarray(idx[0]), -1))


def test_the_shares_add_up():
    """16 chips of 2 experts each: their parts, with the shared expert
    counted once, are the uncut layer."""
    p, x = _layer_inputs()
    want = ref._moe(x[None], p, SIZES, 0)[0]
    sh = p["shared"]
    shared = np.asarray(ds.gated_mlp(x, sh["gate"], sh["up"], sh["down"]),
                        np.float64)             # every chip computes it alike
    total, handed = shared, 0
    for share in range(16):
        cfg = dataclasses.replace(CFG, first_expert=2 * share,
                                  n_local_experts=2)
        part = {**p, "experts": {k: v[2 * share:2 * share + 2]
                                 for k, v in p["experts"].items()}}
        y, stats = moe_dropless.expert_layer(cfg, part, x, impl="reference")
        # the reference, given the same share, leaves out the same experts
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref._moe(x[None], part, SIZES,
                                               2 * share)[0][0]),
            rtol=2e-5, atol=2e-6)
        total = total + np.asarray(y, np.float64) - shared
        handed += int(stats["assignments_local"])
    assert handed == 24 * 4                     # every assignment, once
    np.testing.assert_allclose(total, np.asarray(want[0]), rtol=2e-5,
                               atol=5e-6)


def test_dropless_when_every_token_picks_one_expert():
    """A load no capacity factor would hold: the router sends all 24
    tokens to the same four experts."""
    p, x = _layer_inputs()
    bias = np.zeros(32, np.float32)
    bias[[3, 4, 11, 12]] = 10.0
    p = {**p, "bias": jnp.asarray(bias)}
    got, stats = moe_dropless.expert_layer(CFG, p, x, impl="kernel",
                                           interpret=True)
    want = ref._moe(x[None], p, SIZES, 0)[0]
    assert int(stats["expert_load_max"]) == 24
    assert int(stats["experts_touched"]) == 4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-6)


def test_inactive_rows_are_routed_and_counted_nowhere():
    p, x = _layer_inputs()
    active = jnp.arange(24) < 10
    _, stats = moe_dropless.expert_layer(CFG, p, x, active=active,
                                         impl="reference")
    assert int(stats["assignments_local"]) == 10 * 4


# -- through the engine ------------------------------------------------------------------

@pytest.mark.parametrize("impls", [REFERENCE, KERNELS],
                         ids=["reference", "kernels-interpreted"])
def test_prefill_then_decode_is_the_full_forward(params, impls):
    """Prefill (expanded attention) then decode steps (absorbed, through
    the paged latent pool) against the reference's one full forward."""
    eng, ids = _serve(params, **impls)
    for sid, prompt in zip(ids, PROMPTS):
        tokens = eng.seqs[sid].tokens
        want, chosen, _ = ref.forward(params, jnp.asarray([tokens]), SIZES,
                                      routing=True)
        want, chosen = np.asarray(want[0]), np.asarray(chosen[:, 0])
        aux = eng.aux_log[sid]
        # a prefill keeps every prompt row's choice, a decode step its row's
        mine = np.concatenate(
            [aux[len(prompt)]["moe_idx"][:len(prompt)]]
            + [aux[len(prompt) + j]["moe_idx"][None]
               for j in range(1, N_NEW)])            # [rows, layers, k]
        assert np.array_equal(
            np.sort(mine, -1),
            np.sort(chosen[:, :len(mine)].transpose(1, 0, 2), -1))
        for j in range(N_NEW):
            row = len(prompt) + j - 1
            assert _rel(eng.logits_log[sid][row + 1], want[row]) < 2e-5


def test_full_forward_logits_at_every_prompt_length(params):
    """Prefill alone IS the full forward: its logits at the last position,
    for every length up to the padded prompt length."""
    tokens = list(np.random.RandomState(3).randint(0, 96, 16))
    want = _want(params, tokens)
    eng, ids = _serve(params, prompts=[tokens[:n] for n in (1, 7, 8, 9, 16)],
                      n_new=1)
    for sid in ids:
        n = len(eng.seqs[sid].prompt)
        assert _rel(eng.logits_log[sid][n], want[n - 1]) < 2e-5


def test_absorbed_attention_is_expanded_attention(params):
    """The same position reached through prefill (expanded form) and
    through a decode step (absorbed form over the cached latent)."""
    tokens = list(np.random.RandomState(4).randint(0, 96, 12))
    eng, (a, b) = _serve(params, prompts=[tokens, tokens[:11]], n_new=2)
    # request b decodes token 11 only if it sampled it; feed it by hand.
    # The fed token lives on the device (the engine's ``last_tok`` row of
    # the sequence), so teacher-forcing sets it there too, after a drain
    # has put the prefill's own token on the host
    eng2 = _engine(params)
    sid = eng2.add_request(tokens[:11], 2)
    eng2.step()                                   # prefill: dispatches one
    eng2._drain("test")                           # its token, by value
    seq = eng2.seqs[sid]
    seq.tokens[-1] = tokens[11]                   # teacher-force token 11
    eng2._last_tok = eng2._last_tok.at[seq.slot].set(tokens[11])
    eng2.step()                                   # decode it (absorbed)
    eng2._drain("test")
    np.testing.assert_allclose(eng2.logits_log[sid][12],
                               eng.logits_log[a][12], rtol=1e-4, atol=1e-5)


def test_bf16_model_is_within_a_bf16_tolerance():
    cfg = _cfg(jnp.bfloat16)
    params = ds.init_params(cfg, jax.random.PRNGKey(0))
    eng, ids = _serve(params, cfg=cfg, **KERNELS)
    errs = []
    for sid, prompt in zip(ids, PROMPTS):
        want = _want(params, eng.seqs[sid].tokens)
        errs += [_rel(eng.logits_log[sid][len(prompt) + j],
                      want[len(prompt) + j - 1]) for j in range(N_NEW)]
    assert 1e-4 < max(errs) < 3e-2, errs


def _assert_bitwise_equal(a, b, ids):
    for sid in ids:
        assert set(a.logits_log[sid]) == set(b.logits_log[sid])
        for pos, row in a.logits_log[sid].items():
            assert np.array_equal(row, b.logits_log[sid][pos]), (sid, pos)


@pytest.mark.parametrize("impls", [REFERENCE, KERNELS],
                         ids=["reference", "kernels-interpreted"])
def test_evict_and_readmit_through_the_latent_pool_is_bit_exact(params,
                                                                impls):
    """A pool too small for both sequences: the scheduler evicts one,
    re-admits it and replays its tokens through the same decode program;
    tokens and every logits row equal the roomy pool's, bit for bit."""
    prompts = [PROMPTS[1], PROMPTS[2]]
    roomy, ids = _serve(params, prompts=prompts, n_new=8, num_pages=24,
                        **impls)
    tight, ids2 = _serve(params, prompts=prompts, n_new=8, num_pages=6,
                         **impls)
    assert ids == ids2
    assert sum(s.n_preemptions for s in tight.seqs.values()) >= 1
    assert [roomy.seqs[i].tokens for i in ids] == \
        [tight.seqs[i].tokens for i in ids]
    _assert_bitwise_equal(roomy, tight, ids)


def test_one_round_ahead_equals_the_synchronous_order_bit_for_bit(params):
    """The engine dispatches a round before it has read the last one's
    tokens. Through the latent pool and the expert layers that changes
    nothing: tokens, every logits row and every routing choice equal those
    of the synchronous order (a drain after every step), a token is
    counted only once its value is in ``seq.tokens``, and ``run()`` leaves
    nothing in flight."""
    requests = [(PROMPTS[0], 6), (PROMPTS[2], 1), (PROMPTS[1], 4),
                (PROMPTS[0][:2], 7), (PROMPTS[2][3:], 3), (PROMPTS[1], 2)]
    ahead, sync = _engine(params), _engine(params)
    for eng in (ahead, sync):
        ids = [eng.add_request(p, n) for p, n in requests]
        while eng.sched.has_work:
            eng.step()
            assert eng.tokens_generated == sum(
                s.num_generated for s in eng.seqs.values())
            if eng is sync:
                eng._drain("test")
    assert ahead._in_flight == [] and ahead.run() == sync.run()
    _assert_bitwise_equal(ahead, sync, ids)
    for sid, (prompt, n) in zip(ids, requests):
        rows = list(range(len(prompt), len(prompt) + n))
        assert sorted(ahead.logits_log[sid]) == rows
        assert sorted(ahead.aux_log[sid]) == rows
        for pos in rows:
            assert np.array_equal(ahead.aux_log[sid][pos]["moe_idx"],
                                  sync.aux_log[sid][pos]["moe_idx"])
    assert (ahead._decode._cache_size(), ahead._prefill._cache_size()) == \
        (1, 1)


# -- the pool's geometry and what the engine refuses ----------------------------------------

def test_latent_pool_geometry_and_bytes(params):
    eng = _engine(params)
    assert latent_row_width(CFG) == 128              # 32 + 8, padded
    assert eng.state.pools[0].shape == (1, 24, 8, 128)
    assert len(eng.state.pools) == CFG.num_layers
    c = eng.ccfg
    assert c.bytes_per_page() == 3 * 8 * 128 * 4     # layers x page x row
    assert c.pool_bytes() == 24 * c.bytes_per_page()
    assert c.occupancy_bytes(5) == 5 * c.bytes_per_page()
    # the published row: 512 + 64 -> 640 lanes, 1,280 B a token a layer
    pub = _cfg(jnp.bfloat16, kv_lora_rank=512, qk_rope_head_dim=64)
    ccfg = DeepseekServed(pub).cache_config(num_pages=5121, page_size=128)
    assert ccfg.width == 640
    assert ccfg.bytes_per_page() == 3 * 128 * 640 * 2


@pytest.mark.parametrize("kw,what", [({"fp8_kv": True}, "fp8 latent pool"),
                                     ({"fp8_weights": True}, "fp8 weights"),
                                     ({"spec_k": 2}, "speculative")])
def test_engine_refuses_what_is_out_of_scope(params, kw, what):
    with pytest.raises(NotImplementedError, match=what):
        _engine(params, **kw)


def test_counters_of_a_decode_round(params):
    rec = monitor.Recorder(name="t", traced_hooks=False)
    monitor.attach(rec)
    try:
        eng, _ = _serve(params)
    finally:
        monitor.detach()
    c = rec.counters()
    assert c["serve/latent_bytes_per_token"] == 128 * 4
    ev = [e for e in rec.records("counter")
          if e["name"] == "moe/assignments_local"]
    rounds = len(eng.decode_step_times)
    assert len(ev) == 2 * rounds and {e["layer"] for e in ev} == {0, 1}
    # the whole model is held: every active row's 4 assignments are local
    assert c["moe/assignments_local"] == 2 * 4 * (len(PROMPTS) * (N_NEW - 1))
    assert c["moe/expert_load_max"] >= c["moe/assignments_local"] / 32


# -- nothing new on the old models' import path ------------------------------------------------

def test_the_new_modules_are_imported_on_demand_only():
    code = ("import sys\n"
            "import apex_tpu, apex_tpu.models, apex_tpu.serve, apex_tpu.ops\n"
            "new = ['apex_tpu.models.deepseek', 'apex_tpu.serve.deepseek',\n"
            "       'apex_tpu.transformer.moe_dropless',\n"
            "       'apex_tpu.ops.mla_attention',\n"
            "       'apex_tpu.ops.grouped_matmul']\n"
            "print([m for m in new if m in sys.modules])\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, check=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip() == "[]", out.stdout


# -- the benchmark's configuration file, as the family reads it --------------------------------

def test_the_configuration_file_is_the_published_config_cut_by_reduced():
    """Every ``config.json`` key sits at the file's top level as it is run
    (the driver compares those with the catalog) and untouched under
    ``published``; only the keys ``reduced`` lists differ, and the family
    builds the chip's share from them."""
    import json
    from benchmarks.families import deepseek as family
    path = os.path.join(ROOT, "benchmarks", "configs",
                        "gigachat3.1-702b-ep16.json")
    with open(path) as f:
        body = json.load(f)
    pub = body["published"]
    assert {k for k in pub if body[k] != pub[k]} == set(body["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    cfg = family.model_config(body, max_seq_len=2560)
    assert (cfg.num_layers, cfg.first_k_dense_replace, cfg.vocab_size) == (
        5, 1, 16032)
    assert (cfg.n_routed_experts, cfg.first_expert, cfg.n_local_experts) == (
        256, 0, 16)
    assert (cfg.hidden_size, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.v_head_dim, cfg.moe_intermediate_size) == (
        7168, 1536, 512, 192, 2048)
