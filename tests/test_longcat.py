"""The LongCat-Flash model (``models/longcat.py``): its routing rule and
zero-compute slots in the dropless expert layer, its two-attentions-a-layer
shortcut topology, and its path through the serve engine over a pool of two
latent leaves a layer, against the plain reference the benchmark keeps
(``benchmarks/reference/longcat.py``), at tiny sizes that keep every
structure: q_lora / kv_lora / nope / rope / v_head_dim all different
(``v_head_dim`` under the key head, as published), 16 experts + 8 identity
slots, top 4, two layers."""

import dataclasses
import json
import os
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
from apex_tpu.models import longcat as lc
from apex_tpu.serve.latent import latent_row_width
from apex_tpu.serve.longcat import LongcatServed
from apex_tpu.transformer import moe_dropless
from benchmarks.reference import longcat as ref

#: the reference reads the published key names
SIZES = dict(num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=12, kv_lora_rank=32, q_lora_rank=48,
             rms_norm_eps=1e-5, rope_theta=1e7, mla_scale_q_lora=True,
             mla_scale_kv_lora=True, n_routed_experts=16, zero_expert_num=8,
             moe_topk=4, routed_scaling_factor=6.0)
STATIC = ref.static(SIZES)


def _cfg(dtype=jnp.float32, **kw):
    base = dict(vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
                q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=12, ffn_hidden_size=160,
                expert_ffn_hidden_size=32, n_routed_experts=16,
                zero_expert_num=8, moe_topk=4, routed_scaling_factor=6.0,
                max_seq_len=64, dtype=dtype)
    return lc.LongcatConfig(**{**base, **kw})


CFG = _cfg()
PROMPTS = [list(range(3, 8)), list(range(20, 31)), list(range(40, 56))]
N_NEW = 5
REFERENCE = dict(paged_impl="reference", attention_impl="reference")
KERNELS = dict(paged_impl="kernel", attention_impl="flash", interpret=True)
IMPLS = pytest.mark.parametrize("impls", [REFERENCE, KERNELS],
                                ids=["reference", "kernels-interpreted"])


@pytest.fixture(scope="module")
def params():
    return lc.init_params(CFG, jax.random.PRNGKey(0))


def _engine(params, cfg=CFG, num_pages=24, **kw):
    kw = {**REFERENCE, **kw}
    return serve.ServeEngine(LongcatServed(cfg), params,
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


# -- the routing rule --------------------------------------------------------------

def _route_on(p, bias=None):
    """Route one token whose softmax scores are ``p`` [24] (they sum to 1)."""
    cfg = _cfg(hidden_size=1)
    router = np.log(p)[None, :]                           # x = [[1.0]]
    bias = np.zeros_like(p) if bias is None else bias
    args = (jnp.asarray(router, jnp.float32), jnp.asarray(bias, jnp.float32))
    one = jnp.ones((1, 1), jnp.float32)
    idx, w = moe_dropless.route(cfg, *args, one)
    r_idx, r_w = ref.route(one, *args, SIZES)
    assert sorted(np.asarray(idx)[0]) == sorted(np.asarray(r_idx)[0])
    np.testing.assert_allclose(np.sort(np.asarray(w)[0]),
                               np.sort(np.asarray(r_w)[0]), rtol=1e-6)
    return np.asarray(idx)[0], np.asarray(w)[0]


def _scores():
    p = np.full(24, 0.02)
    p[[2, 9]] = 0.20, 0.15          # two real experts ...
    p[[17, 22]] = 0.12, 0.10        # ... and two identity slots (>= 16)
    p[5] = 0.05                     # fifth: misses
    return p


def test_routing_rule_on_a_hand_made_case():
    """A softmax over ALL 24 slots, top 4 with no groups: the weights are
    the chosen scores times 6 and are NOT renormalised."""
    p = _scores()
    assert p.sum() == pytest.approx(1.0)
    idx, w = _route_on(p)
    assert sorted(idx) == [2, 9, 17, 22]
    by = dict(zip(idx, w))
    assert by[2] == pytest.approx(6 * 0.20, rel=1e-5)
    assert by[22] == pytest.approx(6 * 0.10, rel=1e-5)
    assert w.sum() == pytest.approx(6 * 0.57, rel=1e-5)     # not 6


def test_bias_changes_the_choice_and_not_the_weight():
    p, bias = _scores(), np.zeros(24)
    bias[5] = 0.08                  # lifts 0.05 over the fourth (0.10)
    idx, w = _route_on(p, bias)
    assert sorted(idx) == [2, 5, 9, 17]
    by = dict(zip(idx, w))
    assert by[5] == pytest.approx(6 * 0.05, rel=1e-5)       # not 0.13


def test_a_zero_slot_returns_its_weight_times_the_input():
    """A token whose whole choice falls on identity slots gets nothing from
    the experts: its row is the sum of the slots' weights times itself."""
    moe = lc.init_params(CFG, jax.random.PRNGKey(1))["layer_0"]["moe"]
    bias = np.zeros(24, np.float32)
    bias[[16, 18, 20, 23]] = 10.0
    moe = {**moe, "bias": jnp.asarray(bias)}
    x = jax.random.normal(jax.random.PRNGKey(2), (6, 64), jnp.float32)
    y, stats = moe_dropless.expert_layer(CFG, moe, x, impl="reference")
    idx, w = moe_dropless.route(CFG, moe["router"], moe["bias"], x)
    assert (np.asarray(idx) >= 16).all()
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(w.sum(-1, keepdims=True) * x), rtol=1e-6)
    assert int(stats["assignments_zero"]) == 6 * 4
    assert int(stats["assignments_local"]) == 0
    assert int(stats["real_experts_per_token_max"]) == 0


def test_tie_distance_of_a_choice():
    """0 where the rule picks the choice, the relative move that would make
    it where a near-tie decides, large where nothing near would."""
    cor = np.full((3, 24), 0.001)
    cor[:, [2, 9, 17]] = 0.20, 0.15, 0.12
    cor[:, 22], cor[:, 5] = 0.0100, 0.0098          # fourth and fifth
    theirs = np.asarray([[2, 9, 17, 22], [2, 9, 17, 5], [0, 1, 3, 4]])
    need = ref.tie_distance(cor, theirs)
    assert need[0] == 0.0
    assert need[1] == pytest.approx(0.0002 / 0.0198)    # ~1% each way
    assert need[2] > 0.9
    assert np.isinf(ref.tie_distance(cor - 0.002, theirs)[2])


def test_a_wrong_rule_is_far_from_every_tie():
    """What the benchmark's routing limit has to refuse: on seeded weights
    a choice made without the correction bias, or among the real experts
    alone, is far from a tie in some row of a batch."""
    moe, x = _layer_inputs(t=256)
    _, cor = ref.scores(x, moe["router"], moe["bias"])
    right = ref.choose(cor, SIZES)
    assert ref.tie_distance(cor, right).max() == 0.0
    p = cor - moe["bias"]
    for wrong in (jax.lax.top_k(p, 4)[1], jax.lax.top_k(cor[:, :16], 4)[1]):
        assert ref.tie_distance(cor, wrong).max() > 0.2


# -- the expert layer -------------------------------------------------------------------

def _layer_inputs(seed=1, t=24):
    p = lc.init_params(CFG, jax.random.PRNGKey(seed))["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (t, CFG.hidden_size),
                          jnp.float32)
    return p["moe"], x


def _ref_experts(moe, x, first_expert=0, **kw):
    """The reference's expert layer on rows that ARE the normalised state
    (a norm weight of 1 over rows of unit mean square changes nothing, so
    the rows are normalised here first)."""
    m = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
    sub = {"ffn_norm": jnp.ones((x.shape[1],), jnp.float32)}
    return m, ref.experts(x[None], sub, moe, sizes=STATIC,
                          first_expert=first_expert, **kw)


@pytest.mark.parametrize("impl", ["kernel", "reference"])
def test_expert_layer_is_the_reference_layer(impl):
    moe, x = _layer_inputs()
    m, (want, idx, _) = _ref_experts(moe, x)
    got, stats = moe_dropless.expert_layer(CFG, moe, m, impl=impl,
                                           interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               rtol=2e-5, atol=2e-6)
    assert np.array_equal(np.sort(np.asarray(stats["idx"]), -1),
                          np.sort(np.asarray(idx[0]), -1))
    zero = int((np.asarray(idx) >= 16).sum())
    assert 0 < zero < 24 * 4
    assert int(stats["assignments_zero"]) == zero
    assert int(stats["assignments_local"]) == 24 * 4 - zero
    assert int(stats["real_experts_per_token_max"]) == \
        int((np.asarray(idx[0]) < 16).sum(-1).max())


def test_the_shares_add_up():
    """8 chips of 2 experts each: their routed parts, with the identity
    part (which every chip computes alike for the tokens it holds) counted
    once, are the uncut layer."""
    moe, x = _layer_inputs()
    m, (want, idx, _) = _ref_experts(moe, x)
    _, w = moe_dropless.route(CFG, moe["router"], moe["bias"], m)
    zero_w = np.where(np.asarray(idx[0]) >= 16, np.asarray(w), 0.0).sum(-1)
    # the order of idx and w agree: both are top_k of the same scores
    identity = zero_w[:, None].astype(np.float64) * np.asarray(m, np.float64)
    total, handed = identity, 0
    for share in range(8):
        cfg = dataclasses.replace(CFG, first_expert=2 * share,
                                  n_local_experts=2)
        part = {**moe, "experts": {k: v[2 * share:2 * share + 2]
                                   for k, v in moe["experts"].items()}}
        y, stats = moe_dropless.expert_layer(cfg, part, m, impl="reference")
        # the reference, given the same share, leaves out the same experts
        np.testing.assert_allclose(
            np.asarray(y),
            np.asarray(_ref_experts(part, x, 2 * share)[1][0][0]),
            rtol=2e-5, atol=2e-6)
        total = total + np.asarray(y, np.float64) - identity
        handed += int(stats["assignments_local"])
        assert int(stats["assignments_zero"]) == int(
            (np.asarray(idx) >= 16).sum())
    assert handed == int((np.asarray(idx) < 16).sum())   # each real one, once
    np.testing.assert_allclose(total, np.asarray(want[0]), rtol=2e-5,
                               atol=5e-6)


def test_forced_choice_is_summed_with_the_layers_own_scores():
    moe, x = _layer_inputs()
    _, (free, idx, _) = _ref_experts(moe, x)
    same = _ref_experts(moe, x, forced=idx)[1][0]
    np.testing.assert_allclose(np.asarray(same), np.asarray(free),
                               rtol=1e-6, atol=1e-7)
    other = _ref_experts(moe, x, forced=(idx + 1) % 24)[1][0]
    assert not np.allclose(np.asarray(other), np.asarray(free), atol=1e-3)


def test_inactive_rows_are_routed_and_counted_nowhere():
    moe, x = _layer_inputs()
    active = jnp.arange(24) < 10
    _, stats = moe_dropless.expert_layer(CFG, moe, x, active=active,
                                         impl="reference")
    assert int(stats["assignments_local"]) \
        + int(stats["assignments_zero"]) == 10 * 4


# -- the layer ---------------------------------------------------------------------------

def _forward_with(params, tokens, layer):
    """The reference's forward with ``layer`` in place of its own."""
    x = ref.embed(params["embed"], jnp.asarray([tokens]))
    for i in range(CFG.num_layers):
        x = layer(x, params[f"layer_{i}"])
    return np.asarray(ref.head(x, params["norm_f"], params["head"],
                               eps=1e-5)[0])


def test_the_expert_layer_sits_on_a_shortcut(params):
    """The expert layer reads the state after attention 0 and is added
    after feed-forward 1: the program agrees with the reference's layer,
    and a layer with either end of the shortcut moved does not."""
    def pieces(x, p):
        att = lambda x, j: ref.attention(x, p[f"sub_{j}"], sizes=STATIC)
        ffn = lambda x, j: ref.feed_forward(x, p[f"sub_{j}"], sizes=STATIC)
        moe = lambda x, j: ref.experts(x, p[f"sub_{j}"], p["moe"],
                                       sizes=STATIC)[0]
        return att, ffn, moe

    def published(x, p):
        return ref.layer(x, p, STATIC)[0]

    def read_late(x, p):            # the experts read the state after
        att, ffn, moe = pieces(x, p)        # attention 1 (its own norm)
        x = att(ffn(att(x, 0), 0), 1)
        return ffn(x, 1) + moe(x, 1)

    def added_early(x, p):          # added with feed-forward 0, so that
        att, ffn, moe = pieces(x, p)        # attention 1 sees it
        x = att(x, 0)
        x = ffn(x, 0) + moe(x, 0)
        return ffn(att(x, 1), 1)

    tokens = list(np.random.RandomState(5).randint(0, 96, 16))
    eng, (sid,) = _serve(params, prompts=[tokens], n_new=1)
    got = eng.logits_log[sid][16]
    assert _rel(got, _forward_with(params, tokens, published)[15]) < 2e-5
    for moved in (read_late, added_early):
        assert _rel(got, _forward_with(params, tokens, moved)[15]) > 1e-3


def test_the_two_latent_scales(params):
    """(64 / 48)^0.5 on the query latent and (64 / 32)^0.5 on the key-value
    latent: the program follows the reference with each on and off, and
    each moves the logits."""
    assert CFG.q_scale == pytest.approx((64 / 48) ** 0.5)
    assert CFG.kv_scale == pytest.approx(2 ** 0.5)
    pub = _cfg(hidden_size=6144, q_lora_rank=1536, kv_lora_rank=512)
    assert (pub.q_scale, pub.kv_scale) == (2.0, pytest.approx(12 ** 0.5))
    tokens = list(np.random.RandomState(6).randint(0, 96, 12))
    base = _want(params, tokens)
    for key in ("mla_scale_q_lora", "mla_scale_kv_lora"):
        cfg = dataclasses.replace(CFG, **{key: False})
        eng, (sid,) = _serve(params, prompts=[tokens], n_new=1, cfg=cfg)
        want = _want(params, tokens, {**SIZES, key: False})
        assert _rel(eng.logits_log[sid][12], want[11]) < 2e-5
        assert _rel(want, base) > 1e-3
    # at 1 nothing is multiplied: the shared projections are DeepSeek's
    p = params["layer_0"]["sub_0"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 64), jnp.float32)
    pos = jnp.arange(5)
    plain = ds.attention_inputs(CFG, p, x, pos)
    scaled = ds.attention_inputs(CFG, p, x, pos, q_scale=CFG.q_scale,
                                 kv_scale=CFG.kv_scale)
    np.testing.assert_allclose(np.asarray(scaled[2]),
                               np.asarray(plain[2]) * CFG.kv_scale,
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(scaled[3]),
                                  np.asarray(plain[3]))   # k_pe: not scaled


# -- through the engine ------------------------------------------------------------------

@IMPLS
def test_prefill_then_decode_is_the_full_forward(params, impls):
    """Prefill (expanded attention; with the kernels, V's 12 lanes padded
    to the key head's 24) then decode steps (absorbed, through the two
    latent leaves of each layer) against the reference's one full forward,
    choices included."""
    eng, ids = _serve(params, **impls)
    for sid, prompt in zip(ids, PROMPTS):
        tokens = eng.seqs[sid].tokens
        want, chosen, _ = ref.forward(params, jnp.asarray([tokens]), SIZES,
                                      routing=True)
        want, chosen = np.asarray(want[0]), np.asarray(chosen[:, 0])
        aux = eng.aux_log[sid]
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


@IMPLS
def test_full_forward_logits_at_every_prompt_length(params, impls):
    tokens = list(np.random.RandomState(3).randint(0, 96, 16))
    want = _want(params, tokens)
    eng, ids = _serve(params, prompts=[tokens[:n] for n in (1, 7, 8, 9, 16)],
                      n_new=1, **impls)
    for sid in ids:
        n = len(eng.seqs[sid].prompt)
        assert _rel(eng.logits_log[sid][n], want[n - 1]) < 2e-5


def test_absorbed_attention_is_expanded_attention(params):
    """The same position reached through prefill (expanded form, values of
    ``v_head_dim`` 12 under keys of 16 + 8) and through a decode step
    (absorbed form over the cached latent)."""
    assert CFG.v_head_dim != CFG.qk_nope_head_dim
    tokens = list(np.random.RandomState(4).randint(0, 96, 12))
    eng, (a,) = _serve(params, prompts=[tokens], n_new=1)
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
    params = lc.init_params(cfg, jax.random.PRNGKey(0))
    eng, ids = _serve(params, cfg=cfg, **KERNELS)
    errs = []
    for sid, prompt in zip(ids, PROMPTS):
        n = len(prompt)
        aux = eng.aux_log[sid]
        forced = np.zeros((CFG.num_layers, 1, n + N_NEW, 4), np.int32)
        forced[:, 0, :n] = aux[n]["moe_idx"][:n].transpose(1, 0, 2)
        for j in range(1, N_NEW):
            forced[:, 0, n + j - 1] = aux[n + j]["moe_idx"]
        # over the slots the program chose: a flipped near-tie is the
        # choice's to answer for, not the arithmetic's (the chip's check)
        want = _want(params, eng.seqs[sid].tokens,
                     forced=jnp.asarray(forced))
        errs += [_rel(eng.logits_log[sid][n + j], want[n + j - 1])
                 for j in range(N_NEW)]
    assert 1e-4 < max(errs) < 3e-2, errs


def _assert_bitwise_equal(a, b, ids):
    for sid in ids:
        assert set(a.logits_log[sid]) == set(b.logits_log[sid])
        for pos, row in a.logits_log[sid].items():
            assert np.array_equal(row, b.logits_log[sid][pos]), (sid, pos)


@IMPLS
def test_evict_and_readmit_through_both_leaves_is_bit_exact(params, impls):
    """A pool too small for both sequences: the scheduler evicts one,
    re-admits it and replays its tokens through the same decode program,
    into both leaves of every layer; tokens and every logits row equal the
    roomy pool's, bit for bit."""
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
        for pos in range(len(prompt), len(prompt) + n):
            assert np.array_equal(ahead.aux_log[sid][pos]["moe_idx"],
                                  sync.aux_log[sid][pos]["moe_idx"])
    assert (ahead._decode._cache_size(), ahead._prefill._cache_size()) == \
        (1, 1)


# -- the pool's geometry and what the engine refuses ----------------------------------------

def test_two_latent_leaves_a_layer_and_their_bytes(params):
    eng = _engine(params)
    assert latent_row_width(CFG) == 128              # 32 + 8, padded
    assert len(eng.state.pools) == 2 * CFG.num_layers
    assert eng.state.pools[3].shape == (1, 24, 8, 128)
    c = eng.ccfg
    assert c.num_layers == 4
    assert c.bytes_per_page() == 4 * 8 * 128 * 4     # leaves x page x row
    assert c.pool_bytes() == 24 * c.bytes_per_page()
    # the published row: 512 + 64 -> 640 lanes, 2 x 1,280 B a token a layer;
    # cell 6's pool: 4 layers, 3,073 pages of 128 tokens, 4.03 GB
    pub = _cfg(jnp.bfloat16, num_layers=4, kv_lora_rank=512,
               qk_rope_head_dim=64)
    ccfg = LongcatServed(pub).cache_config(num_pages=3073, page_size=128)
    assert (ccfg.width, ccfg.num_layers) == (640, 8)
    assert ccfg.bytes_per_page() == 128 * 4 * 2 * 1280
    assert ccfg.pool_bytes() == 3073 * 128 * 10240 == 4_027_842_560


@pytest.mark.parametrize("kw,what", [({"fp8_kv": True}, "fp8 latent pool"),
                                     ({"fp8_weights": True}, "fp8 weights"),
                                     ({"spec_k": 2}, "speculative")])
def test_engine_refuses_what_is_out_of_scope(params, kw, what):
    with pytest.raises(NotImplementedError, match=what):
        _engine(params, **kw)


def test_a_share_outside_the_routed_experts_is_refused():
    with pytest.raises(ValueError, match="not among the 16 routed"):
        _cfg(first_expert=12, n_local_experts=8)     # 12-19: identity slots


def test_counters_of_a_decode_round(params):
    rec = monitor.Recorder(name="t", traced_hooks=False)
    monitor.attach(rec)
    try:
        eng, _ = _serve(params)
    finally:
        monitor.detach()
    c = rec.counters()
    assert c["serve/latent_bytes_per_token"] == 2 * 128 * 4
    rounds = len(eng.decode_step_times)
    for name in ("assignments_local", "assignments_zero",
                 "expert_load_max", "experts_touched",
                 "real_experts_per_token_max"):
        ev = [e for e in rec.records("counter")
              if e["name"] == f"moe/{name}"]
        assert len(ev) == 2 * rounds and {e["layer"] for e in ev} == {0, 1}
    # the whole model is held: every active row's 4 choices are a local
    # expert or an identity slot
    rows = len(PROMPTS) * (N_NEW - 1)
    assert c["moe/assignments_local"] + c["moe/assignments_zero"] == \
        2 * 4 * rows
    assert 0 < c["moe/assignments_zero"] < 2 * 4 * rows
    assert c["moe/real_experts_per_token_max"] <= 2 * rounds * 4


# -- the benchmark's configuration file, as the family reads it --------------------------------

def test_the_configuration_file_is_the_published_config_cut_by_reduced():
    """Every ``config.json`` key sits at the file's top level as it is run
    and untouched under ``published``; only the keys ``reduced`` lists
    differ, and the family builds the chip's share from them."""
    from benchmarks.families import longcat as family
    path = os.path.join(ROOT, "benchmarks", "configs",
                        "longcat-flash-560b-ep32.json")
    with open(path) as f:
        body = json.load(f)
    pub = body["published"]
    assert {k for k in pub if body[k] != pub[k]} == set(body["reduced"]) == {
        "num_layers", "n_routed_experts", "vocab_size"}
    assert (pub["num_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (28, 512, 131072)
    cfg = family.model_config(body, max_seq_len=1536)
    assert (cfg.num_layers, cfg.vocab_size) == (4, 16384)
    assert (cfg.n_routed_experts, cfg.zero_expert_num, cfg.router_slots,
            cfg.moe_topk, cfg.first_expert, cfg.n_local_experts) == (
        512, 256, 768, 12, 0, 16)
    assert (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size,
            cfg.routed_scaling_factor) == (
        6144, 64, 1536, 512, 128, 64, 128, 12288, 2048, 6)
    assert (cfg.q_scale, cfg.rope_theta, cfg.rms_norm_eps) == (
        2.0, 1e7, 1e-5)
    # 28 x (638.8M + 512 x 37.75M) + 2 x 131,072 x 6,144 = 560.7B
    per_layer = 2 * (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576
                     + 512 * 64 * 256 + 8192 * 6144
                     + 3 * 6144 * 12288) + 6144 * 768
    total = 28 * (per_layer + 512 * 3 * 6144 * 2048) + 2 * 131072 * 6144
    assert round(total / 1e9, 1) == 560.7
