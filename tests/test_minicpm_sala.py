"""MiniCPM-SALA on the engine at a tiny size on the CPU, seeded weights,
against the plain reference (``benchmarks/reference/minicpm_sala.py``: run
pytest from the repo root): the lightning kernels against the token-by-token
recurrence, the block selection, chunked prefill + decode through both
caches against one full forward, the state's life with its batch row, and
the scheduler's chunk-a-round admission."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import serve
from apex_tpu.models import minicpm_sala as ms
from apex_tpu.ops import lightning_attention as la
from apex_tpu.ops import sparse_attention as sa
from apex_tpu.serve.minicpm_sala import MiniCPMSalaServed
from apex_tpu.serve.scheduler import (PREFILLING, RUNNING, WAITING,
                                      Scheduler, Sequence)
from benchmarks.reference import minicpm_sala as ref

SPEC = sa.SparseSpec(kernel_size=4, kernel_stride=2, block_size=8,
                     window_size=16, init_blocks=1, topk=4, dense_len=32)
MIX = (ms.SPARSE, ms.LIGHTNING, ms.LIGHTNING, ms.SPARSE)
KERNELS = dict(paged_impl="kernel", attention_impl="flash", interpret=True)


def _cfg(d=16, dtype=jnp.float32, mix=MIX):
    return ms.MiniCPMSalaConfig(
        vocab_size=96, hidden_size=64, intermediate_size=160,
        mixer_types=mix, num_attention_heads=4, num_key_value_heads=2,
        head_dim=d, lightning_nh=8, lightning_nkv=8, lightning_head_dim=d,
        mup_denominator=8, dim_model_base=32, sparse=SPEC, max_seq_len=96,
        dtype=dtype)


def _sizes(cfg):
    return dict(
        hidden_size=cfg.hidden_size, rms_norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta, scale_emb=cfg.scale_emb,
        scale_depth=cfg.scale_depth, mup_denominator=cfg.mup_denominator,
        dim_model_base=cfg.dim_model_base,
        **{k: getattr(cfg, k) for k in (
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "lightning_nh", "lightning_head_dim")},
        **dataclasses.asdict(SPEC))


def _engine(cfg, params, chunk=None, max_batch=3, **kw):
    return serve.ServeEngine(
        MiniCPMSalaServed(cfg), params, num_pages=40, max_seq_len=96,
        max_prompt_len=72, page_size=8, max_batch=max_batch,
        record_logits=True, prefill_chunk=chunk, **kw)


def _prompts(lens, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 96, n).tolist() for n in lens]


def _logits(eng, sid, n_prompt, n_new):
    return np.stack([eng.logits_log[sid][n_prompt + j]
                     for j in range(n_new)])


def _rel(a, b):
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, ms.init_params(cfg, jax.random.PRNGKey(0))


# -- the lightning kernels against the recurrence ---------------------------------

def _recurrence(q, k, v, S, n):
    """Token by token, float64: ``(o [H, n, d], S)``."""
    lam = np.exp(-np.asarray(la.decay_slopes(q.shape[0])))[:, None, None]
    S, outs = np.array(S, np.float64), []
    for t in range(n):
        S = lam * S + np.einsum("hi,hj->hij", k[:, t], v[:, t])
        outs.append(np.einsum("hi,hij->hj", q[:, t], S))
    return np.stack(outs, 1), S


@pytest.mark.parametrize("impl", ["reference", "kernel"])
@pytest.mark.parametrize("chunk", [16, 24, 40])
def test_lightning_chunks_then_decode_are_the_recurrence(impl, chunk):
    """A prompt of 70 tokens in chunks that do (none here) and do not divide
    it, then 3 decode steps, on batch row 1 of a state that held another
    sequence's: outputs and final state are the recurrence's; the other rows
    are untouched."""
    H, d, n, n_dec = 8, 128 if impl == "kernel" else 16, 70, 3
    q, k, v = (np.asarray(jax.random.normal(kk, (H, n + n_dec, d)))
               for kk in jax.random.split(jax.random.PRNGKey(0), 3))
    q = q * d ** -0.5
    dirty = jax.random.normal(jax.random.PRNGKey(5), (3, H, d, d))
    state, outs = dirty, []
    for start in range(0, n, chunk):
        live = min(chunk, n - start)
        pad = lambda x: jnp.asarray(np.pad(
            x[:, start:start + live], ((0, 0), (0, chunk - live), (0, 0)),
            constant_values=7.0))        # rows past the prompt hold anything
        o, state = la.lightning_prefill(pad(q), pad(k), pad(v), state, 1,
                                        start, live, impl=impl,
                                        interpret=True)
        outs.append(np.asarray(o)[:, :live])
    act = jnp.asarray([False, True, False])
    for t in range(n, n + n_dec):
        row = lambda x: jnp.zeros((3, H, d)).at[1].set(x[:, t])
        o, state = la.lightning_decode(row(q), row(k), row(v), state, act,
                                       impl=impl, interpret=True)
        outs.append(np.asarray(o)[1][:, None])
    want_o, want_S = _recurrence(q, k, v, np.zeros((H, d, d)), n + n_dec)
    np.testing.assert_allclose(np.concatenate(outs, 1), want_o, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state[1]), want_S, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(state[0]), np.asarray(dirty[0]))
    np.testing.assert_array_equal(np.asarray(state[2]), np.asarray(dirty[2]))


@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_state_after_chunks_is_state_after_the_tokens_one_at_a_time(impl):
    H, d, n = 8, 128 if impl == "kernel" else 16, 48
    q, k, v = (jax.random.normal(kk, (H, n, d))
               for kk in jax.random.split(jax.random.PRNGKey(1), 3))
    zero = jnp.zeros((1, H, d, d))
    chunked = zero
    for start in range(0, n, 16):
        sl = slice(start, start + 16)
        _, chunked = la.lightning_prefill(q[:, sl], k[:, sl], v[:, sl],
                                          chunked, 0, start, 16, impl=impl,
                                          interpret=True)
    stepped = zero
    for t in range(n):
        _, stepped = la.lightning_decode(
            q[None, :, t], k[None, :, t], v[None, :, t], stepped,
            jnp.asarray([True]), impl=impl, interpret=True)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(stepped),
                               atol=2e-5)


# -- the selection ----------------------------------------------------------------

def _keys_and_queries(n=80, kv=2, g=2, d=16, seed=3):
    kq, kk = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kq, (n, kv, g, d)),
            jax.random.normal(kk, (n, kv, d)))


def test_forced_blocks_are_always_chosen_and_a_group_shares_one_choice():
    q, k = _keys_and_queries()
    n = q.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    ck = sa.compress(k, SPEC)                       # every key, from full k
    idx, att = sa.select(q, ck, pos, n // 8, SPEC, 0.25)
    att = np.asarray(att)
    assert att.shape == (n, 2, n // 8)              # one choice a K|V head
    for t in range(n):
        live = t // 8 + 1
        if t + 1 <= SPEC.dense_len:                 # dense: every live block
            assert att[t, :, :live].all() and not att[t, :, live:].any()
            continue
        assert (att[t].sum(-1) == SPEC.topk).all()
        assert att[t, :, 0].all()                   # init block
        first_w = (t - SPEC.window_size + 1) // 8   # the window's blocks
        assert att[t, :, first_w:live].all() and not att[t, :, live:].any()
        np.testing.assert_array_equal(
            np.sort(np.asarray(idx[t]), -1),
            np.stack([np.flatnonzero(a) for a in att[t]]))
    # the two K|V heads do choose differently somewhere
    assert (att[:, 0] != att[:, 1]).any()


def test_choice_from_the_compressed_key_cache_is_the_choice_from_full_keys(
        model):
    """Chunked prefill and decode keep the compressed keys current page by
    page; the blocks they choose are those the reference chooses from the
    whole key matrix (float32 weights: no near-tie flips)."""
    cfg, params = model
    eng = _engine(cfg, params, chunk=16)
    (p,) = _prompts([61])
    n_new = 6
    sid = eng.add_request(p, n_new)
    eng.run()
    seq = np.asarray(eng.seqs[sid].tokens)
    _, own, _ = ref.forward(params, seq, _sizes(cfg), cfg.mixer_types,
                            rows=np.asarray([0]), selection=True)
    aux = eng.aux_log[sid]
    nb = own.shape[-1]
    for start in range(0, len(p), 16):
        live = min(16, len(p) - start)
        mine = aux["chunk", start]["attended"][:live]   # [t, layers, kv, nb]
        np.testing.assert_array_equal(
            mine.transpose(1, 0, 2, 3)[..., :nb],
            own[:, start:start + live, :, :mine.shape[-1]])
    for j in range(1, n_new):
        t = len(p) + j - 1
        mine = aux[len(p) + j]["attended"]
        np.testing.assert_array_equal(mine[..., :nb],
                                      own[:, t, :, :mine.shape[-1]])
    assert (own[:, SPEC.dense_len:len(seq) - 1].sum(-1) == SPEC.topk).all()


# -- the engine against the full forward ------------------------------------------

@pytest.mark.parametrize("chunk", [None, 16, 24])
def test_prefill_in_chunks_then_decode_is_the_full_forward(model, chunk):
    """Prompts under and over the tiny ``dense_len``, chunks that do and do
    not divide them, three sequences side by side: logits of prefill + 4
    decode steps against the reference's one forward, float32."""
    cfg, params = model
    eng = _engine(cfg, params, chunk=chunk)
    prompts = _prompts([61, 23, 70])
    n_new = 5
    sids = [eng.add_request(p, n_new) for p in prompts]
    eng.run()
    for sid, p in zip(sids, prompts):
        rows = np.asarray([len(p) + j - 1 for j in range(n_new)])
        want = ref.forward(params, np.asarray(eng.seqs[sid].tokens),
                           _sizes(cfg), cfg.mixer_types, rows=rows)
        assert _rel(_logits(eng, sid, len(p), n_new), np.asarray(want)) < 2e-5


def test_bf16_kernels_interpreted_agree_with_the_reference():
    """The Pallas paths (lightning chunk and decode kernels, the sparse
    decode walk over chosen pages, the masked flash product), interpreted,
    at a head size they take, bf16 weights: within bf16's error of the
    float32 reference attending the blocks the program chose."""
    cfg = _cfg(d=128, dtype=jnp.bfloat16)
    params = ms.init_params(cfg, jax.random.PRNGKey(0))
    eng = _engine(cfg, params, chunk=16, **KERNELS)
    (p,) = _prompts([61])
    n_new = 4
    sid = eng.add_request(p, n_new)
    eng.run()
    from benchmarks.families.minicpm_sala import _attended
    seq = np.asarray(eng.seqs[sid].tokens)
    mine = _attended(eng.aux_log[sid], len(p), n_new, len(seq),
                     -(-len(seq) // 8), 16)
    rows = np.asarray([len(p) + j - 1 for j in range(n_new)])
    want = ref.forward(params, seq, _sizes(cfg), cfg.mixer_types, rows=rows,
                       forced=mine)
    assert _rel(_logits(eng, sid, len(p), n_new), np.asarray(want)) < 0.02


def test_a_row_another_sequence_left_starts_from_a_zeroed_state(model):
    """One batch row: the second sequence takes the row (and the pages) the
    first left, and reads what it would in an engine nobody used."""
    cfg, params = model
    first, second = _prompts([45, 52])
    used = _engine(cfg, params, chunk=16, max_batch=1)
    used.add_request(first, 3)
    sid = used.add_request(second, 3)
    used.run()
    assert float(jnp.abs(used.state.states[0]).max()) > 0
    fresh = _engine(cfg, params, chunk=16, max_batch=1)
    sid0 = fresh.add_request(second, 3)
    fresh.run()
    np.testing.assert_array_equal(_logits(used, sid, len(second), 3),
                                  _logits(fresh, sid0, len(second), 3))


@pytest.mark.parametrize("chunk", [None, 16])
def test_a_preempted_sequence_resumes_to_the_same_logits(model, chunk):
    """Its pages AND its state go; prefill (in chunks) and the replay of its
    generated tokens rebuild both, bit for bit."""
    cfg, params = model
    prompts = _prompts([50, 41])
    plain = _engine(cfg, params, chunk=chunk)
    sids = [plain.add_request(p, 8) for p in prompts]
    plain.run()
    eng = _engine(cfg, params, chunk=chunk)
    for p in prompts:
        eng.add_request(p, 8)
    while eng.seqs[sids[1]].num_generated < 4:
        eng.step()
    eng.preempt(sids[1])
    assert eng.seqs[sids[1]].state == WAITING
    eng.run()
    assert eng.seqs[sids[1]].n_preemptions == 1
    for sid, p in zip(sids, prompts):
        assert eng.seqs[sid].tokens == plain.seqs[sid].tokens
        np.testing.assert_array_equal(_logits(eng, sid, len(p), 8),
                                      _logits(plain, sid, len(p), 8))


def test_refusals_at_construction(model):
    cfg, params = model
    for kw, err in ((dict(fp8_kv=True), NotImplementedError),
                    (dict(fp8_weights=True), NotImplementedError),
                    (dict(spec_k=2), NotImplementedError),
                    (dict(chunk=12), ValueError)):       # not whole blocks
        with pytest.raises(err):
            _engine(cfg, params, **kw)
    with pytest.raises(ValueError, match="block_size"):
        serve.ServeEngine(MiniCPMSalaServed(cfg), params, num_pages=40,
                          max_seq_len=96, max_prompt_len=64, page_size=16)


def test_cache_config_counts_pages_compressed_keys_and_state(model):
    cfg, params = model
    eng = _engine(cfg, params, chunk=16)
    c = eng.ccfg
    assert (c.num_layers, c.state_leaves, c.state_rows, c.state_shape,
            c.keys_per_page) == (2, 2, 3, (8, 16, 16), 4)
    assert [s.shape for s in eng.state.states] == [(3, 8, 16, 16)] * 2
    assert [k.shape for k in eng.state.ckeys] == [(40 * 4, 2 * 16)] * 2
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(eng.state))
    assert c.pool_bytes() == held
    assert c.state_bytes() == 2 * 3 * 8 * 16 * 16 * 4


# -- an engine that was not asked to chunk -----------------------------------------

@pytest.mark.parametrize("chunk", [None, 16, 64])
def test_an_engine_whose_prompts_fit_a_chunk_dispatches_what_it_did(chunk):
    """GPT, ``max_prompt_len`` 16: without ``prefill_chunk``, or with one no
    smaller than the prompt, the engine holds the two programs it always
    held, called with the arguments it always passed."""
    from apex_tpu.models import GPT, GPTConfig
    cfg = GPTConfig(vocab_size=64, max_seq_len=32, hidden_size=32,
                    num_layers=1, num_heads=2, dtype=jnp.float32)
    params = GPT(cfg).init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    eng = serve.ServeEngine(cfg, params, num_pages=9, max_seq_len=32,
                            max_prompt_len=16, page_size=8, max_batch=2,
                            prefill_chunk=chunk)
    assert eng.prefill_chunk is None and eng.sched.prefill_chunk is None
    calls = []
    inner = eng._prefill
    eng._prefill = lambda *a: (calls.append(a), inner(*a))[1]
    eng.add_request(list(range(1, 12)), 3)
    eng.add_request(list(range(1, 6)), 3)
    eng.run()
    assert len(calls) == 2                  # one prefill a prompt
    for a in calls:
        assert [np.shape(x) for x in a[2:]] == [(4,), (), (16,), (2,), ()]
    assert eng._draft_decode is None
    assert eng.state.states == () and eng.state.ckeys == ()


def test_models_that_cannot_chunk_refuse_it():
    from apex_tpu.models import GPT, GPTConfig
    cfg = GPTConfig(vocab_size=64, max_seq_len=32, hidden_size=32,
                    num_layers=1, num_heads=2, dtype=jnp.float32)
    params = GPT(cfg).init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]
    with pytest.raises(NotImplementedError, match="whole prompt"):
        serve.ServeEngine(cfg, params, num_pages=9, max_seq_len=32,
                          max_prompt_len=16, page_size=8, prefill_chunk=8)


# -- the scheduler: a chunk a round -------------------------------------------------

def _sched(chunk):
    return Scheduler(num_pages=30, page_size=8, max_batch=3,
                     prefill_chunk=chunk)


def _dispatch(plan, chunk):
    """What the engine does with a plan: advance ``num_cached``."""
    for s in plan.prefill:
        s.num_cached = min(len(s.prompt), s.num_cached + (chunk or 10 ** 9))


def test_scheduler_hands_out_one_chunk_a_round():
    sch = _sched(16)
    a = Sequence(0, list(range(1, 41)), 4)          # 40 tokens: 3 chunks
    b = Sequence(1, list(range(1, 11)), 4)          # 10 tokens: 1 chunk
    sch.add(a)
    sch.add(b)
    seen = []
    for _ in range(5):
        plan = sch.schedule()
        seen.append([s.seq_id for s in plan.prefill])
        assert len(plan.prefill) <= 1
        if seen[-1] == [0] and len(seen) < 3:
            # admitted with its first chunk: pages for the whole prompt,
            # still at the head of the queue
            assert a.state == PREFILLING and sch.waiting[0] is a
            assert len(a.pages) == 6 and a not in sch.running
        _dispatch(plan, 16)
    assert seen == [[0], [0], [0], [1], []]
    assert a.state == b.state == RUNNING and not sch.waiting
    assert sch.running == [a, b]


def test_scheduler_without_a_chunk_admits_everything_that_fits():
    sch = _sched(None)
    seqs = [Sequence(i, list(range(1, 41)), 4) for i in range(3)]
    for s in seqs:
        sch.add(s)
    plan = sch.schedule()
    assert plan.prefill == seqs and sch.running == seqs and not sch.waiting


def test_a_sequence_between_two_chunks_is_the_first_victim():
    """The pool runs dry while the latest arrival is half prefilled: it
    gives its pages back and starts again from its first chunk."""
    sch = Scheduler(num_pages=9, page_size=8, max_batch=3, prefill_chunk=16)
    a = Sequence(0, list(range(1, 16)), 30)         # 15 tokens, 2 pages
    b = Sequence(1, list(range(1, 41)), 4)          # 40 tokens, 6 pages
    sch.add(a)
    _dispatch(sch.schedule(), 16)
    sch.add(b)
    _dispatch(sch.schedule(), 16)
    assert b.state == PREFILLING and sch.allocator.free_pages == 0
    a.tokens += [5, 6]                  # a's next write needs a third page
    plan = sch.schedule()
    assert plan.preempted == [b] and b.state == WAITING
    assert b.num_cached == 0 and b.pages == [] and len(a.pages) == 3
    assert plan.decode == [a] and sch.waiting == [b]
