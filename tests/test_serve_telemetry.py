"""Request-level serve telemetry: span traces, SLO histograms, serve
health events, MFU/goodput.

The acceptance contracts of the observability PR:

- every request gets a span trace (queue-wait -> prefill -> decode),
  aggregated into the report's ``serve`` block with span-derived
  TTFT/queue-wait and streaming token-latency percentiles;
- preempt -> re-admit trace continuity — the telemetry twin of the
  bit-exact replay test: ONE request span across the preemption, a
  ``serve/preempt`` annotation, a resumed prefill + replay span, and
  the same final tokens as the uninterrupted run;
- purity: decode/prefill jaxprs are BYTE-identical with spans attached
  vs detached (host-clock-only, zero jax in the hot path), and
  detached runs record nothing;
- the Watchdog fires ``kv_pool_exhaustion`` + ``eviction_storm`` on a
  forced-tiny-pool engine and the events render under ``## health``;
- MFU table lookups + the goodput gauge.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu import monitor, serve
from apex_tpu.models.gpt import GPT, GPTConfig
from apex_tpu.monitor import attribution
from apex_tpu.monitor import profile as profile_mod
from apex_tpu.transformer import parallel_state as ps

CFG = GPTConfig(vocab_size=64, max_seq_len=128, hidden_size=32,
                num_layers=2, num_heads=2, dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    ps.destroy_model_parallel()
    return GPT(CFG).init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture(autouse=True, scope="module")
def _abandoned_requests_leave_no_span_open():
    """Tests here step an engine a few rounds and drop it with requests
    unfinished: their ``serve/request`` spans would stay open into the
    next file of the same worker (``test_spans.py`` counts them)."""
    yield
    from apex_tpu.monitor import spans
    with spans._lock:
        spans._open.clear()


PROMPTS = [[5, 9, 17, 3, 40, 22, 8], [11, 2, 33, 60, 7, 7, 1]]
N_NEW = 8


def _engine(params, **kw):
    kw.setdefault("num_pages", 32)
    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 8)
    return serve.ServeEngine(CFG, params, max_seq_len=64,
                             max_prompt_len=16, **kw)


def _run_monitored(params, *, preempt_at=None, **kw):
    rec = monitor.Recorder(traced_hooks=False, name="serve_tel")
    eng = _engine(params, **kw)
    with monitor.attached(rec):
        ids = [eng.add_request(p, N_NEW) for p in PROMPTS]
        steps = 0
        while eng.sched.has_work:
            eng.step()
            steps += 1
            if preempt_at and steps == preempt_at and any(
                    s.seq_id == ids[0] for s in eng.sched.running):
                eng.preempt(ids[0])
            assert steps < 500
        eng._record_run_summary(0.0, 0)   # goodput uses run(); noop ok
    out = {sid: s.tokens[len(s.prompt):] for sid, s in eng.seqs.items()}
    return rec, eng, ids, out


# ---------------------------------------------------------------------------
# request traces
# ---------------------------------------------------------------------------

def test_request_span_trace_end_to_end(params):
    rec, eng, ids, out = _run_monitored(params)
    agg = rec.aggregate()
    sv = agg["serve"]
    rows = {r["seq_id"]: r for r in sv["requests"]}
    assert set(rows) == set(ids)
    for sid in ids:
        r = rows[sid]
        assert r["new_tokens"] == N_NEW
        assert r["prompt_tokens"] == len(PROMPTS[sid])
        assert r["ttft_ms"] > 0
        assert r["queue_wait_ms"] >= 0
        assert r["e2e_ms"] >= r["ttft_ms"]
        assert r["preemptions"] == 0
    # streaming SLO histograms: one token-latency sample per generated
    # token that came from a BATCHED decode step (prefill's first token
    # is TTFT, not steady-state token latency)
    slo = sv["slo"]
    assert slo["token_latency_ms"]["count"] > 0
    assert slo["ttft_ms"]["count"] == len(ids)
    assert slo["queue_wait_ms"]["count"] == len(ids)
    assert slo["token_latency_ms"]["p50"] <= slo["token_latency_ms"]["p99"]
    # counters + gauges
    c = sv["counters"]
    assert c["serve/tokens_generated"] == sum(len(v) for v in out.values())
    assert c["serve/requests_finished"] == len(ids)
    assert sv["pool"]["pages_total"] == 31
    assert sv["pool"]["pages_in_use"] == 0       # drained
    assert "queue_depth" in sv
    # per-step records carried the serve gauges (the Watchdog's input)
    assert rec.steps(), "engine rounds did not open step records"
    assert "serve/pages_free" in rec.steps()[-1]["gauges"]
    # CLI render includes the serve section + request table
    rendered = monitor.render_report(rec.records()
                                     + rec._histogram_events())
    assert "## serve (request-level telemetry)" in rendered
    assert "| request |" in rendered


def test_preempt_readmit_trace_continuity(params):
    """The bit-exact replay test's telemetry twin: the trace must show
    ONE request span spanning the preemption, the preempt transition,
    a resumed prefill and a replay span — and the tokens must equal
    the uninterrupted run's."""
    _, _, _, out_plain = _run_monitored(params)
    rec, eng, ids, out = _run_monitored(params, preempt_at=3)
    assert out == out_plain                       # bit-exact replay
    evs = rec.records()
    req_starts = [e for e in evs if e["kind"] == "span_start"
                  and e["name"] == "serve/request"]
    req_ends = [e for e in evs if e["kind"] == "span_end"
                and e["name"] == "serve/request"]
    assert len(req_starts) == len(req_ends) == len(ids)
    rows = {r["seq_id"]: r for r in rec.aggregate()["serve"]["requests"]}
    assert rows[ids[0]]["preemptions"] == 1
    # the preempt transition annotates the SAME root span
    root = next(e["value"] for e in req_starts
                if e["seq_id"] == ids[0])
    (pre,) = [e for e in evs if e["kind"] == "span_event"
              and e["name"] == "serve/preempt"]
    assert pre["seq_id"] == ids[0] and pre["value"] == root
    assert pre["tokens_kept"] > len(PROMPTS[0])   # kept its generation
    # two queue-wait spans for the preempted request (initial + requeue,
    # the second marked resumed), one for the other
    qw = [e for e in evs if e["kind"] == "span_start"
          and e["name"] == "serve/queue_wait"]
    per_seq = {}
    for e in qw:
        per_seq.setdefault(e["seq_id"], []).append(e)
    assert len(per_seq[ids[0]]) == 2
    assert per_seq[ids[0]][1].get("resumed") is True
    assert len(per_seq[ids[1]]) == 1
    assert all(e["parent"] == root for e in per_seq[ids[0]])
    # resumed prefill + decode-replay, parented under the same root
    prefills = [e for e in evs if e["kind"] == "span_start"
                and e["name"] == "serve/prefill"
                and e["seq_id"] == ids[0]]
    assert [e.get("resumed") for e in prefills] == [False, True]
    (replay,) = [e for e in evs if e["kind"] == "span_start"
                 and e["name"] == "serve/replay"]
    assert replay["parent"] == root
    # TTFT measured ONCE (before the preemption), never re-measured
    assert rows[ids[0]]["ttft_ms"] > 0
    from apex_tpu.monitor import spans
    assert spans.open_spans() == 0


# ---------------------------------------------------------------------------
# the round, by phase
# ---------------------------------------------------------------------------

def _closed(rec):
    """``{span id: (name, parent, t0, t1, start event)}`` of the closed
    spans, on the recorder's clock."""
    starts = {e["value"]: e for e in rec.records("span_start")}
    return {e["span"]: (e["name"], e["parent"], e["t"] - e["value"],
                        e["t"], starts[e["span"]])
            for e in rec.records("span_end")}


def _check_dispatch_and_wait(spans_, first, second, steady):
    """``serve/decode_step`` = ``serve/decode_dispatch`` then
    ``serve/token_wait``, and the wait's other two places: under
    ``serve/sample`` in a round that sent out no decode, under nothing
    in a forced ``preempt``."""
    eps = 1e-4
    kids = {}
    for s in spans_.values():
        kids.setdefault(s[1], []).append(s)
    steps = {i: s for i, s in spans_.items() if s[0] == "serve/decode_step"}
    assert [s[1] for s in steps.values()] == [second] + steady
    for i, (_, rnd, t0, t1, _) in steps.items():
        mine = sorted(kids[i], key=lambda k: k[2])
        # the first decode round of the run finds nothing to read: the
        # prefill's token went out in this very step
        want = ["serve/decode_dispatch"] + (
            ["serve/token_wait"] if rnd != second else [])
        assert [k[0] for k in mine] == want
        assert t0 - eps <= mine[0][2] and mine[-1][3] <= t1 + eps
        for a, b in zip(mine, mine[1:]):
            assert a[3] <= b[2] + eps                 # not overlapping
    waits = [s for s in spans_.values() if s[0] == "serve/token_wait"]
    # dispatches read: the second round's prefill and decode, then a
    # round's decode a step
    assert [w[4]["n_read"] for w in waits if w[1] in steps] == \
        [2] + [1] * (len(steady) - 1)
    sample = next(i for i, s in spans_.items()
                  if s[0] == "serve/sample" and s[1] == first)
    assert [k[0] for k in kids[sample]] == ["serve/token_wait"]
    assert kids[sample][0][4]["n_read"] == 1          # the prefill's token
    # the preempt's read is under no span: it ran between two steps
    assert [w[4]["n_read"] for w in waits if w[1] is None] == [1]
    assert len(waits) == len(steady) + 2


def test_round_span_tree_every_child_inside_its_parent(params):
    """One engine round that admits a prompt and decodes the sequence
    already running: the phase spans of docs/observability.md, each a
    child of the round by nesting and inside it in time, in the order the
    round runs them."""
    rec = monitor.Recorder(traced_hooks=False)
    eng = _engine(params)
    with monitor.attached(rec):
        eng.add_request(PROMPTS[0], N_NEW)
        eng.step()                       # a round that only prefills
        sid = eng.add_request(PROMPTS[1], N_NEW)
        eng.step()
        eng.step()                       # steady state: reads the last round
        eng.step()
        eng.preempt(sid)                 # reads what is in flight, early
    spans_ = _closed(rec)
    first, rid, *steady = [i for i, s in spans_.items()
                           if s[0] == "serve/round"]
    # no decode went out: the prefill's token is read in the round itself
    assert [s[0] for s in spans_.values() if s[1] == first] == [
        "serve/schedule", "serve/prefill", "serve/sample", "serve/gauges"]
    _check_dispatch_and_wait(spans_, first, rid, steady)
    _, parent, r0, r1, _ = spans_[rid]
    assert parent is None
    kids = sorted((s for s in spans_.values() if s[1] == rid),
                  key=lambda s: s[2])
    assert [k[0] for k in kids] == [
        "serve/schedule", "serve/prefill", "serve/decode_inputs",
        "serve/decode_step", "serve/sample", "serve/gauges"]
    eps = 1e-4        # the recorder rounds t and durations to the us
    for a, b in zip(kids, kids[1:]):
        assert a[3] <= b[2] + eps               # one after the other
    assert r0 - eps <= kids[0][2] and kids[-1][3] <= r1 + eps
    step = next(k for k in kids if k[0] == "serve/decode_step")
    assert step[4]["n_active"] == 1
    # what the round admitted and decoded is read off the tree itself
    assert [k[4]["seq_id"] for k in kids if k[0] == "serve/prefill"] == [sid]
    # the host's time outside the dispatching spans: what the round does
    # not spend inside a prefill or the decode step
    covered = sum(k[3] - k[2] for k in kids
                  if k[0] in ("serve/prefill", "serve/decode_step"))
    assert 0 < (r1 - r0) - covered < r1 - r0


def test_prefill_span_closes_at_dispatch_and_its_token_counts_when_read(
        params):
    """``serve/prefill`` closes at the dispatch: the prompt's first token
    stays on the device and nothing of it is counted. With a decode round
    going out, the NEXT step reads it, inside ``serve/decode_step`` and
    after that round's own dispatch, and only ``serve/sample`` counts
    it."""
    from apex_tpu.monitor import spans
    rec = monitor.Recorder(traced_hooks=False)
    eng = _engine(params)
    fetch, decode = eng._fetch, eng._decode

    def marked_fetch(e):
        spans.annotate("test/fetch", decode=e.decode)
        return fetch(e)

    def marked_decode(*args):
        spans.annotate("test/dispatch")
        return decode(*args)

    def names():
        return [(e["kind"], e["name"]) for e in rec.records()]

    eng.add_request(PROMPTS[0], N_NEW)
    eng.step()
    eng.step()                            # the first request is decoding
    eng._fetch, eng._decode = marked_fetch, marked_decode
    with monitor.attached(rec):
        sid = eng.add_request(PROMPTS[1], 3)
        seq = eng.seqs[sid]
        generated = eng.tokens_generated
        eng.step()        # prefills it, dispatches a decode, reads the last
        assert ("span_end", "serve/prefill") in names()
        assert [e["decode"] for e in rec.records("span_event")
                if e["name"] == "test/fetch"] == [True]
        assert (seq.num_generated, seq.in_flight, seq.ttft_ms) == (0, 1, None)
        assert eng.tokens_generated == generated + 1     # the other's token
        n = len(rec.records())
        eng.step()
    assert (seq.num_generated, seq.in_flight) == (1, 1)
    assert seq.ttft_ms > 0
    later = [(e["kind"], e["name"]) for e in rec.records()[n:]]
    order = [later.index(k) for k in (
        ("span_start", "serve/decode_step"),
        ("span_start", "serve/decode_dispatch"),
        ("span_event", "test/dispatch"),
        ("span_end", "serve/decode_dispatch"),
        ("span_start", "serve/token_wait"), ("span_event", "test/fetch"),
        ("span_end", "serve/token_wait"), ("span_end", "serve/decode_step"),
        ("span_start", "serve/sample"), ("counter", "serve/tokens_generated"),
        ("span_end", "serve/sample"))]
    assert order == sorted(order), order


def test_one_counter_event_and_one_histogram_record_a_dispatch(
        params, monkeypatch):
    """Four rows a round: ``serve/tokens_generated`` goes out once a
    DISPATCH with ``inc`` = its tokens, ``serve/token_latency_ms`` is
    recorded once a decode round with its rows as the weight. Totals
    and counts are what one event and one record a token gave."""
    from apex_tpu.monitor import spans
    rec = monitor.Recorder(traced_hooks=False)
    eng = _engine(params, max_batch=4)
    records = []
    record = spans.LogHistogram.record

    def counted(self, value, n=1):
        records.append(n)
        return record(self, value, n)

    monkeypatch.setattr(spans.LogHistogram, "record", counted)
    with monitor.attached(rec):
        for i in range(4):
            eng.add_request(PROMPTS[i % 2][i:], N_NEW)
        eng.run()
    total = 4 * N_NEW
    assert eng.tokens_generated == total
    events = [e for e in rec.records("counter")
              if e["name"] == "serve/tokens_generated"]
    # four prefills of one token, then decode rounds of four rows
    assert [e["value"] for e in events] == [1] * 4 + [4] * (N_NEW - 1)
    assert events[-1]["total"] == total == \
        rec.counters()["serve/tokens_generated"]
    from_decode = total - 4
    h = rec.histograms()["serve/token_latency_ms"]
    assert h.count == from_decode
    assert h.snapshot()["count"] == sum(h.snapshot()["counts"].values())
    # one record a decode round (the other records are TTFT, queue wait)
    assert sorted(records)[-(N_NEW - 1):] == [4] * (N_NEW - 1)
    assert sum(n for n in records if n > 1) == from_decode
    assert h.mean == pytest.approx(h.sum / from_decode)


# ---------------------------------------------------------------------------
# purity + detached mode
# ---------------------------------------------------------------------------

def test_decode_prefill_jaxprs_byte_identical_spans_on_vs_off(params,
                                                              monkeypatch):
    """The PR 2/10 purity contract, serve edition: tracing the
    engine's compiled decode/prefill steps with a (traced-hooks)
    recorder attached — spans live, histograms observing — yields
    byte-identical jaxprs to detached tracing. Spans are host-only by
    construction; this pins it."""
    eng = _engine(params)
    bts = jnp.zeros((eng.max_batch, eng.pages_per_seq), jnp.int32)
    pos = jnp.zeros((eng.max_batch,), jnp.int32)
    tok = jnp.zeros((eng.max_batch,), jnp.int32)
    act = jnp.zeros((eng.max_batch,), bool)
    ids = jnp.zeros((eng.max_prompt_len,), jnp.int32)
    bt1 = jnp.zeros((eng.pages_per_seq,), jnp.int32)

    def trace_both():
        d = jax.make_jaxpr(eng._decode)(
            params, eng.state, bts, pos, tok, act)
        p = jax.make_jaxpr(eng._prefill)(
            params, eng.state, bt1, jnp.int32(4), ids, tok, jnp.int32(0))
        return str(d), str(p)

    detached = trace_both()
    rec = monitor.Recorder(traced_hooks=True)
    with monitor.attached(rec):
        from apex_tpu.monitor import spans
        with spans.span("serve/decode_step", n_active=1):
            attached = trace_both()
        rec.observe("serve/token_latency_ms", 1.0)
    assert attached[0] == detached[0], "decode jaxpr drifted with spans"
    assert attached[1] == detached[1], "prefill jaxpr drifted with spans"
    assert "callback" not in detached[0] and "callback" not in detached[1]
    # and the profile scopes (``kv_write``, ``paged_attn``, the kernels'
    # per-direction ones) are metadata: the programs without any of them
    # are the same programs
    import contextlib
    monkeypatch.setattr(profile_mod, "scope",
                        lambda name: contextlib.nullcontext())
    assert trace_both() == detached


def test_detached_engine_records_nothing(params):
    """Detached overhead is the no-op path: a full engine run with no
    recorder attached allocates no span ids and leaves no open state —
    and a recorder attached AFTERWARDS starts empty."""
    from apex_tpu.monitor import spans
    assert monitor.get_recorder() is None
    before = spans.open_spans()
    eng = _engine(params)
    for p in PROMPTS:
        eng.add_request(p, N_NEW)
    eng.run()
    assert spans.open_spans() == before
    rec = monitor.Recorder()
    with monitor.attached(rec):
        pass
    assert rec.records() == []


# ---------------------------------------------------------------------------
# serve health events (forced-tiny-pool)
# ---------------------------------------------------------------------------

def test_watchdog_fires_kv_pool_exhaustion_and_eviction_storm(params):
    """A pool sized below the working set: growth must evict
    repeatedly (storm) and the free list must cross the exhaustion
    threshold; both events render under ``## health``."""
    rec = monitor.Recorder(traced_hooks=False)
    dog = monitor.Watchdog(rec, eviction_window=20, eviction_trips=3,
                           kv_pool_min_free_fraction=0.2)
    eng = serve.ServeEngine(CFG, params, num_pages=8, max_seq_len=32,
                            max_prompt_len=8, page_size=4, max_batch=3)
    with monitor.attached(rec):
        for p in ([5, 9, 17, 3, 40, 22], [11, 2, 33, 60, 7, 7],
                  [1, 2, 3, 4, 5, 6]):
            eng.add_request(p, 16)
        out = eng.run(max_steps=4000)
    assert all(len(v) == 16 for v in out.values())   # still correct
    names = [e["name"] for e in dog.events]
    assert "kv_pool_exhaustion" in names, names
    assert "eviction_storm" in names, names
    by_name = {e["name"]: e for e in dog.events}
    assert by_name["kv_pool_exhaustion"]["severity"] == "warn"
    assert by_name["kv_pool_exhaustion"]["pages_total"] == 7
    assert by_name["eviction_storm"]["severity"] == "error"
    rendered = monitor.render_report(rec.records())
    assert "## health" in rendered
    assert "kv_pool_exhaustion" in rendered
    assert "eviction_storm" in rendered
    # the events also ride the report aggregate (typed health_event)
    agg = rec.aggregate()
    assert {h["name"] for h in agg["health"]} >= {"kv_pool_exhaustion",
                                                  "eviction_storm"}


def test_watchdog_admission_starvation_ema():
    """Waiting-queue age EMA over the bar fires once (with
    hysteresis); below half the bar it re-arms."""
    rec = monitor.Recorder()
    dog = monitor.Watchdog(rec, admission_age_s=0.1,
                           admission_smoothing=1.0)
    for age in (0.25, 0.3):
        with rec.step():
            rec.gauge("serve/queue_wait_oldest_s", age)
    assert [e["name"] for e in dog.events] == ["admission_starvation"]
    with rec.step():
        rec.gauge("serve/queue_wait_oldest_s", 0.01)   # re-arm
    with rec.step():
        rec.gauge("serve/queue_wait_oldest_s", 0.5)
    assert [e["name"] for e in dog.events] == \
        ["admission_starvation", "admission_starvation"]


def test_watchdog_healthy_serve_run_quiet_and_goodput_recorded(params):
    """An adequately-pooled watched run fires NO serve health events;
    drain records the tokens/s/chip goodput gauge and flushes the SLO
    histogram snapshots into the ring (crash resilience)."""
    rec = monitor.Recorder(traced_hooks=False)
    dog = monitor.Watchdog(rec)
    eng = _engine(params)
    with monitor.attached(rec):
        for p in PROMPTS:
            eng.add_request(p, N_NEW)
        eng.run()
    assert dog.events == [], dog.events
    g = rec.gauges()
    assert g["serve/goodput_tokens_per_sec_chip"] > 0
    assert rec.records("histogram"), "emit_histograms not called at drain"


# ---------------------------------------------------------------------------
# MFU / goodput
# ---------------------------------------------------------------------------

def test_peak_flops_table_lookup():
    assert attribution.peak_flops_for("TPU v5e") == 197e12
    assert attribution.peak_flops_for("TPU v5 lite") == 197e12
    assert attribution.peak_flops_for("TPU v4") == 275e12
    assert attribution.peak_flops_for("some-future-asic") is None
    # the cpu row exists (nominal; platform-bound units gate its use)
    assert attribution.peak_flops_for("cpu") == 5e10


def test_mfu_arithmetic_and_guards():
    row = attribution.mfu(1e9, 1e-3, peak=1e12)
    assert row["mfu_pct"] == 100.0
    assert row["achieved_flops_per_sec"] == 1e12
    assert attribution.mfu(1e9, 0.0, peak=1e12) is None
    assert attribution.mfu(0, 1.0, peak=1e12) is None
    assert attribution.mfu(1e9, 1e-3, device_kind="unknown-chip") is None
    half = attribution.mfu(1e9, 1e-3, peak=1e12, n_devices=2)
    assert half["mfu_pct"] == 50.0


def test_measured_mfu_records_gauges():
    def step(x):
        return x @ x

    x = jnp.ones((64, 64), jnp.float32)
    rec = monitor.Recorder(traced_hooks=False)
    with monitor.attached(rec):
        row = attribution.measured_mfu(jax.jit(step), (x,), repeats=2,
                                       record=True)
    assert row["flops"] == 2 * 64 * 64 * 64
    assert row["step_time_s"] > 0
    g = rec.gauges()
    assert g["profile/step_time_ms"] > 0
    # on this host the nominal cpu table row resolves, so MFU lands too
    if row.get("mfu_pct") is not None:
        assert g["profile/mfu_pct"] == row["mfu_pct"]


def test_serve_engine_exports_during_drain(params):
    """export.serve_engine(eng, export_port=0) binds an ephemeral
    /metrics endpoint for the drain and stops it after; outputs ==
    run()."""
    from apex_tpu.monitor import export
    rec = monitor.Recorder(traced_hooks=False)
    eng = _engine(params)
    ports = []
    with monitor.attached(rec):
        for p in PROMPTS:
            eng.add_request(p, N_NEW)
        out = export.serve_engine(
            eng, export_port=0,
            on_export=lambda _eng, port: ports.append(port))
    assert ports[0] > 0
    assert all(len(v) == N_NEW for v in out.values())
    import urllib.error
    import urllib.request
    with pytest.raises(urllib.error.URLError):     # stopped after drain
        urllib.request.urlopen(
            f"http://127.0.0.1:{ports[0]}/metrics", timeout=2)
