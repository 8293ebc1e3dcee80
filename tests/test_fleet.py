"""Fleet-level telemetry: multi-replica scrape aggregation, SLO
burn-rate alerting, autoscale decisions.

The acceptance contracts of the fleet PR:

- a LIVE two-replica round trip: two ``ServeEngine``s serving on
  threads with ephemeral ``/metrics`` endpoints, scraped by a
  ``FleetPoller`` — fleet counters sum EXACTLY, the merged-histogram
  p99 lands within the documented ~12% bucket band of the pooled-exact
  percentile, and killing one replica mid-poll degrades its row to
  ``up=0`` + last-seen age without an exception;
- honest aggregation semantics: counters summed, gauges per-replica +
  min/max/sum views, ``LogHistogram.merge`` so fleet percentiles come
  from one merged histogram — never an average of percentiles;
- alert correctness both ways: a starved fixture fires the fast-burn
  ``slo_alert`` AND a ``scale_out`` decision with quoted rationale;
  its healthy twin stays silent — and the events render under
  ``## fleet``/``## health`` and survive flight-dump → timeline;
- purity: serve decode/prefill jaxprs are byte-identical with a
  ``FleetPoller`` actively scraping (all host-side thread plumbing).
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu import monitor, serve
from apex_tpu.models.gpt import GPT, GPTConfig
from apex_tpu.monitor import export
from apex_tpu.monitor import fleet as fleet_mod
from apex_tpu.monitor import slo as slo_mod
from apex_tpu.monitor.recorder import Recorder
from apex_tpu.monitor.spans import LogHistogram
from apex_tpu.transformer import parallel_state as ps

CFG = GPTConfig(vocab_size=64, max_seq_len=128, hidden_size=32,
                num_layers=2, num_heads=2, dtype=jnp.float32)

# one geometric bucket is a 10^(1/bpd) span; the midpoint estimate is
# off by at most half a bucket — the documented ~12% band at bpd=10
BAND = 10.0 ** (1.0 / (2 * 10))


@pytest.fixture(scope="module")
def params():
    ps.destroy_model_parallel()
    return GPT(CFG).init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(params, **kw):
    kw.setdefault("num_pages", 32)
    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("max_prompt_len", 16)
    return serve.ServeEngine(CFG, params, **kw)


# ---------------------------------------------------------------------------
# LogHistogram.merge (the aggregation primitive)
# ---------------------------------------------------------------------------

def test_merge_percentiles_match_pooled_exact():
    """Merged-histogram percentiles vs numpy over the pooled raw
    samples: within one half-bucket (the ~12% band) — the honest-
    semantics contract (average-of-percentiles would not be)."""
    rng = np.random.default_rng(7)
    pools = [rng.lognormal(mean=m, sigma=0.8, size=400)
             for m in (1.0, 2.0, 3.5)]
    hists = []
    for xs in pools:
        h = LogHistogram()
        for x in xs:
            h.record(float(x))
        hists.append(h)
    merged = LogHistogram.merge(*[h.snapshot() for h in hists])
    pooled = np.concatenate(pools)
    assert merged.count == len(pooled)
    assert merged.sum == pytest.approx(pooled.sum(), rel=1e-9)
    assert merged.min == pytest.approx(pooled.min())
    assert merged.max == pytest.approx(pooled.max())
    for p in (50, 90, 99):
        exact = float(np.percentile(pooled, p))
        est = merged.percentile(p)
        assert exact / BAND <= est <= exact * BAND, (p, est, exact)


def test_merge_rejects_config_mismatch_and_empty():
    a = LogHistogram()
    b = LogHistogram(buckets_per_decade=5)
    a.record(1.0)
    b.record(1.0)
    with pytest.raises(ValueError, match="config mismatch"):
        LogHistogram.merge(a.snapshot(), b.snapshot())
    with pytest.raises(ValueError):
        LogHistogram.merge()


def test_merge_carries_underflow_overflow_minmax():
    a = LogHistogram(lo=1.0, hi=100.0, buckets_per_decade=1)
    b = LogHistogram(lo=1.0, hi=100.0, buckets_per_decade=1)
    a.record(0.5)        # underflow
    a.record(5.0)
    b.record(500.0)      # overflow
    m = LogHistogram.merge(a.snapshot(), b.snapshot())
    assert m.count == 3
    assert m.underflow == 1 and m.overflow == 1
    assert m.min == 0.5 and m.max == 500.0


# ---------------------------------------------------------------------------
# file-backed round trip (labels + reconstruction)
# ---------------------------------------------------------------------------

def _file_replica(tmp_path, rid, *, counters=(), gauges=(), observes=()):
    rec = Recorder(traced_hooks=False, name=rid)
    for name, v in counters:
        rec.counter(name, v)
    for name, v in gauges:
        rec.gauge(name, v)
    for name, vals in observes:
        for v in vals:
            rec.observe(name, v)
    text = export.render_prometheus(export.snapshot(recorder=rec),
                                    replica=rid)
    p = tmp_path / f"{rid}.prom"
    p.write_text(text)
    return rec, str(p)


def test_two_replica_file_pair_roundtrip(tmp_path):
    """The labeled-exposition regression: two file-backed replicas →
    counters summed, a gauge named ``*_total`` stays a gauge (declared
    type wins over suffix), per-replica gauge views kept, and the
    merged histogram equals a direct ``LogHistogram.merge`` of the
    source snapshots."""
    rec_a, pa = _file_replica(
        tmp_path, "ra",
        counters=[("serve/tokens_generated", 120)],
        gauges=[("serve/pages_in_use", 6.0), ("serve/pages_total", 31.0)],
        observes=[("serve/token_latency_ms", [2.0, 4.0, 9.0, 30.0])])
    rec_b, pb = _file_replica(
        tmp_path, "rb",
        counters=[("serve/tokens_generated", 80)],
        gauges=[("serve/pages_in_use", 20.0), ("serve/pages_total", 31.0)],
        observes=[("serve/token_latency_ms", [3.0, 7.0, 60.0, 200.0])])
    rs = fleet_mod.ReplicaSet()
    rs.add("ra", pa)
    rs.add("rb", pb)
    view = fleet_mod.FleetPoller(rs).poll_once()
    assert view["n_up"] == 2 and view["n_replicas"] == 2
    assert view["counters"]["apex_serve_tokens_generated_total"] == 200.0
    assert "apex_serve_pages_total" not in view["counters"]
    g = view["gauges"]["apex_serve_pages_in_use"]
    assert g["by_replica"] == {"ra": 6.0, "rb": 20.0}
    assert (g["min"], g["max"], g["sum"]) == (6.0, 20.0, 26.0)
    # merged histogram == direct merge of the source snapshots
    direct = LogHistogram.merge(
        rec_a.histograms()["serve/token_latency_ms"].snapshot(),
        rec_b.histograms()["serve/token_latency_ms"].snapshot())
    got = view["histograms"]["apex_serve_token_latency_ms"]
    assert got["count"] == direct.count == 8
    assert got["counts"] == {k: v for k, v in
                             direct.snapshot()["counts"].items()}
    # exposition reconstruction keeps buckets exactly but replaces
    # exact min/max with bucket-range bounds (documented slack), so the
    # clipped p99 may drift up to one half-bucket from the direct merge
    p99 = view["hist_summary"]["apex_serve_token_latency_ms"]["p99"]
    assert direct.percentile(99) / BAND <= p99 \
        <= direct.percentile(99) * BAND
    # pooled-exact within one full bucket (reconstruction + midpoint)
    pooled = [2.0, 4.0, 9.0, 30.0, 3.0, 7.0, 60.0, 200.0]
    exact = float(np.percentile(pooled, 99))
    assert exact / BAND ** 2 <= p99 <= exact * BAND ** 2


def test_dead_endpoint_marks_down_never_raises(tmp_path):
    _, pa = _file_replica(tmp_path, "ra",
                          counters=[("serve/tokens_generated", 5)])
    rs = fleet_mod.ReplicaSet()
    rs.add("ra", pa)
    rs.add("gone", str(tmp_path / "missing.prom"))
    rs.add("refused", "http://127.0.0.1:9/metrics")   # discard port
    poller = fleet_mod.FleetPoller(rs, timeout_s=0.5)
    view = poller.poll_once()                          # must not raise
    rows = {r["replica"]: r for r in view["replicas"]}
    assert view["n_up"] == 1 and view["n_replicas"] == 3
    assert rows["ra"]["up"] == 1
    assert rows["gone"]["up"] == 0 and rows["gone"]["error"]
    assert rows["refused"]["up"] == 0 and rows["refused"]["error"]
    # live-only aggregation: the dead replicas contribute nothing
    assert view["counters"]["apex_serve_tokens_generated_total"] == 5.0


def test_one_document_many_replicas():
    """A concatenated exposition document carrying two ``replica=``
    labels classifies into two per-replica views."""
    rec = Recorder(traced_hooks=False)
    rec.counter("serve/requests_finished", 3)
    snap = export.snapshot(recorder=rec)
    text = export.render_prometheus(snap, replica="x") \
        + export.render_prometheus(snap, replica="y")
    views = fleet_mod.classify_samples(
        export.parse_prometheus(text),
        types=export.parse_prometheus_types(text))
    assert set(views) == {"x", "y"}
    for v in views.values():
        assert v["counters"]["apex_serve_requests_finished_total"] == 3.0


# ---------------------------------------------------------------------------
# router (per-thread recorder routing)
# ---------------------------------------------------------------------------

def test_replica_thread_router_routes_per_thread():
    router = fleet_mod.ReplicaThreadRouter()
    ra = Recorder(traced_hooks=False, name="a")
    rb = Recorder(traced_hooks=False, name="b")

    def work(rid, rec, n):
        router.bind(rid, rec)
        for _ in range(n):
            router.counter("hits")
        router.observe("lat_ms", float(n))

    ta = threading.Thread(target=work, args=("a", ra, 3))
    tb = threading.Thread(target=work, args=("b", rb, 5))
    ta.start(); tb.start(); ta.join(); tb.join()
    assert ra.counters()["hits"] == 3
    assert rb.counters()["hits"] == 5
    assert ra.histograms()["lat_ms"].count == 1
    # unbound thread: writes drop silently, reads are empty
    assert router.counter("hits") == 0
    assert router.records() == []
    assert router.counters() == {}
    with router.step():
        pass                                     # no-op context


# ---------------------------------------------------------------------------
# SLO evaluation + autoscale decisions (policy unit tests)
# ---------------------------------------------------------------------------

def _hist_fleet_view(ms_samples, *, counters=None, gauges=None,
                     metric="apex_serve_queue_wait_ms"):
    h = LogHistogram()
    for v in ms_samples:
        h.record(float(v))
    return {"histograms": {metric: h.snapshot()},
            "counters": counters or {}, "counters_by_replica": {},
            "gauges": gauges or {}}


def test_slo_burn_alert_fires_once_with_hysteresis():
    ev = slo_mod.SLOEvaluator()
    h = LogHistogram()                     # ONE cumulative histogram,
    for _ in range(10):                    # like a real scrape stream
        h.record(60_000.0)                 # every sample > the 30 s bound

    def view():
        return {"histograms": {"apex_serve_queue_wait_ms": h.snapshot()},
                "counters": {}, "counters_by_replica": {}, "gauges": {}}

    alerts = ev.observe(view(), t=0.0)
    assert {a["window"] for a in alerts} >= {"fast"}
    fast = next(a for a in alerts if a["window"] == "fast")
    assert fast["slo"] == "queue_wait_p99"
    assert fast["severity"] == "error"
    assert fast["burn_short"] >= 14.4
    assert "queue_wait_p99" in fast["diagnosis"]
    # sustained violation: latched, no re-fire
    for _ in range(10):
        h.record(60_000.0)
    assert ev.observe(view(), t=10.0) == []
    # recovery re-arms: only-good new samples age the bad minute out
    # of the short window, burn drops under threshold, latch clears
    t = 10.0
    for _ in range(6):
        for _ in range(2000):
            h.record(5.0)
        t += 200.0
        ev.observe(view(), t=t)
    assert ("queue_wait_p99", "fast") not in ev._latched


def test_slo_healthy_traffic_silent():
    ev = slo_mod.SLOEvaluator()
    good = _hist_fleet_view([5.0, 9.0, 40.0] * 5)
    assert ev.observe(good, t=0.0) == []
    assert ev.observe(_hist_fleet_view([5.0, 9.0, 40.0] * 6),
                      t=5.0) == []


def test_autoscale_pressure_fires_scale_out_with_rationale():
    dec = slo_mod.AutoscaleDecider()
    view = {
        "counters": {"apex_health_admission_starvation_total": 3.0},
        "counters_by_replica": {
            "apex_health_admission_starvation_total": {"rb": 3.0}},
        "gauges": {
            "apex_serve_pages_in_use": {"by_replica": {"ra": 30.0}},
            "apex_serve_pages_total": {"by_replica": {"ra": 31.0}},
            "apex_serve_queue_depth": {"sum": 4.0}},
    }
    d = dec.decide(view, alerts=[])
    assert d["decision"] == "scale_out"
    assert "3 new admission_starvation firing(s)" in d["rationale"]
    assert "worst: rb" in d["rationale"]
    assert d["inputs"]["pressure"][
        "apex_health_admission_starvation_total"] == 3.0
    # same cumulative counter next poll: no NEW pressure, cooldown holds
    assert dec.decide(view, alerts=[]) is None


def test_autoscale_rebalance_and_scale_in():
    dec = slo_mod.AutoscaleDecider(scale_in_idle_polls=3)
    hot = {"counters": {}, "counters_by_replica": {},
           "gauges": {
               "apex_serve_pages_in_use": {"by_replica": {"ra": 28.0,
                                                          "rb": 2.0}},
               "apex_serve_pages_total": {"by_replica": {"ra": 31.0,
                                                         "rb": 31.0}},
               "apex_serve_queue_depth": {"sum": 1.0}}}
    d = dec.decide(hot, alerts=[])
    assert d["decision"] == "rebalance"
    assert "'ra'" in d["rationale"] and "'rb'" in d["rationale"]
    idle = {"counters": {}, "counters_by_replica": {},
            "gauges": {
                "apex_serve_pages_in_use": {"by_replica": {"ra": 0.0,
                                                           "rb": 0.0}},
                "apex_serve_pages_total": {"by_replica": {"ra": 31.0,
                                                          "rb": 31.0}},
                "apex_serve_queue_depth": {"sum": 0.0}}}
    outs = [dec.decide(idle, alerts=[]) for _ in range(3)]
    assert outs[0] is None and outs[1] is None         # needs 3 in a row
    assert outs[2]["decision"] == "scale_in"
    assert outs[2]["severity"] == "info"


# ---------------------------------------------------------------------------
# alert correctness end to end (file fixtures → report/flight/timeline)
# ---------------------------------------------------------------------------

def _starved_pair(tmp_path):
    _, healthy = _file_replica(
        tmp_path, "healthy",
        counters=[("serve/tokens_generated", 100)],
        gauges=[("serve/pages_in_use", 2.0), ("serve/pages_total", 31.0),
                ("serve/queue_depth", 0.0)],
        observes=[("serve/queue_wait_ms", [4.0, 9.0, 15.0])])
    _, starved = _file_replica(
        tmp_path, "starved",
        counters=[("serve/tokens_generated", 10),
                  ("health/admission_starvation", 3)],
        gauges=[("serve/pages_in_use", 30.0), ("serve/pages_total", 31.0),
                ("serve/queue_depth", 6.0)],
        observes=[("serve/queue_wait_ms", [65_000.0, 70_000.0, 90_000.0])])
    return healthy, starved


def test_starved_fixture_fires_alert_and_scale_out(tmp_path):
    healthy, starved = _starved_pair(tmp_path)
    rec = Recorder(traced_hooks=False, name="fleet-ctl")
    rs = fleet_mod.ReplicaSet()
    rs.add("healthy", healthy)
    rs.add("starved", starved)
    poller = fleet_mod.FleetPoller(rs, recorder=rec)
    view = poller.poll_once()
    # the fast-burn page fires (half the new queue waits blow the 30 s
    # objective → burn far above 14.4x on the 1% budget)
    assert any(a["slo"] == "queue_wait_p99" and a["window"] == "fast"
               for a in view["alerts"]), view["alerts"]
    (decision,) = view["decisions"]
    assert decision["decision"] == "scale_out"
    assert "admission_starvation" in decision["rationale"]
    assert "worst: starved" in decision["rationale"]
    # typed health events + the fleet poll event landed in the recorder
    health = rec.records("health_event")
    names = [e["name"] for e in health]
    assert "slo_alert" in names and "scale_decision" in names
    sd = next(e for e in health if e["name"] == "scale_decision")
    assert sd["diagnosis"].startswith("[scale_out]")
    # shadow counters make the control plane itself scrapeable
    assert rec.counters()["health/slo_alert"] >= 1
    assert rec.counters()["fleet/decision_scale_out"] == 1
    # ## fleet and ## health render from the same record stream
    rendered = monitor.render_report(rec.records())
    assert "## fleet (multi-replica aggregation)" in rendered
    assert "## health" in rendered
    assert "slo_alert" in rendered and "[scale_out]" in rendered
    agg = monitor.aggregate(rec.records())
    assert agg["fleet"]["n_up"] == 2
    assert agg["fleet"]["alerts"] and agg["fleet"]["decisions"]
    # flight-dump → timeline: the events survive as health instants
    from apex_tpu.monitor import flight, timeline
    path = flight.snapshot(reason="test", directory=str(tmp_path),
                           recorder=rec)
    trace = timeline.build_timeline(timeline.load_sources([path]))
    names = {e["name"] for e in trace["traceEvents"]}
    assert "health/slo_alert" in names
    assert "health/scale_decision" in names
    assert timeline.validate_timeline(trace) == []


def test_healthy_pair_silent(tmp_path):
    healthy, _ = _starved_pair(tmp_path)
    _, healthy2 = _file_replica(
        tmp_path, "healthy2",
        counters=[("serve/tokens_generated", 90)],
        gauges=[("serve/queue_depth", 0.0)],
        observes=[("serve/queue_wait_ms", [3.0, 8.0])])
    rec = Recorder(traced_hooks=False)
    rs = fleet_mod.ReplicaSet()
    rs.add("healthy", healthy)
    rs.add("healthy2", healthy2)
    view = fleet_mod.FleetPoller(rs, recorder=rec).poll_once()
    assert view["alerts"] == [] and view["decisions"] == []
    assert rec.records("health_event") == []


def test_fleet_cli_once_json_gates(tmp_path, capsys):
    """``monitor fleet --once --json``: healthy pair exits 0 with both
    replicas + a merged histogram in the JSON; the starved pair exits
    non-zero with the alert in the view."""
    import json as json_mod
    from apex_tpu.monitor.__main__ import main as cli_main
    healthy, starved = _starved_pair(tmp_path)
    rc = cli_main(["fleet", healthy, starved, "--once", "--json"])
    view = json_mod.loads(capsys.readouterr().out)
    assert rc == 1
    assert {r["replica"] for r in view["replicas"]} == \
        {"healthy", "starved"}
    assert view["alerts"]
    _, healthy2 = _file_replica(
        tmp_path, "h2", observes=[("serve/queue_wait_ms", [2.0])])
    rc = cli_main(["fleet", healthy, healthy2, "--once", "--json"])
    view = json_mod.loads(capsys.readouterr().out)
    assert rc == 0
    assert view["n_up"] == 2 and not view["alerts"]
    assert "apex_serve_queue_wait_ms" in view["hist_summary"]


# ---------------------------------------------------------------------------
# the live two-replica round trip (the flagship contract)
# ---------------------------------------------------------------------------

PROMPTS_A = [[5, 9, 17, 3, 40, 22, 8], [11, 2, 33, 60, 7, 7, 1]]
PROMPTS_B = [[4, 8, 15, 16, 23, 42], [1, 3, 5, 7]]
N_NEW = 8


def test_live_two_replica_fleet_roundtrip(params):
    eng_a = _engine(params)
    eng_b = _engine(params)
    fleet = fleet_mod.LocalFleet([eng_a, eng_b])
    ctl = Recorder(traced_hooks=False, name="fleet-ctl")
    rid_a, rid_b = eng_a.replica_id, eng_b.replica_id
    with monitor.attached(fleet.router):
        fleet.start({rid_a: [(p, N_NEW) for p in PROMPTS_A],
                     rid_b: [(p, N_NEW) for p in PROMPTS_B]})
        fleet.wait_ready()
        poller = fleet_mod.FleetPoller(fleet.replica_set, recorder=ctl,
                                       timeout_s=10.0)
        # scrape while serving — must never raise
        poller.poll_once()
        deadline = time.monotonic() + 120.0
        while not fleet.drained():
            assert time.monotonic() < deadline, "fleet never drained"
            time.sleep(0.05)
        # post-drain, pre-release: the endpoints are still held open —
        # the counters-sum-exactly moment
        view = poller.poll_once()
        assert view["n_up"] == 2
        # now kill ONE replica: its endpoint dies, the fleet degrades
        fleet.release(rid_b)
        deadline = time.monotonic() + 30.0
        while True:
            down_view = poller.poll_once()        # never raises
            rows = {r["replica"]: r for r in down_view["replicas"]}
            if rows[rid_b]["up"] == 0:
                break
            assert time.monotonic() < deadline, "replica never went down"
            time.sleep(0.05)
        assert rows[rid_a]["up"] == 1
        assert rows[rid_b]["age_s"] is not None
        assert rows[rid_b]["age_s"] >= 0.0
        assert down_view["n_up"] == 1
        outputs = fleet.join()
    # every request completed on both replicas
    n_tokens = {rid: sum(len(v) for v in outs.values())
                for rid, outs in outputs.items()}
    assert n_tokens[rid_a] == len(PROMPTS_A) * N_NEW
    assert n_tokens[rid_b] == len(PROMPTS_B) * N_NEW
    # counters sum EXACTLY across replicas at the post-drain scrape
    assert view["counters"]["apex_serve_tokens_generated_total"] == \
        n_tokens[rid_a] + n_tokens[rid_b]
    assert view["counters"]["apex_serve_requests_finished_total"] == \
        len(PROMPTS_A) + len(PROMPTS_B)
    assert view["counters_by_replica"][
        "apex_serve_tokens_generated_total"] == \
        {rid_a: float(n_tokens[rid_a]), rid_b: float(n_tokens[rid_b])}
    # merged histogram == direct merge of the per-replica recorders'
    # histograms (same buckets; the scrape round trip may only fold
    # underflow — token latencies are in-range so p99 matches the band)
    direct = LogHistogram.merge(
        fleet.recorders[rid_a].histograms()[
            "serve/token_latency_ms"].snapshot(),
        fleet.recorders[rid_b].histograms()[
            "serve/token_latency_ms"].snapshot())
    got = view["hist_summary"]["apex_serve_token_latency_ms"]
    assert got["count"] == direct.count
    assert direct.percentile(99) / BAND <= got["p99"] \
        <= direct.percentile(99) * BAND
    # the dead-replica poll aggregated the LIVE replica only
    assert down_view["counters"][
        "apex_serve_tokens_generated_total"] == n_tokens[rid_a]
    # the control recorder carried one fleet event per poll
    polls = ctl.records("fleet")
    assert len(polls) == poller.polls
    agg = monitor.aggregate(ctl.records())
    assert agg["fleet"]["polls"] == poller.polls


def test_purity_jaxprs_byte_identical_under_scraping(params):
    """Re-tracing the engine's compiled programs while a FleetPoller
    actively scrapes a live exporter through the thread router yields
    byte-identical jaxprs — the whole fleet layer is host-side."""
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
    router = fleet_mod.ReplicaThreadRouter()
    rec = Recorder(traced_hooks=False, name="r0")
    router.bind("r0", rec)
    rec.observe("serve/token_latency_ms", 1.0)
    exporter = export.MetricsExporter(recorder=rec, port=0, replica="r0")
    port = exporter.start()
    rs = fleet_mod.ReplicaSet()
    rs.add("r0", f"http://127.0.0.1:{port}/metrics")
    poller = fleet_mod.FleetPoller(rs, timeout_s=5.0)
    stop = threading.Event()

    def scrape_loop():
        while not stop.is_set():
            poller.poll_once()
            time.sleep(0.005)

    th = threading.Thread(target=scrape_loop, daemon=True)
    th.start()
    try:
        with monitor.attached(router):
            attached = trace_both()
    finally:
        stop.set()
        th.join(10)
        exporter.stop()
    assert attached[0] == detached[0], "decode jaxpr drifted under fleet"
    assert attached[1] == detached[1], "prefill jaxpr drifted under fleet"
    assert "callback" not in detached[0] and "callback" not in detached[1]
    assert poller.last_view["n_up"] == 1
