"""monitor.spans: typed span events + log-scale streaming histograms.

The acceptance contracts:

- histogram percentile estimates match exact nearest-rank quantiles to
  within the bucket-resolution bound (``10^(1/(2*bpd)) - 1`` relative)
  — the O(1)-memory claim is only honest if the error bound is proven;
- span nesting builds correct parent links, exception unwind closes
  the span with the error attached and re-raises;
- detached mode is free: no ids, no events, no open-span state;
- ``Recorder.observe`` histograms survive the dump → load → aggregate
  round trip (cumulative ``histogram`` snapshot events).
"""

import io
import math
import random

import pytest

from apex_tpu import monitor
from apex_tpu.monitor import spans
from apex_tpu.monitor.spans import LogHistogram


# ---------------------------------------------------------------------------
# LogHistogram
# ---------------------------------------------------------------------------

def test_histogram_percentiles_within_bucket_resolution_bound():
    """Estimated percentiles vs exact nearest-rank quantiles of the
    same samples: the geometric-midpoint estimate must sit within one
    half-bucket of the exact sample (relative error <= 10^(1/(2*bpd))
    - 1, ~12.2% at the default bpd=10)."""
    h = LogHistogram()
    rng = random.Random(0)
    vals = [math.exp(rng.gauss(2.0, 1.5)) for _ in range(5000)]
    for v in vals:
        h.record(v)
    exact_sorted = sorted(vals)
    bound = 10.0 ** (1.0 / (2 * h.bpd)) - 1.0
    for p in (10, 50, 90, 95, 99, 99.9):
        exact = exact_sorted[max(1, math.ceil(p / 100 * len(vals))) - 1]
        est = h.percentile(p)
        rel = abs(est - exact) / exact
        assert rel <= bound + 1e-9, (p, exact, est, rel, bound)
    # exact (not bucketed) moments ride alongside
    assert h.count == len(vals)
    assert h.min == min(vals) and h.max == max(vals)
    assert h.sum == pytest.approx(sum(vals))
    assert h.mean == pytest.approx(sum(vals) / len(vals))


def test_histogram_under_overflow_and_edges():
    h = LogHistogram(lo=1.0, hi=1000.0, buckets_per_decade=10)
    assert h.n_buckets == 30
    for v in (0.0, -5.0, 0.5):          # <= 0 and < lo -> underflow
        h.record(v)
    h.record(5000.0)                    # >= hi -> overflow
    h.record(10.0)                      # an exact bucket edge
    assert h.underflow == 3 and h.overflow == 1 and h.count == 5
    # p10 falls in the underflow mass -> observed min; p99 -> max
    assert h.percentile(10) == -5.0
    assert h.percentile(99) == 5000.0
    # the edge sample landed in exactly one bucket
    assert sum(h._counts) == 1


def test_histogram_snapshot_roundtrip():
    h = LogHistogram()
    rng = random.Random(1)
    for _ in range(500):
        h.record(math.exp(rng.gauss(0.0, 2.0)))
    snap = h.snapshot()
    h2 = LogHistogram.from_snapshot(snap)
    for p in (50, 95, 99):
        assert h2.percentile(p) == h.percentile(p)
    assert (h2.count, h2.underflow, h2.overflow) == \
        (h.count, h.underflow, h.overflow)
    summ = spans.hist_summary(snap)
    assert summ["count"] == h.count
    assert summ["p50"] == pytest.approx(h.percentile(50))


@pytest.mark.parametrize("v, k", [(7.3, 256), (0.0, 3), (5e-4, 64),
                                  (1e7, 2), (1.0, 1), (20.28, 512)])
def test_weighted_record_equals_that_many_records(v, k):
    """``record(v, n=k)`` leaves what ``k`` calls of ``record(v)`` leave:
    the bucket, the underflow and overflow bins, ``count`` and ``sum``;
    and so does the recorder's ``observe(.., n=k)`` on top of it."""
    one, many = LogHistogram(), LogHistogram()
    for h in (one, many):
        h.record(3.0)
    one.record(v, n=k)
    for _ in range(k):
        many.record(v)
    assert one.snapshot() == many.snapshot()
    assert one.percentile(99) == many.percentile(99)
    rec = monitor.Recorder()
    rec.observe("t/h", 3.0)
    rec.observe("t/h", v, n=k)
    assert rec.histograms()["t/h"].snapshot() == one.snapshot()


def test_histogram_validation():
    with pytest.raises(ValueError):
        LogHistogram(lo=0.0, hi=1.0)
    with pytest.raises(ValueError):
        LogHistogram(lo=2.0, hi=1.0)
    with pytest.raises(ValueError):
        LogHistogram(buckets_per_decade=0)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_parent_links_and_durations():
    rec = monitor.Recorder()
    with monitor.attached(rec):
        with spans.span("outer") as outer:
            with spans.span("inner") as inner:
                pass
        assert outer is not None and inner is not None
    starts = {e["value"]: e for e in rec.records("span_start")}
    ends = {e["span"]: e for e in rec.records("span_end")}
    assert starts[outer]["parent"] is None
    assert starts[inner]["parent"] == outer      # implicit nesting
    assert ends[inner]["parent"] == outer
    assert ends[outer]["value"] >= ends[inner]["value"] >= 0.0
    assert spans.open_spans() == 0


def test_span_exception_unwind():
    rec = monitor.Recorder()
    with monitor.attached(rec):
        with pytest.raises(ValueError):
            with spans.span("will_fail"):
                raise ValueError("boom")
    (end,) = rec.records("span_end")
    assert end["name"] == "will_fail" and end["error"] == "ValueError"
    assert spans.open_spans() == 0


def test_explicit_parent_across_turns():
    """A request-shaped span: the root outlives many child open/close
    cycles; children link to it by explicit parent id."""
    rec = monitor.Recorder()
    with monitor.attached(rec):
        root = spans.start("request", seq_id=7)
        for _ in range(3):
            with spans.span("child", parent=root, seq_id=7):
                pass
        spans.annotate("transition", span=root, seq_id=7, cause="evict")
        dur = spans.end(root, seq_id=7, tokens=3)
    assert dur is not None and dur >= 0.0
    child_starts = [e for e in rec.records("span_start")
                    if e["name"] == "child"]
    assert len(child_starts) == 3
    assert all(e["parent"] == root for e in child_starts)
    (note,) = rec.records("span_event")
    assert note["cause"] == "evict" and note["value"] == root
    agg = rec.aggregate()
    assert agg["spans"]["by_name"]["child"]["n"] == 3


def test_spans_detached_are_free():
    """No recorder: start returns None, everything downstream no-ops,
    and NO open-span state accumulates (the detached hot path is one
    global read)."""
    assert monitor.get_recorder() is None
    before = spans.open_spans()
    sid = spans.start("nope")
    assert sid is None
    assert spans.end(sid) is None
    spans.annotate("nope", span=sid)
    with spans.span("nope") as s:
        assert s is None
    assert spans.open_spans() == before


# -- the second sink: the profiler's host plane ----------------------------

def _host_events(logdir):
    """``[(name, span id or None, seconds)]`` of the ``/host:CPU`` plane
    of the profiler session under ``logdir``."""
    import glob
    import os
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                sid = next((v for k, v in ev.stats if k == "span"), None)
                out.append((ev.name, sid, ev.duration_ns * 1e-9))
    return out


def _profiled(logdir, fn):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_events(logdir)


def test_span_is_on_the_profilers_host_plane_with_its_id(tmp_path):
    """Attached, a block span is in the recorder AND on the host plane of
    the profiler's trace, under the same name with its id as the ``span``
    stat; both sinks agree on how long it took."""
    import time
    rec = monitor.Recorder()
    ids = {}

    def work():
        with monitor.attached(rec):
            with spans.span("t/outer", k=1) as ids["outer"]:
                with spans.span("t/inner") as ids["inner"]:
                    time.sleep(0.02)
    events = _profiled(tmp_path, work)
    ends = {e["span"]: e for e in rec.records("span_end")}
    for name in ("outer", "inner"):
        (hit,) = [e for e in events if e[0] == "t/" + name]
        assert hit[1] == ids[name]
        assert hit[2] == pytest.approx(ends[ids[name]]["value"], abs=2e-3)
        assert hit[2] >= 0.02


def test_detached_span_makes_no_annotation_and_no_id(tmp_path):
    assert monitor.get_recorder() is None
    next_id = spans._next_id

    def work():
        with spans.span("t/detached") as sid:
            assert sid is None
    events = _profiled(tmp_path, work)
    assert not [e for e in events if e[0] == "t/detached"]
    assert spans._next_id == next_id


def test_start_end_spans_stay_recorder_only(tmp_path):
    """A span that outlives a block (``serve/request``) has no extent on
    one thread's timeline: the recorder alone holds it."""
    rec = monitor.Recorder()

    def work():
        with monitor.attached(rec):
            spans.end(spans.start("t/request", seq_id=3))
    events = _profiled(tmp_path, work)
    assert not [e for e in events if e[0] == "t/request"]
    assert [e["name"] for e in rec.records("span_end")] == ["t/request"]


def test_span_detach_mid_flight_drops_cleanly():
    """A span whose recorder detaches before end(): the close is
    dropped (no event, no crash) and the open-table entry is freed."""
    rec = monitor.Recorder()
    monitor.attach(rec)
    sid = spans.start("orphan")
    monitor.detach()
    assert spans.end(sid) is not None     # duration still measured
    assert rec.records("span_end") == []  # ...but nothing emitted
    assert spans.open_spans() == 0


# ---------------------------------------------------------------------------
# Recorder.observe -> aggregate round trip
# ---------------------------------------------------------------------------

def test_observe_histograms_roundtrip_through_dump():
    rec = monitor.Recorder(name="hist_rt")
    for v in (1.0, 2.0, 4.0, 8.0, 16.0):
        rec.observe("serve/token_latency_ms", v)
    rec.observe("serve/ttft_ms", 40.0)
    # no per-sample events: O(1) stream traffic under sustained load
    assert rec.records("histogram") == []
    agg = rec.aggregate()                 # live snapshot, no emit needed
    assert agg["histograms"]["serve/token_latency_ms"]["count"] == 5
    buf = io.StringIO()
    rec.dump_jsonl(buf)
    buf.seek(0)
    header, events = monitor.load_jsonl(buf)
    agg2 = monitor.aggregate(events, header=header)
    h = agg2["histograms"]["serve/token_latency_ms"]
    assert h["count"] == 5 and h["min"] == 1.0 and h["max"] == 16.0
    assert agg2["serve"]["slo"]["token_latency_ms"]["p50"] == \
        agg["serve"]["slo"]["token_latency_ms"]["p50"]
    # emit_histograms flushes the same snapshot into the ring/stream
    rec.emit_histograms()
    evs = rec.records("histogram")
    assert {e["name"] for e in evs} == {"serve/token_latency_ms",
                                        "serve/ttft_ms"}
    assert all(e["value"] == e_count for e, e_count in
               zip(sorted(evs, key=lambda e: e["name"]), (5, 1)))


def test_observe_custom_bucket_range_first_call_wins():
    rec = monitor.Recorder()
    rec.observe("x", 5.0, lo=1.0, hi=100.0, buckets_per_decade=5)
    rec.observe("x", 7.0, lo=999.0)       # ignored: histogram exists
    h = rec.histograms()["x"]
    assert (h.lo, h.hi, h.bpd) == (1.0, 100.0, 5)
    assert h.count == 2
