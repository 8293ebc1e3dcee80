"""The serve round on the host (``harness/host_round.py``) and the four
readers built on it: on hand-made spans, counters and sessions whose every
number is worked out beside it, on runs that lack what they read, and on the
tiny serve rehearsal's CPU session for cell 3's and cell 5's shapes."""

import os
import types

import pytest

from benchmarks.harness import host_round as hr
from benchmarks.harness import manifest, span_reduce as sr
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness import tracing

from . import _tiny
from .test_deepseek import TINY_DEEPSEEK

READERS = ("host_busy_share", "decode_dispatch_ms_p50",
           "rounds_overlapped_share", "idle_host_late_share")
US = 1e-6


def _reader(name):
    return manifest.load_layer_metric(name).compute


# -- a session, hand-made ------------------------------------------------------
# The recorder's clock is in seconds, the plane's runs AHEAD of it by SKEW.
# ``TREE`` is one line a span: (id, name, parent, start_us, dur_us, on the
# plane?, attributes). Six rounds; the profiler session holds rounds 2-6.
#
#   round  period  token waits that began in it          busy   share
#   2      10 ms   4 ms (under its decode_step)          6      60%
#   3      12 ms   3 ms (a prefill went out before)      9      75%
#   4       5 ms   2 ms (no decode: under serve/sample)  3      60%
#   5     110 ms   104 ms (the machine stalled in it)    6      5.45%
#   6      --      the session's last: no successor there, left out
#   1, 7   --      outside the session: in the recorder alone
SKEW = 7.0
TREE = [
    (10, "serve/round", None, 0, 9000, False, {}),
    (11, "serve/decode_step", 10, 1000, 7000, False, {"n_active": 4}),
    (12, "serve/decode_dispatch", 11, 1000, 900, False, {}),
    (13, "serve/token_wait", 11, 2000, 6000, False, {"n_read": 1}),
    (20, "serve/round", None, 10000, 9000, True, {}),
    (21, "serve/decode_inputs", 20, 10500, 1000, True, {}),
    (22, "serve/decode_step", 20, 11500, 5000, True, {"n_active": 4}),
    (23, "serve/decode_dispatch", 22, 11500, 1000, True, {}),
    (24, "serve/token_wait", 22, 12500, 4000, True, {"n_read": 1}),
    (30, "serve/round", None, 20000, 11000, True, {}),
    (31, "serve/prefill", 30, 20500, 2000, True, {"seq_id": 1,
                                                 "resumed": False}),
    (32, "serve/decode_step", 30, 23500, 4400, True, {"n_active": 3}),
    (33, "serve/decode_dispatch", 32, 23500, 1400, True, {}),
    (34, "serve/token_wait", 32, 24900, 3000, True, {"n_read": 2}),
    (40, "serve/round", None, 32000, 4000, True, {}),
    (41, "serve/prefill", 40, 32200, 1500, True, {"seq_id": 2,
                                                 "resumed": False}),
    (42, "serve/sample", 40, 33800, 2100, True, {}),
    (43, "serve/token_wait", 42, 33800, 2000, True, {"n_read": 2}),
    (50, "serve/round", None, 37000, 109000, True, {}),
    (52, "serve/decode_step", 50, 38000, 105000, True, {"n_active": 4}),
    (53, "serve/decode_dispatch", 52, 38000, 1000, True, {}),
    (54, "serve/token_wait", 52, 39000, 104000, True, {"n_read": 1}),
    (60, "serve/round", None, 147000, 8000, True, {}),
    (62, "serve/decode_step", 60, 148000, 6000, True, {"n_active": 4}),
    (63, "serve/decode_dispatch", 62, 148000, 1200, True, {}),
    (64, "serve/token_wait", 62, 149200, 4800, True, {"n_read": 1}),
    (70, "serve/round", None, 210000, 8000, False, {}),
    (72, "serve/decode_step", 70, 211000, 6000, False, {"n_active": 4}),
    (73, "serve/decode_dispatch", 72, 211000, 800, False, {}),
    (74, "serve/token_wait", 72, 211800, 5200, False, {"n_read": 1}),
]
COUNTERS = [("serve/rounds_overlapped", 1100, {}),
            ("serve/rounds_overlapped", 11600, {}),
            ("serve/rounds_overlapped", 23600, {}),
            ("serve/pipeline_drains", 33700, {"reason": "idle"}),
            ("serve/rounds_overlapped", 148100, {}),
            ("serve/pipeline_drains", 209000, {"reason": "preempt"}),
            ("serve/tokens_generated", 209500, {})]
# the device, in us on the plane's clock (SKEW left out here, added below):
# five programs; the gaps after them are
#   30 us, midpoint inside serve/decode_dispatch 33   the host's
#   40 us, midpoint inside serve/prefill 41           the host's
#   50 us, midpoint inside serve/token_wait 54        the runtime's: left out
#   10 us                                             under 20 us: left out
# and the window is whole periods: 11600 .. 148000 = 136.4 ms
PROGRAMS = [(11600, 23600 - 30 - 11600), (23600, 33000 - 40 - 23600),
            (33000, 45000 - 50 - 33000), (45000, 147990 - 45000),
            (148000, 5000)]
ADMIT = (31100, 800)        # the harness's own work between rounds 3 and 4
WINDOW_S = (148000 - 11600) * US
LATE_S = (30 + 40) * US


def _events(tree=TREE, counters=COUNTERS):
    """The recorder's side of the session, in its order of emission."""
    rows = []
    for sid, name, parent, s, d, _, attrs in tree:
        rows.append((s * US, {"kind": "span_start", "name": name,
                              "value": sid, "parent": parent, **attrs}))
        rows.append(((s + d) * US, {"kind": "span_end", "name": name,
                                    "value": d * US, "span": sid,
                                    "parent": parent}))
    for name, t, attrs in counters:
        rows.append((t * US, {"kind": "counter", "name": name, "value": 1,
                              **attrs}))
    return [{**e, "t": t} for t, e in sorted(rows, key=lambda r: r[0])]


def _plane(tree=TREE):
    return [(SKEW + s * US, SKEW + (s + d) * US, name, sid)
            for sid, name, _, s, d, on, _ in tree if on]


def _textproto(planes):
    """An XSpace text proto of ``{plane: {line: [(start_s, end_s, name,
    span id or None)]}}``; a span id goes out as the stat ``span``, as
    ``monitor/spans.py:span`` writes it."""
    out = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        meta = {}
        body = []
        for lid, (line, rows) in enumerate(lines.items(), 1):
            evs = []
            for s, e, name, sid in rows:
                mid = meta.setdefault(name, len(meta) + 1)
                stat = (f" stats {{ metadata_id: 1 int64_value: {sid} }}"
                        if sid is not None else "")
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{round(s * 1e12)} duration_ps: "
                           f"{round((e - s) * 1e12)}{stat} }}")
            body.append(f'lines {{ id: {lid} name: "{line}" '
                        f'timestamp_ns: 0 {" ".join(evs)} }}')
        out.append(f'planes {{ id: {pid} name: "{plane}"')
        out.append('stat_metadata { key: 1 value { id: 1 name: "span" } }')
        for name, mid in meta.items():
            quoted = name.replace("\\", "\\\\").replace('"', '\\"')
            out.append(f'event_metadata {{ key: {mid} value {{ id: {mid} '
                       f'name: "{quoted}" }} }}')
        out += body + ["}"]
    return "\n".join(out)


def _device(programs, t0=0.0):
    """One chip that runs ``programs`` (start_us, dur_us): a module event
    and one operation each."""
    return {"XLA Modules": [(t0 + s * US, t0 + (s + d) * US,
                             f"jit_decode({i})", None)
                            for i, (s, d) in enumerate(programs)],
            "XLA Ops": [(t0 + s * US, t0 + (s + d) * US,
                         f"%fusion.{i} = f32[4]{{0}} fusion(f32[4] %x)",
                         None) for i, (s, d) in enumerate(programs)]}


def _session_run(tmp_path, monkeypatch, tree=TREE, counters=COUNTERS,
                 programs=PROGRAMS):
    """A traced run as ``run.py`` hands it to a reader, over the hand-made
    session."""
    host = {"python": _plane(tree) + [
        (SKEW + ADMIT[0] * US, SKEW + sum(ADMIT) * US, "admit", None)]}
    path = tmp_path / "session.textproto"
    path.write_text(_textproto({
        "/device:TPU:0": _device(programs, SKEW), "/host:CPU": host}))
    monkeypatch.setattr(sr, "trace_path", lambda run: str(path))
    prog = types.SimpleNamespace(engine=types.SimpleNamespace(max_batch=4))
    return {"workload": "cell", "traced": {"rounds": 5}, "notes": {},
            "trace": tr.reduce_file(str(path), tracing.ANNOTATIONS),
            "window_events": _events(tree, counters), "program": prog}


def test_round_periods_by_hand():
    spans = sr.closed_spans(_events())
    got = hr.round_periods(spans, _plane())
    assert [(round(p / US), [round(w / US) for w in ws]) for p, ws in got] \
        == [(10000, [4000]), (12000, [3000]), (5000, [2000]),
            (110000, [104000])]
    # a wait between two steps (a forced preempt's read) goes to the round
    # it began in; one that began before the first counted round to none
    extra = TREE + [(99, "serve/token_wait", None, 29500, 400, True, {}),
                    (98, "serve/token_wait", None, 9990, 5, True, {})]
    got = hr.round_periods(sr.closed_spans(_events(extra)), _plane(extra))
    assert [round(w / US) for w in got[0][1]] == [4000]
    assert [round(w / US) for w in got[1][1]] == [3000, 400]
    # an id on the plane under another name is not the recorder's span
    plane = [(s, e, "serve/other", sid) if sid == 30 else (s, e, n, sid)
             for s, e, n, sid in _plane()]
    assert [round(p / US) for p, _ in hr.round_periods(spans, plane)] == \
        [5000, 110000]
    assert hr.round_periods(spans, []) == []
    assert hr.round_periods([], _plane()) == []


def test_the_four_readers_on_the_hand_made_session(tmp_path, monkeypatch):
    run = _session_run(tmp_path, monkeypatch)
    # shares 60, 75, 60, 5.4545: the stalled round moves the maximum of the
    # waits and not the median share
    assert _reader("host_busy_share")(run) == pytest.approx(60.0)
    assert run["notes"]["token_wait"] == {
        "n": 4, "ms_p50": pytest.approx(3.5),
        "ms_p99": pytest.approx(4.0 + 0.97 * 100.0),
        "ms_max": pytest.approx(104.0)}
    # a decode step beside its two children: 100 us in round 1's, none
    # in the others'
    assert run["notes"]["host_round"] == {
        "rounds": 4, "period_ms_p50": pytest.approx(11.0),
        "busy_ms_p50": pytest.approx(6.0),
        "step_rest_us_p50": pytest.approx(0.0, abs=1e-6),
        "step_rest_us_min": pytest.approx(0.0, abs=1e-6),
        "step_rest_us_max": pytest.approx(100.0)}
    # every dispatch of the WINDOW, the recorder's durations: 0.9, 1.0,
    # 1.4, 1.0, 1.2, 0.8 ms
    assert _reader("decode_dispatch_ms_p50")(run) == pytest.approx(1.0)
    # four counter events over the WINDOW's six decode steps
    assert _reader("rounds_overlapped_share")(run) == \
        pytest.approx(100.0 * 4 / 6)
    assert run["notes"]["pipeline_drains"] == {"idle": 1, "preempt": 1}
    idle = run["notes"]["idle_by_span"]
    assert idle == {"serve/token_wait": pytest.approx(50 * US),
                    "serve/prefill": pytest.approx(40 * US),
                    "serve/decode_dispatch": pytest.approx(30 * US),
                    "gaps-under-20us": pytest.approx(10 * US)}
    assert run["trace"]["window_s"] == pytest.approx(WINDOW_S)
    assert _reader("idle_host_late_share")(run) == \
        pytest.approx(100.0 * LATE_S / WINDOW_S)
    link = run["notes"]["span_clock"]["joined"]
    assert link["serve/decode_dispatch"] == link["serve/decode_step"] == 4
    assert link["serve/token_wait"] == 5


def test_a_gap_under_the_harness_is_the_hosts_too(tmp_path, monkeypatch):
    """The third program ends early, inside ``admit``: what the harness
    does between two steps keeps the next dispatch from going out."""
    programs = [PROGRAMS[0], (23600, 31500 - 23600 - 25),
                (31500, 45000 - 50 - 31500)] + PROGRAMS[3:]
    run = _session_run(tmp_path, monkeypatch, programs=programs)
    assert _reader("idle_host_late_share")(run) == \
        pytest.approx(100.0 * (30 + 25) * US / WINDOW_S)
    assert run["notes"]["idle_by_span"]["admit"] == pytest.approx(25 * US)


def test_nothing_is_reported_without_the_spans(tmp_path, monkeypatch):
    """The parent's program: ``serve/decode_step`` whole, no dispatch and
    no wait told apart. Every second of a round would read as busy and
    every gap as the host's, so neither is reported; the counters are
    there and their share is."""
    tree = [r for r in TREE if r[1] not in (hr.DISPATCH, hr.WAIT)]
    run = _session_run(tmp_path, monkeypatch, tree)
    assert _reader("host_busy_share")(run) is None
    assert _reader("decode_dispatch_ms_p50")(run) is None
    assert _reader("idle_host_late_share")(run) is None
    assert "token_wait" not in run["notes"]
    assert "serve/decode_step" in run["notes"]["idle_by_span"]
    assert _reader("rounds_overlapped_share")(run) == \
        pytest.approx(100.0 * 4 / 6)
    # and without the decode step's span on the plane, not even that
    tree = [r[:5] + (False,) + r[6:] for r in TREE]
    run = _session_run(tmp_path, monkeypatch, tree)
    assert [_reader(n)(run) for n in READERS] == [None] * 4


@pytest.mark.parametrize("name", READERS)
def test_reader_reports_nothing_without_a_session(name, tmp_path,
                                                  monkeypatch):
    """No trace file, no session, a session without any span: None, and
    none raises."""
    monkeypatch.setattr(sr, "ROOT", str(tmp_path))
    prog = types.SimpleNamespace(engine=types.SimpleNamespace(max_batch=4))

    def bare(**more):
        return {"workload": "cell", "traced": {"rounds": 3}, "trace": None,
                "window_events": _events(), "notes": {}, "program": prog,
                **more}
    compute = _reader(name)
    assert compute(bare()) is None
    assert compute(bare(traced=None)) is None
    run = _session_run(tmp_path, monkeypatch, tree=[])
    assert compute(run) is None


# -- the rehearsal: the engine's own spans, tiny, on the CPU --------------------

CELLS = {"tiny-serve": (_tiny.TINY_GPT, _tiny.TINY_SERVE, 1),
         "tiny-latent": (TINY_DEEPSEEK, _tiny.TINY_SERVE, 1)}
GAP = 30 * US


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return _tiny.make_root(tmp_path_factory.mktemp("bench"), CELLS)


def _a_device_beside_the_cpu_session(monkeypatch):
    """The CPU's session has the host plane and no device. Beside its
    program spans and harness annotations, as they are: one chip that runs
    a program from the end of each ``serve/decode_dispatch`` to 30 us
    before the end of the next, with 30 us off in the middle of the
    ``serve/token_wait`` that follows the dispatch: an in-span dispatch gap
    a round, and a gap under the wait."""
    def beside(logdir):
        lines = sr.host_lines(tr.load(tr.newest_xplane(logdir)))
        # the session's clock counts from the epoch: picoseconds of it
        # do not fit the proto's 64 bits
        base = min(r[0] for rows in lines.values() for r in rows)
        host = {ln: [(s - base, e - base, n, sid) for s, e, n, sid in rows
                     if sid is not None or n in tracing.ANNOTATIONS]
                for ln, rows in lines.items()}
        spans = sorted(r for rows in host.values() for r in rows)
        sent = [r for r in spans if r[2] == hr.DISPATCH]
        waits = [r for r in spans if r[2] == hr.WAIT]
        modules, ops = [], []
        for i, (a, b) in enumerate(zip(sent, sent[1:])):
            s, e = a[1], b[1] - GAP
            if e - s < 4 * GAP:
                continue
            modules.append((s, e, f"jit_decode({i})", None))
            w = next((w for w in waits if s <= w[0] and w[1] <= b[0]
                      and w[1] - w[0] > 4 * GAP), None)
            mid = 0.5 * (w[0] + w[1]) if w else None
            for k, (lo, hi) in enumerate(
                    [(s, mid - GAP / 2), (mid + GAP / 2, e)] if w
                    else [(s, e)]):
                ops.append((lo, hi, f"%fusion.{2 * i + k} = f32[4]{{0}} "
                                    f"fusion(f32[4] %x)", None))
        path = os.path.join(logdir, "beside.textproto")
        with open(path, "w") as f:
            f.write(_textproto({
                "/device:TPU:0": {"XLA Modules": modules, "XLA Ops": ops},
                "/host:CPU": host}))
        return path

    def reduce(self):
        out = tr.reduce_file(beside(self.logdir), tracing.ANNOTATIONS)
        out["xplane_bytes"] = 0
        return out
    monkeypatch.setattr(tracing.Tracer, "reduce", reduce)
    monkeypatch.setattr(
        sr, "trace_path", lambda run: os.path.join(
            sr.ROOT, ".bench_trace", run["workload"], "beside.textproto")
        if run.get("traced") else None)


@pytest.mark.parametrize("cell", list(CELLS))
def test_all_four_from_the_cpu_session(copy, monkeypatch, cell):
    root, man = copy
    monkeypatch.setattr(sr, "ROOT", root)
    _a_device_beside_the_cpu_session(monkeypatch)
    lines, res = _tiny.run(root, man, cell, trace=True, seconds=1.0)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(READERS) <= set(m)
    # every entry the cell reported before is still there
    assert {"round_host_ms_p50", "prefill_ms_p50", "decode_batch_fill",
            "decode_step_ms_p50", "device_idle_share.serve"} <= set(m)
    notes = next(l for l in lines if l["phase"] == "trace")["notes"]
    joined = notes["span_clock"]["joined"]
    assert joined["serve/decode_dispatch"] == joined["serve/decode_step"] > 3
    assert joined["serve/token_wait"] >= joined["serve/decode_step"] - 1
    # on the CPU the "device" computes on the host's own cores inside the
    # wait, so the share says nothing of a chip: only that it is one
    assert 0 < m["host_busy_share"] < 100
    wait = notes["token_wait"]
    assert 0 < wait["ms_p50"] <= wait["ms_p99"] <= wait["ms_max"]
    assert wait["n"] > 3 and notes["host_round"]["rounds"] > 3
    assert 0 < notes["host_round"]["busy_ms_p50"] < \
        notes["host_round"]["period_ms_p50"]
    table = notes["window_spans"]
    assert m["decode_dispatch_ms_p50"] == pytest.approx(
        table["serve/decode_dispatch"]["ms_p50"])
    assert 0 < m["decode_dispatch_ms_p50"] < m["decode_step_ms_p50"]
    # three clients on four rows: every decode round but a cold start's
    # goes out over the one before
    assert 50 < m["rounds_overlapped_share"] <= 100
    assert set(notes["pipeline_drains"]) <= {"idle", "preempt", "replay"}
    # the gaps of the device beside the session, under the new spans
    idle = notes["idle_by_span"]
    assert idle["serve/decode_dispatch"] > 0 and idle["serve/token_wait"] > 0
    late = sum(v for k, v in idle.items() if k not in hr.NOT_LATE)
    t = next(l for l in lines if l["phase"] == "trace")
    assert m["idle_host_late_share"] == pytest.approx(
        100 * late / t["window_s"])
    assert 0 < m["idle_host_late_share"] < m["device_idle_share.serve"]
    # a round's dispatch and wait lie inside its decode step: every step
    # has its dispatch, and what the step holds beside the two is small
    assert table["serve/decode_dispatch"]["n"] == \
        table["serve/decode_step"]["n"]
    rest = notes["host_round"]
    assert -50 < rest["step_rest_us_min"] <= rest["step_rest_us_p50"] < 2000
