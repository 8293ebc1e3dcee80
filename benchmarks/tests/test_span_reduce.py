"""The program's scopes and spans, read back out of a trace
(``harness/span_reduce.py``) and the readers built on it: the scope join on
a hand-written trace whose every number is worked out beside it, the host
plane on the recorded v5e trace, every new reader on a run that lacks what
it reads, and the CPU rehearsal of each new metric at the tiny size."""

import os
import re
import shutil
import types

import pytest

from benchmarks.harness import manifest, span_reduce as sr
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness import tracing

from . import _tiny
from .test_trace_reduce import FIXTURES, _textproto

MICRO = os.path.join(FIXTURES, "micro_v5e.xplane.pb")
NEW_READERS = ("step_forward_share", "step_backward_share",
               "step_update_share", "flash_attention_fwd_roofline",
               "flash_attention_bwd_roofline", "kv_write_share",
               "round_host_ms_p50", "prefill_ms_p50", "decode_batch_fill")

# -- the scope join, by hand --------------------------------------------------
# Two programs on each of two chips, times in us. Both have a ``fusion.1``:
# the module that contains the event decides whose it is.
HLO_A = '''HloModule jit_a, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %inner.1 = f32[4]{0} add(%p, %p), metadata={op_name="jit(a)/apx:amp_grad/jvp(apx:mlp)/add"}
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(a)/apx:amp_grad/jvp(apx:mlp)/dot_general" stack_frame_id=3}
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, metadata={op_name="jit(a)/apx:amp_grad/transpose(jvp(apx:mlp))/dot_general"}
  %copy.3 = f32[4]{0} copy(%fusion.2)
  ROOT %fusion.4 = f32[4]{0} fusion(%copy.3), kind=kLoop, metadata={op_name="jit(a)/apx:amp_optimizer/apx:adam/mul"}
}
'''
# another program of the same name (a second engine, say): none of the
# trace's instruction names but ``fusion.1`` is in it, so it loses
HLO_A_OTHER = '''HloModule jit_a

ENTRY %main.2 (x: f32[4]) -> f32[4] {
  ROOT %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(a)/apx:amp_scaler/select_n"}
}
'''
HLO_B = '''HloModule jit_b, entry_computation_layout={()->f32[4]}

ENTRY %main.3 (x: f32[4]) -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(b)/apx:serve_decode/apx:block_0/apx:kv_write/scatter"}
  %fusion.1.remat_compressed = f32[4]{0} copy(%fusion.1)
  %fusion.1.remat_uncompressed = f32[4]{0} copy(%fusion.1.remat_compressed)
  ROOT %fusion.9 = f32[4]{0} fusion(%fusion.1), kind=kLoop, metadata={op_name="jit(b)/reshape"}
}
'''
CHIP = [
    ("XLA Modules", "jit_a(111)", 0, 50),
    ("XLA Modules", "jit_b(222)", 50, 50),
    ("XLA Ops", "%fusion.1 = f32[4]{0} fusion(f32[4] %x)", 0, 10),    # fwd
    ("XLA Ops", "%fusion.2 = f32[4]{0} fusion(f32[4] %f)", 10, 20),   # bwd
    ("XLA Ops", "%copy.3 = f32[4]{0} copy(f32[4] %f)", 30, 5),  # no op_name
    ("XLA Ops", "%fusion.4 = f32[4]{0} fusion(f32[4] %c)", 35, 5),  # update
    ("XLA Ops", "%fusion.1 = f32[4]{0} fusion(f32[4] %x)", 50, 30),  # jit_b
    ("XLA Ops", "%fusion.9 = f32[4]{0} fusion(f32[4] %f)", 80, 8),
    # XLA's remat clone of jit_b's fusion.1: no metadata in the text, so
    # unattributed; named after its source, so also counted under it apart
    ("XLA Ops", "%fusion.1.remat_uncompressed = f32[4]{0} copy(f32[4] %c)",
     88, 2),
]
# per chip: forward 10, backward 20, unattributed 5 + 2, update 5, other
# 30 + 8; busy 80; under apx:kv_write 30, its clone 2; under apx:amp_grad 30


def test_parse_op_names_skips_what_has_none_and_the_kernel_metadata():
    table = sr.parse_op_names(HLO_A)
    assert table == {
        "inner.1": "jit(a)/apx:amp_grad/jvp(apx:mlp)/add",
        "fusion.1": "jit(a)/apx:amp_grad/jvp(apx:mlp)/dot_general",
        "fusion.2": "jit(a)/apx:amp_grad/transpose(jvp(apx:mlp))/"
                    "dot_general",
        "fusion.4": "jit(a)/apx:amp_optimizer/apx:adam/mul"}
    assert sr.hlo_module_name(HLO_B) == "jit_b"
    assert sr.instruction_name(CHIP[2][1]) == "fusion.1"
    assert [sr.phase_of(table.get(n)) for n in
            ("fusion.1", "fusion.2", "copy.3", "fusion.4")] == \
        ["forward", "backward", "unattributed", "update"]
    assert sr.phase_of("jit(b)/reshape") == "other"


def test_scope_join_by_containing_module(tmp_path):
    path = tmp_path / "scopes.textproto"
    path.write_text(_textproto(chip0=CHIP, chip1=CHIP))
    got = sr.device_scopes(tr.load(str(path)),
                           [HLO_A_OTHER, HLO_A, HLO_B])
    us = 1e-6
    assert got["busy_s"] == pytest.approx(80 * us)
    assert got["phase_s"] == pytest.approx(
        {"forward": 10 * us, "backward": 20 * us, "update": 5 * us,
         "other": 38 * us, "unattributed": 7 * us})
    assert sum(got["phase_s"].values()) == pytest.approx(got["busy_s"])
    assert got["scope_s"]["kv_write"] == pytest.approx(30 * us)
    # the clone, by its source's phase and scopes; copy.3 is no clone
    assert got["remat_clone_s"] == {
        k: pytest.approx(2 * us)
        for k in ("block_0", "kv_write", "other", "serve_decode")}
    assert got["scope_s"]["amp_grad"] == pytest.approx(30 * us)
    assert got["scope_s"]["mlp"] == pytest.approx(30 * us)
    assert got["attributed_share"] == pytest.approx(100 * (1 - 7 / 80))
    assert got["top_unattributed"] == [
        ["copy f32[4]", "unattributed", pytest.approx(5 * us)],
        ["fusion.1.remat_uncompressed f32[4]", "unattributed",
         pytest.approx(2 * us)]]
    assert got["top_ops"][0][:2] == ["fusion f32[4]", "other"]
    # without the programs' text nothing is attributed, and nothing raises
    bare = sr.device_scopes(tr.load(str(path)), [])
    assert bare["phase_s"]["unattributed"] == pytest.approx(80 * us)
    assert bare["attributed_share"] == 0 and bare["remat_clone_s"] == {}
    assert sr.device_scopes(tr.load(str(path)), [HLO_B])["scope_s"] == \
        {"block_0": pytest.approx(30 * us),
         "kv_write": pytest.approx(30 * us),
         "serve_decode": pytest.approx(30 * us)}


# -- the host plane of the recorded trace -------------------------------------
# (test_trace_reduce.py describes it: a matmul under "dispatch", a sleep, the
# matmul twice under a second "dispatch"; the window is whole periods)

def test_dispatch_annotation_split_by_the_runtimes_events(monkeypatch):
    monkeypatch.setattr(sr, "TOP_NESTED", 200)      # every row
    lines = sr.host_lines(tr.load(MICRO))
    split = sr.annotation_split(lines, "dispatch")
    assert split["n"] == 2
    by = {(r["thread"], r["event"]): r for r in split["nested"]}
    pjit = by[("python", "PjitFunction(mm)")]
    assert pjit["calls"] == pytest.approx(3.0)   # 6 events (nested pairs)/2
    assert ("main", "PJRT_LoadedExecutable_Execute") in by
    assert ("main", "Handle inputs") in by
    assert by[("python", "ParseArguments")]["calls"] == pytest.approx(1.5)
    # one thread's rows add up to the annotation: its own self time plus
    # the self times of what is nested in it on that thread
    durs = [e - s for s, e, n, _ in lines["python"] if n == "dispatch"]
    own = sum(r["self_ms"] for r in split["nested"]
              if r["thread"] == "python")
    assert split["self_ms_mean"] + own == pytest.approx(
        1e3 * sum(durs) / 2, rel=1e-6)
    assert split["ms_max"] == pytest.approx(1e3 * max(durs))
    assert sr.annotation_split(lines, "serve-step") is None
    assert sr.program_spans(lines) == []        # recorded before PR 23


def test_idle_gaps_go_to_the_innermost_span():
    """The recorded trace's two gaps (5.9 ms after the first matmul, 84 us
    after the second) under hand-placed spans: a program span inside the
    harness's annotation takes the gap."""
    profile = tr.load(MICRO)
    (gaps,) = sr.device_gaps(profile)
    big, small = sorted(gaps, key=lambda g: g[0] - g[1])[:2]
    assert big[1] - big[0] == pytest.approx(5.936e-3, abs=2e-6)
    assert small[1] - small[0] == pytest.approx(84.4e-6, abs=1e-6)
    ms = 1e-3
    lines = {"python": [
        (45.0 * ms, 52.0 * ms, "serve-step", None),
        (45.1 * ms, 51.2 * ms, "serve/round", 7),
        (51.30 * ms, 51.34 * ms, "serve/gauges", 8),
        (45.0 * ms, 52.0 * ms, "not-a-span", None)]}
    got = sr.idle_by_span([gaps], lines)
    assert got["serve/round"] == pytest.approx(big[1] - big[0])
    assert got["serve/gauges"] == pytest.approx(small[1] - small[0])
    assert "serve-step" not in got and "not-a-span" not in got
    # with the spans gone the harness's own annotation has it all
    alone = sr.idle_by_span([gaps], {"python": lines["python"][:1]})
    assert alone["serve-step"] == pytest.approx(
        big[1] - big[0] + small[1] - small[0])
    assert sum(got.values()) == pytest.approx(sum(alone.values()))


# -- the recorder's side -------------------------------------------------------

EVENTS = [
    {"kind": "span_start", "name": "serve/round", "value": 1,
     "parent": None, "t": 1.0},
    {"kind": "span_start", "name": "serve/prefill", "value": 2,
     "parent": 1, "t": 1.001, "seq_id": 4, "resumed": False},
    {"kind": "span_end", "name": "serve/prefill", "value": 0.05, "span": 2,
     "parent": 1, "t": 1.051},
    {"kind": "gauge", "name": "serve/batch_fill", "value": 0.75, "t": 1.2},
    {"kind": "span_start", "name": "serve/decode_step", "value": 3,
     "parent": 1, "t": 1.06, "n_active": 3},
    {"kind": "span_end", "name": "serve/decode_step", "value": 0.1,
     "span": 3, "parent": 1, "t": 1.16},
    {"kind": "span_end", "name": "serve/round", "value": 0.17, "span": 1,
     "parent": None, "t": 1.17},
    # closed in the window, opened before it: left out
    {"kind": "span_end", "name": "serve/round", "value": 9.0, "span": 0,
     "parent": None, "t": 1.18},
]


def test_closed_spans_round_host_time_and_the_clock_link():
    spans = sr.closed_spans(EVENTS)
    assert [s["id"] for s in spans] == [2, 3, 1]
    assert spans[0]["seq_id"] == 4 and spans[1]["n_active"] == 3
    assert spans[2]["t0"] == pytest.approx(1.0)
    # 170 ms of round - 50 of prefill - 100 of decode step
    assert sr.round_host_s(spans) == [pytest.approx(0.02)]
    assert sr.span_table(spans)["serve/prefill"] == {
        "n": 1, "ms_p50": pytest.approx(50.0), "ms_sum": pytest.approx(50.0)}
    # the plane runs 7 s ahead of the recorder; one span is not on it, one
    # id on it is not the recorder's
    lines = {"python": [(8.0, 8.17, "serve/round", 1),
                        (8.06, 8.1601, "serve/decode_step", 3),
                        (8.5, 8.6, "serve/round", 99),
                        (8.0, 9.0, "serve-step", None)]}
    link = sr.clock_link(lines, spans)
    assert link["joined"] == {"serve/round": 1, "serve/decode_step": 1}
    assert link["duration_diff_us_max"] == pytest.approx(100.0)
    assert sr.clock_link({}, spans) == {"joined": {},
                                        "duration_diff_us_max": None}


# -- readers on a run that lacks what they read --------------------------------

def _reader(name):
    return manifest.load_layer_metric(name)


def _bare_run(workload="cell", **more):
    prog = types.SimpleNamespace(
        attention={"kind": "flash", "kernel": r"^apx_flash_attention",
                   "batch": 1, "heads": 2, "seq": 8, "head_dim": 8,
                   "causal": True, "layers": 1},
        step=None, engine=types.SimpleNamespace(max_batch=4))
    return {"workload": workload, "traced": {"steps": 3},
            "trace": tr.reduce_file(MICRO, tracing.ANNOTATIONS),
            "window_events": [], "notes": {}, "program": prog,
            "peak": _tiny.FAKE_PEAK, **more}


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_reports_nothing_without_its_span(name, tmp_path,
                                                     monkeypatch):
    """A real device trace (the recorded one) of a program that has none of
    this PR's scopes, kernel names or spans: every new reader says None and
    none raises; so it is with no trace at all, and with no session."""
    monkeypatch.setattr(sr, "ROOT", str(tmp_path))
    compute = _reader(name).compute
    assert compute(_bare_run()) is None                 # no file
    assert compute(_bare_run(traced=None)) is None      # no session
    there = tmp_path / ".bench_trace" / "cell" / "plugins"
    there.mkdir(parents=True)
    shutil.copy(MICRO, there / "t.xplane.pb")
    run = _bare_run(window_events=EVENTS)
    assert compute(run) is None
    if "roofline" not in name:      # those read the kernels' names alone
        assert run["notes"]["scope_shares"]["attributed_share"] == 0
        assert run["notes"]["dispatch_split"]["n"] == 2
        assert run["notes"]["span_clock"]["joined"] == {}


# -- the rehearsal: each new metric for its cell, tiny, on the CPU -------------

CELLS = {"tiny-train": (_tiny.TINY_GPT, _tiny.TINY_TRAIN, 1),
         "tiny-serve": (_tiny.TINY_GPT, _tiny.TINY_SERVE, 1)}
KERNEL = ('%{name}.1 = bf16[2,4,32,16]{{3,2,1,0}} custom-call(bf16[2,4,32,16]'
          ' %q), custom_call_target="tpu_custom_call"')


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return _tiny.make_root(tmp_path_factory.mktemp("bench"), CELLS)


def _phase(lines, name):
    return next(l for l in lines if l["phase"] == name)


def test_serve_span_metrics_from_the_cpu_session(copy, monkeypatch):
    """The CPU's profiler session has the host plane: the engine's spans
    are on it with the recorder's ids, and the three span metrics are
    reported (``test_rehearsal.py`` runs the same cell with ``ROOT`` left
    at the checkout, where no trace of it lies: there they are absent)."""
    root, man = copy
    monkeypatch.setattr(sr, "ROOT", root)
    _, res = _tiny.run(root, man, "tiny-serve", trace=True, seconds=1.0)
    m = res["metrics"]
    assert {"round_host_ms_p50", "prefill_ms_p50", "decode_batch_fill"} \
        <= set(m)
    assert "kv_write_share" not in m              # no device plane
    assert 0 < m["decode_batch_fill"]["value"] <= 100
    assert 0 < m["round_host_ms_p50"]["value"]
    assert 0 < m["prefill_ms_p50"]["value"]
    # the same session, read directly: every phase span of the round is on
    # the plane with an id
    path = tr.newest_xplane(os.path.join(root, ".bench_trace", "tiny-serve"))
    names = {n for _, _, n, _ in sr.program_spans(sr.host_lines(
        tr.load(path)))}
    assert names >= {"serve/round", "serve/schedule", "serve/prefill",
                     "serve/decode_inputs", "serve/decode_step",
                     "serve/sample", "serve/gauges"}


def _fake_device_session(monkeypatch, kernels=()):
    """The CPU has no device plane. In its place: one ``XLA Ops`` event of
    1 us for every instruction of the ENTRY computation of each of the
    process's loaded programs named below (so the join runs on the real
    optimized text, with the scopes the real program carries), and one per
    name in ``kernels``, all under one ``XLA Modules`` event a program."""
    def session(logdir):
        rows, t = [], 0
        for text in sr.live_hlo_texts({"jit_step", "jit_decode",
                                       "jit_prefill"}):
            entry = text[text.index("\nENTRY "):]
            names = [m.group(1) for m in map(
                sr._INSTR.match, entry.split("\n}")[0].splitlines()[1:])
                if m]
            start = t
            for n in names + [KERNEL.format(name=k) for k in kernels]:
                ev = n if n.startswith("%") else f"%{n} = f32[] fusion()"
                rows.append(("XLA Ops", ev, t, 1))
                t += 1
            rows.append(("XLA Modules",
                         f"{sr.hlo_module_name(text)}({start})", start,
                         t - start))
            t += 5                                       # an idle gap
        os.makedirs(logdir, exist_ok=True)
        path = os.path.join(logdir, "fake.textproto")
        with open(path, "w") as f:
            f.write(_textproto(chip0=rows, chip1=rows))
        return path

    def reduce(self):
        out = tr.reduce_file(session(self.logdir), tracing.ANNOTATIONS)
        out["xplane_bytes"] = 0
        return out
    monkeypatch.setattr(tracing.Tracer, "reduce", reduce)
    monkeypatch.setattr(
        sr, "trace_path", lambda run: os.path.join(
            sr.ROOT, ".bench_trace", run["workload"], "fake.textproto")
        if run.get("traced") else None)


def test_train_scope_metrics_at_the_tiny_size(copy, monkeypatch):
    root, man = copy
    monkeypatch.setattr(sr, "ROOT", root)
    _fake_device_session(monkeypatch, ("apx_flash_attention_fwd",
                                       "apx_flash_attention_bwd"))
    lines, res = _tiny.run(root, man, "tiny-train", trace=True, seconds=0.5)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW_READERS[:5]) <= set(m)
    shares = _phase(lines, "trace")["notes"]["scope_shares"]
    assert m["step_forward_share"] == pytest.approx(shares["forward"])
    assert m["step_backward_share"] > 0 and m["step_update_share"] > 0
    assert sum(shares[p] for p in sr.PHASES) == pytest.approx(100.0)
    # every instruction of the step that carries an op_name lies under one
    # of the step's scopes; the two fake kernels carry none
    assert 0 < shares["unattributed"] < 50
    assert m["flash_attention_fwd_roofline"] > 0
    assert m["flash_attention_bwd_roofline"] > 0
    # the old reader still sums both directions
    assert 1 / m["flash_attention_roofline"] == pytest.approx(
        (1 / m["flash_attention_fwd_roofline"]
         + 2 / m["flash_attention_bwd_roofline"]) / 3, rel=0.35)


def test_serve_kv_write_share_at_the_tiny_size(copy, monkeypatch):
    root, man = copy
    monkeypatch.setattr(sr, "ROOT", root)
    _fake_device_session(monkeypatch)
    lines, res = _tiny.run(root, man, "tiny-serve", trace=True, seconds=1.0)
    assert 0 < res["metrics"]["kv_write_share"]["value"] < 100
    notes = _phase(lines, "trace")["notes"]
    assert notes["scope_shares"]["other"] > 50       # no amp_grad in serving
    assert "remat_clones" in notes["scope_shares"]
    # the window's spans by name: every round has each of its phases once
    table = notes["window_spans"]
    assert table["serve/round"]["n"] >= 1
    assert all(table[f"serve/{k}"]["n"] == table["serve/round"]["n"]
               for k in ("schedule", "gauges"))
    assert table["serve/gauges"]["ms_sum"] < table["serve/round"]["ms_sum"]
    assert "step_forward_share" not in res["metrics"]
    assert re.fullmatch(r"[\w/-]+", next(iter(notes["idle_by_span"])))
