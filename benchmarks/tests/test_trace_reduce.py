"""The reduction from a trace to numbers, on a recorded trace and on a
hand-written one whose every number is worked out beside it."""

import os

import pytest

from benchmarks.harness import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")

# -- the recorded fixture -----------------------------------------------------
# fixtures/micro_v5e.xplane.pb was recorded on one "TPU v5 lite" (PR 22): a
# jitted 2048^3 bf16 matmul under TraceAnnotation("dispatch"), a 5 ms
# time.sleep under TraceAnnotation("host-sleep"), then the matmul twice under
# "dispatch". Its nine "XLA Ops" events, read off a dump of the file (ns):
#   copy-start 45149676+13   copy-done 45149690+3      fusion 45149693+90923
#   copy-start 51176771+13   copy-done 51176786+11506  fusion 51188293+90891
#   copy-start 51363555+13   copy-done 51363570+11456  fusion 51375026+90891
# and its three "XLA Modules" events, all "jit_mm(1626086940119212895)":
#   45149672+90946   51176768+102417   51363552+102365
# The window is whole periods: first program's start to the last one's
# start, so it holds the first two executions and the gap after each.
MICRO_WINDOW_NS = 51363552 - 45149672                        # 6,213,880
MICRO_BUSY_NS = (13 + 3 + 90923) + (13 + 11506 + 90891)      # 193,349
MICRO_OPS_BUSY_NS = MICRO_BUSY_NS + (13 + 11456 + 90891)     # 295,709
MICRO_FUSION_NS = 90923 + 90891 + 90891


def test_recorded_v5e_trace_busy_idle_and_ops():
    s = tr.reduce_file(os.path.join(FIXTURES, "micro_v5e.xplane.pb"),
                       ("dispatch", "host-sleep"))
    assert len(s["devices"]) == 1 and s["n_device_ops"] == 9
    assert s["whole_periods"] and s["devices"][0]["programs"] == 3
    assert s["window_s"] == pytest.approx(MICRO_WINDOW_NS * 1e-9, abs=2e-9)
    assert s["busy_s"] == pytest.approx(MICRO_BUSY_NS * 1e-9, abs=1e-8)
    # sums by operation are over every traced op, the third matmul too
    assert s["ops_busy_s"] == pytest.approx(MICRO_OPS_BUSY_NS * 1e-9,
                                            abs=1e-8)
    assert tr.module_seconds(s, r"^jit_mm$") == pytest.approx(
        [90946e-9, 102417e-9, 102365e-9], abs=2e-9)
    assert tr.module_seconds(s, r"^jit_prefill$") == []
    assert s["idle_s"] == pytest.approx(
        (MICRO_WINDOW_NS - MICRO_BUSY_NS) * 1e-9, abs=1e-8)
    assert s["op_s"]["fusion bf16[2048,2048]"] == pytest.approx(
        MICRO_FUSION_NS * 1e-9, abs=1e-8)
    assert s["pallas_s"] == 0 and s["collective_s"] == 0
    assert s["host_annotations_found"] == 3
    # the 5.9 ms gap between the first and the second matmul falls under
    # the host's sleep; so does the 84 us one between the second and third,
    # by the 1-2 ms by which device and host clocks disagree (module doc)
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert gaps["host-sleep"] == pytest.approx(
        (MICRO_WINDOW_NS - MICRO_BUSY_NS) * 1e-9, rel=1e-3)
    assert s["breakdown"]["device_ops"][0][0] == "fusion bf16[2048,2048]"


# -- the hand-written fixture ---------------------------------------------------
# Two chips over a 100 us window, times in us. (line, name, start, duration)
PALLAS = ('%apx_flash_attention.3 = bf16[1,2,8,8]{3,2,1,0} custom-call('
          'bf16[1,2,8,8] %q), custom_call_target="tpu_custom_call"')
CHIP0 = [
    ("XLA Ops", "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8] %a)", 0, 10),
    ("XLA Ops", PALLAS, 10, 20),
    # a while loop that encloses two body ops: self time 30 - 10 - 10 = 10
    ("XLA Ops", "%while.2 = (s32[]) while((s32[]) %t), body=%b", 40, 30),
    ("XLA Ops", "%fusion.7 = f32[4]{0} fusion(f32[4] %x)", 45, 10),
    ("XLA Ops", "%fusion.7 = f32[4]{0} fusion(f32[4] %x)", 58, 10),
    # a synchronous all-reduce: nothing else runs, all of it is exposed
    ("XLA Ops", "%all-reduce.5 = f32[4]{0} all-reduce(f32[4] %g)", 80, 10),
    # an async all-gather in flight 15..35: the kernel covers 15..30, so
    # 5 us of it (30..35) are exposed
    ("XLA Ops", "%all-gather-start.1 = f32[8] all-gather-start(f32[4] %p)",
     15, 0),
    ("Async XLA Ops",
     "%all-gather-start.1 = f32[8] all-gather-start(f32[4] %p)", 15, 20),
    ("XLA Ops", "%fusion.9 = f32[4]{0} fusion(f32[4] %y)", 95, 5),
]
CHIP1 = [
    ("XLA Ops", "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8] %a)", 0, 50),
    ("XLA Ops", "%all-reduce.5 = f32[4]{0} all-reduce(f32[4] %g)", 50, 30),
]
HOST = [("main", "dispatch", 0, 40), ("main", "fetch-loss", 70, 30)]
# chip 0: busy = [0,30] + [40,70] + [80,90] + [95,100] = 75, idle 25
#         gaps: 30-40 (mid 35: dispatch), 70-80 (mid 75: fetch-loss),
#               90-95 (mid 92.5: fetch-loss)
#         pallas 20; collectives: union([80,90], [15,35], [15,15]) = 30,
#         exposed = 10 + 5 = 15
# chip 1: busy 80, idle 20 (gap 80-100, mid 90: fetch-loss);
#         collective 30, all exposed
# means: busy 77.5, idle 22.5, pallas 10, collective 30, exposed 22.5


def _textproto(chip0=None, chip1=None, host=None):
    def plane(pid, name, rows):
        meta, lines = {}, {}
        for line, ev, start, dur in rows:
            mid = meta.setdefault(ev, len(meta) + 1)
            lines.setdefault(line, []).append(
                f"events {{ metadata_id: {mid} offset_ps: {start * 10**6} "
                f"duration_ps: {dur * 10**6} }}")
        out = [f'planes {{ id: {pid} name: "{name}"']
        for ev, mid in meta.items():
            quoted = ev.replace("\\", "\\\\").replace('"', '\\"')
            out.append(f'event_metadata {{ key: {mid} value {{ id: {mid} '
                       f'name: "{quoted}" }} }}')
        for i, (line, evs) in enumerate(lines.items()):
            out.append(f'lines {{ id: {i + 1} name: "{line}" '
                       f'timestamp_ns: 1000 {" ".join(evs)} }}')
        out.append("}")
        return "\n".join(out)
    return "\n".join([plane(1, "/device:TPU:0", chip0 or CHIP0),
                      plane(2, "/device:TPU:1", chip1 or CHIP1),
                      plane(3, "/host:CPU", host or HOST)])


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "two_chips.textproto"
    path.write_text(_textproto())
    return tr.reduce_file(str(path), ("dispatch", "fetch-loss"),
                          min_gap_s=1e-6)


def test_hand_written_busy_idle(summary):
    us = 1e-6
    # no "XLA Modules" line: the window falls back to first op .. last op
    assert summary["whole_periods"] is False
    assert summary["window_s"] == pytest.approx(100 * us)
    d0, d1 = summary["devices"]
    assert d0["busy_s"] == pytest.approx(75 * us)
    assert d0["idle_s"] == pytest.approx(25 * us)
    assert d1["busy_s"] == pytest.approx(80 * us)
    assert summary["busy_s"] == pytest.approx(77.5 * us)
    assert d0["longest_gap_s"] == pytest.approx(10 * us)


def test_hand_written_kernel_and_self_time(summary):
    us = 1e-6
    assert summary["devices"][0]["pallas_s"] == pytest.approx(20 * us)
    assert summary["pallas_s"] == pytest.approx(10 * us)
    assert summary["kernel_s"] == {"apx_flash_attention":
                                   pytest.approx(10 * us)}
    assert tr.kernel_seconds(summary, r"^apx_flash") == \
        pytest.approx(10 * us)
    assert tr.kernel_seconds(summary, r"^apx_paged") is None
    # the while's self time excludes its body; summed over chips, / 2
    assert summary["op_s"]["while s32[]"] == pytest.approx(5 * us)
    assert summary["op_s"]["fusion f32[4]"] == pytest.approx(12.5 * us)


def test_hand_written_collectives_exposed(summary):
    us = 1e-6
    d0, d1 = summary["devices"]
    assert d0["collective_s"] == pytest.approx(30 * us)
    assert d0["collective_exposed_s"] == pytest.approx(15 * us)
    assert d1["collective_s"] == pytest.approx(30 * us)
    assert d1["collective_exposed_s"] == pytest.approx(30 * us)
    assert summary["collective_exposed_s"] == pytest.approx(22.5 * us)


def test_hand_written_idle_gaps_by_annotation(summary):
    us = 1e-6
    gaps = dict(summary["breakdown"]["idle_gaps"])
    # chip 0: dispatch 10, fetch-loss 15; chip 1: fetch-loss 20; means
    assert gaps["dispatch"] == pytest.approx(5 * us)
    assert gaps["fetch-loss"] == pytest.approx(17.5 * us)
    assert len(summary["breakdown"]["device_ops"]) <= 10


# -- whole periods -----------------------------------------------------------
# A loop that awaits every step, as cell 4 does: three executions of one
# program, 30 us of device work and a 20 us wait for the host each. Chip 1
# starts each 2 us later. First op to last op would read 130 us holding
# three programs and TWO gaps (idle 30.8%); whole periods read 100 us with
# two programs and two gaps: idle 40%, which is 20 / 50.
STEP = "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8] %a)"
AR = "%all-reduce.5 = f32[4]{0} all-reduce(f32[4] %g)"


def _awaited(shift):
    rows = []
    for k in range(3):
        t = 50 * k + shift
        rows += [("XLA Modules", "jit_step(123)", t, 30),
                 ("XLA Ops", STEP, t, 24), ("XLA Ops", AR, t + 24, 6)]
    return rows


def test_idle_share_over_whole_periods(tmp_path):
    us = 1e-6
    path = tmp_path / "awaited.textproto"
    path.write_text(_textproto(
        _awaited(0), _awaited(2),
        [("main", "dispatch", 30 + 50 * k, 20) for k in range(3)]))
    s = tr.reduce_file(str(path), ("dispatch",), min_gap_s=1e-6)
    assert s["whole_periods"] is True
    for d in s["devices"]:
        assert d["programs"] == 3
        assert d["window_s"] == pytest.approx(100 * us)
        assert d["busy_s"] == pytest.approx(60 * us)
        assert d["idle_s"] == pytest.approx(40 * us)
        # collectives inside the window: two of the three all-reduces
        assert d["collective_s"] == pytest.approx(12 * us)
        assert d["collective_exposed_s"] == pytest.approx(12 * us)
        assert d["ops_busy_s"] == pytest.approx(90 * us)
    assert s["window_s"] == pytest.approx(100 * us)
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(0.40)
    assert dict(s["breakdown"]["idle_gaps"])["dispatch"] == \
        pytest.approx(40 * us)
    # by operation: all three steps on both chips, mean over the chips
    assert s["op_s"]["fusion bf16[8,8]"] == pytest.approx(72 * us)
    assert sorted(tr.module_seconds(s, "^jit_step$")) == \
        pytest.approx([30 * us] * 6)


def test_readers_of_programs_and_tagged_entries(tmp_path):
    """``prefill_device_ms_p50`` takes the median device time of the
    program the family names; a tagged entry finds its reader by the part
    of its name before the dot."""
    import types
    from benchmarks.harness import manifest
    path = tmp_path / "awaited.textproto"
    path.write_text(_textproto(_awaited(0), _awaited(2)))
    trace = tr.reduce_file(str(path))
    run = {"trace": trace,
           "program": types.SimpleNamespace(programs={"prefill":
                                                      r"^jit_step$"})}
    reader = manifest.load_layer_metric("prefill_device_ms_p50")
    assert reader.compute(run) == pytest.approx(0.030)       # 30 us
    run["program"].programs = {"prefill": r"^jit_prefill$"}
    assert reader.compute(run) is None
    idle = manifest.load_layer_metric("device_idle_share.4chip")
    assert idle is not None and idle.compute(run) == pytest.approx(40.0)
    assert manifest.load_layer_metric("collective_exposed_share").compute(
        run) == pytest.approx(12.0)
    assert idle.compute({"trace": None}) is None


def test_interval_arithmetic():
    assert tr.merge([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert tr.measure([(0, 1), (0.5, 2), (5, 6)]) == pytest.approx(3)
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == \
        [(0, 2), (3, 5), (7, 9)]
    assert tr.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert tr.subtract([(0, 2)], []) == [(0, 2)]
    assert tr.clip([(0, 2), (3, 5), (6, 9)], 1, 7) == [(1, 2), (3, 5), (6, 7)]


def test_names():
    n = "%all-gather-start.12 = (f32[4]{0}, f32[8]{0}) all-gather-start(...)"
    assert tr.base_name(n) == "all-gather-start" and tr.is_collective(n)
    assert tr.op_label(n) == "all-gather-start f32[4]"
    assert not tr.is_collective("%fusion.3 = f32[4] fusion(%all-reduce.1)")
    # as a four-chip v5e trace names the SP reduce-scatter (PR 22)
    assert tr.is_collective("%reduce_scatter.7 = bf16[8,256,1280]{2,1,0} "
                            "fusion(bf16[8,1024,1280] %x), kind=kCustom")
    assert tr.is_pallas(PALLAS) and tr.base_name(PALLAS) == \
        "apx_flash_attention"


def test_no_device_plane_gives_nothing(tmp_path):
    p = tmp_path / "host_only.textproto"
    p.write_text('planes { id: 1 name: "/host:CPU" }')
    s = tr.reduce_file(str(p))
    assert s["devices"] == [] and s["busy_s"] == 0.0
