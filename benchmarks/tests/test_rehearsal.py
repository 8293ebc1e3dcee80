"""CPU rehearsals of ``run.py:run_cell`` at tiny sizes: the same code path
as on the chip, from a temporary copy of the benchmark's data to which the
tiny cells, one configuration file each, one traffic file each and one
layer-metric file were ADDED. No harness file is edited or copied: adding a
cell needs data only. What a rehearsal prints is never a device number."""

import os

import pytest

from . import _tiny

CELLS = {
    "tiny-train": (_tiny.TINY_GPT, _tiny.TINY_TRAIN, 1),
    "tiny-mlm": (_tiny.TINY_BERT, _tiny.TINY_MLM, 1),
    "tiny-4dev": (_tiny.TINY_GPT, _tiny.TINY_4DEV, 4),
    "tiny-2x2": (_tiny.TINY_GPT, _tiny.TINY_2X2, 4),
    "tiny-serve": (_tiny.TINY_GPT, _tiny.TINY_SERVE, 1),
    "tiny-open": (_tiny.TINY_GPT, _tiny.TINY_OPEN, 1),
    "tiny-one": (_tiny.TINY_GPT, _tiny.TINY_ONE, 1),
}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return _tiny.make_root(tmp_path_factory.mktemp("bench"), CELLS)


def _phase(lines, name):
    return next(l for l in lines if l["phase"] == name)


def test_the_copy_holds_data_only(copy):
    root, _ = copy
    assert sorted(os.listdir(root)) == ["BENCHMARK.json", "benchmarks"]
    assert sorted(os.listdir(os.path.join(root, "benchmarks"))) == \
        ["configs", "layer_metrics", "traffic"]


@pytest.mark.parametrize("cell,chips", [("tiny-train", 1), ("tiny-mlm", 1),
                                        ("tiny-4dev", 4), ("tiny-2x2", 4)])
def test_train_cells(copy, cell, chips):
    root, man = copy
    lines, res = _tiny.run(root, man, cell, chips=chips, seconds=0.5)
    assert all(l["platform"] == "cpu" and "device_kind" in l for l in lines)
    assert res["correct"] is True and res["failed"] == 0
    rate = "train4_tokens_per_s" if chips == 4 else "train_tokens_per_s"
    assert set(res["metrics"]) == {rate, "setup_s"}
    assert set(res) == {"correct", "attempted", "failed", "metrics",
                        "device"}
    w = _phase(lines, "train-window")
    assert w["steps"] == res["attempted"] and all(w["verdict"].values())
    fetch_every = CELLS[cell][1]["fetch_every"]
    assert w["steps"] == w["groups"] * fetch_every
    if cell == "tiny-mlm":      # a lead-in group before, one in flight after
        assert w["groups_in_flight"] == 2
        assert w["steps_dispatched"] == w["steps"] + 2 * fetch_every
        assert res["metrics"][rate]["value"] == w["rates"]["median_group"]
    else:
        assert w["steps_dispatched"] == w["steps"]
        assert res["metrics"][rate]["value"] == w["rates"]["window"]
    assert _phase(lines, "reference-check")["ok"]
    assert _phase(lines, "window")["compiles_in_window"] == 0
    if chips == 4:      # cell 4's layout (tp=4 + SP), and dp=2 x tp=2
        lay = _phase(lines, "built")["layout"]
        assert (lay["dp"], lay["tp"]) == ((1, 4) if cell == "tiny-4dev"
                                          else (2, 2))
        assert lay["sequence_parallel"] is True


def test_traced_train_cell_reports_layer_metrics_and_the_added_one(copy):
    root, man = copy
    lines, res = _tiny.run(root, man, "tiny-train", trace=True, seconds=0.5)
    # the CPU has no device plane: the trace-derived readers return nothing
    # and are left out; counters, spans and the compiled program still give
    # theirs, and so does the reader the copy added
    assert set(res["metrics"]) == {"compiles_in_window", "train_mfu",
                                   "step_hbm_gb", "tune_hit_share",
                                   "spans_in_window"}
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert "train_tokens_per_s" not in res["metrics"]
    assert res["correct"] is False           # no device trace: says so
    assert "error" in _phase(lines, "trace")


def test_traced_cell_with_a_device_trace(copy, monkeypatch):
    """The CPU's profiler session has no device plane, so the recorded v5e
    trace stands in for it: the result then carries the device's busy and
    window seconds, the breakdown, and the shares the readers take from the
    trace (no kernel of the cell is in that trace: no roofline)."""
    from benchmarks.harness import trace_reduce, tracing
    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "micro_v5e.xplane.pb")

    def reduce(self):
        out = trace_reduce.reduce_file(fixture, tracing.ANNOTATIONS)
        out["xplane_bytes"] = os.path.getsize(fixture)
        return out
    monkeypatch.setattr(tracing.Tracer, "reduce", reduce)
    root, man = copy
    lines, res = _tiny.run(root, man, "tiny-train", trace=True, seconds=0.5)
    assert res["correct"] is True
    assert set(res["metrics"]) == {
        "compiles_in_window", "train_mfu", "step_hbm_gb", "tune_hit_share",
        "pallas_time_share.train", "device_idle_share.train",
        "spans_in_window"}
    dev = res["device"]
    assert 0 < dev["busy_s"] < dev["window_s"]
    assert res["metrics"]["device_idle_share.train"]["value"] == \
        pytest.approx(100 * (1 - dev["busy_s"] / dev["window_s"]))
    assert res["metrics"]["pallas_time_share.train"]["value"] == 0
    assert res["breakdown"]["device_ops"][0][0] == "fusion bf16[2048,2048]"
    assert len(res["breakdown"]["idle_gaps"]) <= 10
    t = _phase(lines, "trace")
    assert t["whole_periods"] is True and "jit_mm" in t["module_s"]
    # the serve cell's readers on the same trace: no prefill program in it
    _, res = _tiny.run(root, man, "tiny-serve", trace=True, seconds=1.0)
    assert res["correct"] is True
    assert {"device_idle_share.serve", "pallas_time_share.serve",
            "decode_step_ms_p50"} <= set(res["metrics"])
    assert "prefill_device_ms_p50" not in res["metrics"]
    assert "paged_decode_attention_roofline" not in res["metrics"]


def test_serve_cell_closed_loop(copy):
    root, man = copy
    lines, res = _tiny.run(root, man, "tiny-serve", seconds=1.0)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    w = _phase(lines, "serve-window")
    assert w["short"] == 0 and w["refused"] == 0 and w["preemptions"] == 0
    assert w["in_flight_at_end"] == 3            # the loop stays closed
    assert w["requests_issued_in_window"] == res["attempted"]
    assert w["ttft_ms"]["n"] >= 10 and w["itl_ms"]["n"] >= 10
    wave = _phase(lines, "first-wave")
    assert wave["requests"] == 3 and wave["ended_in_setup"] == 0
    assert _phase(lines, "reference-check")["ok"]


def test_closed_loop_keeps_a_client_whose_request_ends_in_set_up(copy):
    root, man = copy
    lines, res = _tiny.run(root, man, "tiny-one", seconds=0.5)
    assert _phase(lines, "first-wave")["ended_in_setup"] == 3
    w = _phase(lines, "serve-window")
    assert w["in_flight_at_end"] == 3 and w["completed"] >= 3
    assert res["correct"] is True and res["failed"] == 0


def test_traced_serve_cell_reads_the_engine_and_the_added_reader(copy):
    root, man = copy
    _, res = _tiny.run(root, man, "tiny-serve", trace=True, seconds=1.0)
    # no device plane on the CPU: prefill_device_ms_p50 and the shares of
    # the trace are left out
    assert set(res["metrics"]) == {"compiles_in_window",
                                   "decode_step_ms_p50", "spans_in_window"}
    # at least the engine's serve/decode_step span of every round
    assert res["metrics"]["spans_in_window"]["value"] >= 10


def test_traced_four_device_cell_reads_its_tagged_entries(copy):
    root, man = copy
    _, res = _tiny.run(root, man, "tiny-4dev", trace=True, chips=4,
                       seconds=0.5)
    # ``train_mfu.4chip`` is an entry of its own (it moves
    # train4_tokens_per_s) read by layer_metrics/train_mfu.py
    assert set(res["metrics"]) == {"compiles_in_window", "train_mfu.4chip",
                                   "step_hbm_gb.4chip",
                                   "tune_hit_share.4chip", "spans_in_window"}
    assert res["metrics"]["train_mfu.4chip"]["value"] > 0


def test_serve_cell_open_loop_reports_lateness(copy):
    root, man = copy
    lines, res = _tiny.run(root, man, "tiny-open", seconds=1.0)
    w = _phase(lines, "serve-window")
    assert w["generator_lateness_ms"]["n"] == w["requests_issued_in_window"]
    assert 5 <= w["requests_issued_in_window"] <= 60      # ~20 a second
    assert res["failed"] == 0


def test_same_seed_same_work(copy):
    root, man = copy
    a, _ = _tiny.run(root, man, "tiny-train", seed=3, seconds=0.2)
    b, _ = _tiny.run(root, man, "tiny-train", seed=3, seconds=0.2)
    c, _ = _tiny.run(root, man, "tiny-train", seed=4, seconds=0.2)
    la, lb, lc = (_phase(x, "warm-up")["losses"] for x in (a, b, c))
    assert la == lb and la != lc


def test_missing_files_fail_loudly(copy):
    from benchmarks.harness import manifest
    root, man = copy
    with pytest.raises(manifest.ManifestError, match="traffic"):
        manifest.load_traffic("no-such-mix",
                              os.path.join(root, "benchmarks"))
    with pytest.raises(manifest.ManifestError, match="layer_metrics"):
        manifest.load_layer_metric("no_such_metric",
                                   os.path.join(root, "benchmarks"))
    with pytest.raises(manifest.ManifestError, match="families"):
        manifest.load_family("no_such_family")
    with pytest.raises(manifest.ManifestError, match="workload"):
        manifest.find_workload(man, "no-such-cell")
