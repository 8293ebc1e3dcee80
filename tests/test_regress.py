"""Bench-trajectory regression detection (``apex_tpu.monitor.regress``).

Round-loading robustness matrix (killed rc=124 round, corrupt JSON,
missing file, evidence streams, unit mismatch), legacy unit inference
over the r01-r05 driver rounds (``tests/fixtures/regress/``: each
round's ``n``/``rc`` and the ``parsed`` keys the loader reads, without
the captured stderr — the case the module exists for: r05 must load as
``no-evidence`` and r01 must be ``incomparable`` with r02+ instead of a
fake 50x regression), and MAD-band verdict arithmetic on synthetic
trajectories.
"""

import json
import os

import pytest

from apex_tpu.monitor import regress

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the r01-r05 driver wrappers, cut to what load_round reads
ROUNDS = [os.path.join(REPO, "tests", "fixtures", "regress",
                       f"round_r0{i}.json") for i in range(1, 6)]


def _mk_round(tmp_path, name, metrics, units=None, schema=2):
    data = dict(metrics)
    data["schema"] = schema
    data["units"] = units or {k: regress.suffix_unit(k) for k in metrics}
    p = tmp_path / name
    p.write_text(json.dumps(data))
    return str(p)


# ---------------------------------------------------------------------------
# loader robustness
# ---------------------------------------------------------------------------

def test_rc124_round_is_no_evidence():
    r = regress.load_round(ROUNDS[4])          # the real r05
    assert r["status"] == regress.NO_EVIDENCE
    assert "rc=124" in r["reason"]
    assert r["metrics"] == {}


def test_rc0_with_null_parsed_is_no_evidence(tmp_path):
    p = tmp_path / "r.json"
    p.write_text(json.dumps({"n": 9, "rc": 0, "parsed": None}))
    r = regress.load_round(str(p))
    assert r["status"] == regress.NO_EVIDENCE
    assert "parsed: null" in r["reason"]


def test_corrupt_json_is_no_evidence(tmp_path):
    p = tmp_path / "corrupt.json"
    p.write_text('{"n": 3, "rc": 0, "parsed": {"value": 1.0')
    r = regress.load_round(str(p))
    assert r["status"] == regress.NO_EVIDENCE
    assert "corrupt" in r["reason"]


def test_missing_file_is_no_evidence(tmp_path):
    r = regress.load_round(str(tmp_path / "nope.json"))
    assert r["status"] == regress.NO_EVIDENCE
    assert "unreadable" in r["reason"]


def test_stream_round_loads_sections_and_schema(tmp_path):
    p = tmp_path / "stream.jsonl"
    lines = [
        {"kind": "header", "name": "bench"},
        {"kind": "started", "name": "bench", "value": 2},
        {"kind": "section", "name": "core",
         "data": {"value": 100.0, "o2_step_ms": 9.0},
         "units": {"value": "imgs/sec/chip", "o2_step_ms": "ms"},
         "schema": 2},
        {"kind": "section", "name": "gpt",
         "data": {"gpt_tokens_per_sec": 5e4},
         "units": {"gpt_tokens_per_sec":
                   "tokens/sec (aggregate over 1 chip)"}, "schema": 2},
        "this line is garbage and must be skipped",
    ]
    p.write_text("\n".join(
        ln if isinstance(ln, str) else json.dumps(ln) for ln in lines))
    r = regress.load_round(str(p))
    assert r["status"] == "ok"
    assert r["schema"] == 2
    assert r["metrics"]["gpt_tokens_per_sec"] == 5e4
    assert r["units"]["value"] == "imgs/sec/chip"
    assert "aggregate" in r["units"]["gpt_tokens_per_sec"]


def test_stream_without_sections_is_no_evidence(tmp_path):
    p = tmp_path / "stream.jsonl"
    p.write_text(json.dumps({"kind": "header", "name": "bench"}) + "\n")
    r = regress.load_round(str(p))
    assert r["status"] == regress.NO_EVIDENCE


# ---------------------------------------------------------------------------
# legacy unit inference on the real rounds
# ---------------------------------------------------------------------------

def test_real_rounds_load_with_documented_schemas():
    rounds = regress.load_rounds(ROUNDS)
    statuses = [r["status"] for r in rounds]
    assert statuses == ["ok", "ok", "ok", "ok", regress.NO_EVIDENCE]
    assert [r["schema"] for r in rounds[:4]] == [0, 1, 1, 1]
    # the r01 dispatch-methodology override: every r01 unit is marked
    assert all("dispatch" in u for u in rounds[0]["units"].values())
    # r02+ honor the declared headline unit
    assert rounds[1]["units"]["value"] == "imgs/sec/chip"


def test_real_rounds_verdicts_r05_hole_and_r01_unit_drift():
    rounds = regress.load_rounds(ROUNDS)
    rep = regress.compare(rounds)
    assert rep["candidate"] == "r04"           # r05 carried no evidence
    by = {r["round"]: r for r in rep["rounds"]}
    assert by["r05"]["status"] == regress.NO_EVIDENCE
    # the headline: r01 is incomparable (unit change), NOT a regression
    head = rep["metrics"]["value"]
    assert any(i["round"] == "r01" for i in head.get("incomparable", []))
    assert head["verdict"] != "regression"
    # and the 53x r01->r02 "drop" produced no regression anywhere
    assert rep["regressions"] == []
    assert rep["exit_code"] == 0


# ---------------------------------------------------------------------------
# verdict arithmetic on synthetic trajectories
# ---------------------------------------------------------------------------

def _trajectory(tmp_path, values, name="gpt_tokens_per_sec", units=None):
    return [_mk_round(tmp_path, f"t{i:02d}.json", {name: v}, units=units)
            for i, v in enumerate(values)]


def test_mad_band_confirmed_regression_exits_nonzero(tmp_path):
    paths = _trajectory(tmp_path, [100.0, 101.0, 99.5, 100.5, 70.0])
    rep = regress.compare(regress.load_rounds(paths))
    row = rep["metrics"]["gpt_tokens_per_sec"]
    assert row["verdict"] == "regression"
    assert rep["exit_code"] == 1
    assert rep["regressions"] == ["gpt_tokens_per_sec"]


def test_mad_band_noise_within_band_is_ok(tmp_path):
    # ±1% wiggle sits inside the 5% relative floor
    paths = _trajectory(tmp_path, [100.0, 101.0, 99.0, 100.5, 99.2])
    rep = regress.compare(regress.load_rounds(paths))
    assert rep["metrics"]["gpt_tokens_per_sec"]["verdict"] == "ok"
    assert rep["exit_code"] == 0


def test_mad_band_improvement(tmp_path):
    paths = _trajectory(tmp_path, [100.0, 101.0, 99.5, 100.5, 140.0])
    rep = regress.compare(regress.load_rounds(paths))
    assert rep["metrics"]["gpt_tokens_per_sec"]["verdict"] == "improvement"
    assert rep["exit_code"] == 0


def test_lower_is_better_direction(tmp_path):
    paths = _trajectory(tmp_path, [10.0, 10.1, 9.9, 10.0, 14.0],
                        name="o2_step_ms")
    rep = regress.compare(regress.load_rounds(paths))
    assert rep["metrics"]["o2_step_ms"]["verdict"] == "regression"
    paths = _trajectory(tmp_path, [10.0, 10.1, 9.9, 10.0, 7.0],
                        name="o2_step_ms")
    rep = regress.compare(regress.load_rounds(paths))
    assert rep["metrics"]["o2_step_ms"]["verdict"] == "improvement"


def test_min_history_guards_the_gate(tmp_path):
    # a 50% drop with only two comparable priors must NOT gate: two
    # points cannot define a noise band
    paths = _trajectory(tmp_path, [100.0, 101.0, 50.0])
    rep = regress.compare(regress.load_rounds(paths))
    row = rep["metrics"]["gpt_tokens_per_sec"]
    assert row["verdict"] == "insufficient-history"
    assert rep["exit_code"] == 0
    # ... unless the caller lowers the bar explicitly
    rep = regress.compare(regress.load_rounds(paths), min_history=2)
    assert rep["metrics"]["gpt_tokens_per_sec"]["verdict"] == "regression"


def test_unit_mismatch_rounds_are_incomparable_not_compared(tmp_path):
    per_chip = {"gpt_tokens_per_sec": "tokens/sec/chip"}
    aggregate = {"gpt_tokens_per_sec": "tokens/sec (aggregate)"}
    paths = [
        _mk_round(tmp_path, "a.json", {"gpt_tokens_per_sec": 800.0},
                  units=aggregate),
        _mk_round(tmp_path, "b.json", {"gpt_tokens_per_sec": 100.0},
                  units=per_chip),
        _mk_round(tmp_path, "c.json", {"gpt_tokens_per_sec": 101.0},
                  units=per_chip),
        _mk_round(tmp_path, "d.json", {"gpt_tokens_per_sec": 99.0},
                  units=per_chip),
        _mk_round(tmp_path, "e.json", {"gpt_tokens_per_sec": 100.5},
                  units=per_chip),
    ]
    rep = regress.compare(regress.load_rounds(paths))
    row = rep["metrics"]["gpt_tokens_per_sec"]
    assert [i["round"] for i in row["incomparable"]] == ["a.json"]
    # the 8x "drop" from the aggregate round never entered the band
    assert row["verdict"] == "ok"
    assert rep["exit_code"] == 0


def test_no_evidence_round_mid_trajectory_is_skipped(tmp_path):
    paths = _trajectory(tmp_path, [100.0, 101.0, 99.5, 100.0])
    hole = tmp_path / "hole.json"
    hole.write_text(json.dumps({"n": 42, "rc": 124, "tail": "",
                                "parsed": None}))
    paths.insert(2, str(hole))
    rep = regress.compare(regress.load_rounds(paths))
    assert rep["metrics"]["gpt_tokens_per_sec"]["verdict"] == "ok"
    by = {r["round"]: r for r in rep["rounds"]}
    assert by["r42"]["status"] == regress.NO_EVIDENCE


def test_against_baseline_extends_history(tmp_path):
    paths = _trajectory(tmp_path, [100.0, 101.0, 60.0])
    base = _mk_round(tmp_path, "base.json", {"gpt_tokens_per_sec": 99.5})
    rep = regress.compare(regress.load_rounds(paths),
                          against=regress.load_round(base))
    # the baseline supplies the third comparable prior: the gate arms
    assert rep["metrics"]["gpt_tokens_per_sec"]["verdict"] == "regression"
    assert rep["exit_code"] == 1


def test_min_history_zero_with_no_priors_does_not_crash(tmp_path):
    # review-round regression: min_history=0 with an empty comparable
    # history must report, not IndexError inside the band arithmetic
    paths = _trajectory(tmp_path, [100.0])
    rep = regress.compare(regress.load_rounds(paths), min_history=0)
    row = rep["metrics"]["gpt_tokens_per_sec"]
    assert row["verdict"] == "insufficient-history"
    assert rep["exit_code"] == 0


def test_timing_key_marks_legacy_round_as_schema1(tmp_path):
    # review-round regression: "timing" is a dict (stripped from the
    # numeric metrics), but it is still a round-2-methodology marker —
    # a partial legacy round whose throughput sections errored must not
    # be misfiled as schema 0 (r1 dispatch methodology)
    p = tmp_path / "partial.json"
    p.write_text(json.dumps({
        "n": 7, "rc": 0,
        "parsed": {"metric": "resnet50_O2_train_throughput",
                   "value": 2400.0, "unit": "imgs/sec/chip",
                   "vs_baseline": 1.9, "timing": {"windows": 5}}}))
    r = regress.load_round(str(p))
    assert r["schema"] == 1, r
    assert r["units"]["value"] == "imgs/sec/chip"
    assert "dispatch" not in r["units"]["value"]


def test_all_rounds_no_evidence_is_not_a_crash(tmp_path):
    p1 = tmp_path / "a.json"
    p1.write_text("not json at all")
    rep = regress.compare(regress.load_rounds([str(p1),
                                               str(tmp_path / "b.json")]))
    assert rep["candidate"] is None
    assert rep["exit_code"] == 0
    assert "note" in rep


def test_direction_table():
    assert regress.metric_direction("o2_step_ms", "ms") == "lower"
    assert regress.metric_direction("x_ms_per_dispatch", "ms") == "lower"
    assert regress.metric_direction("gpt_tokens_per_sec",
                                    "tokens/sec") == "higher"
    assert regress.metric_direction("mfu", "mfu") == "higher"
    assert regress.metric_direction("vs_baseline", "ratio") == "higher"
    assert regress.metric_direction("smoke_mlp_final_loss",
                                    "loss") == "lower"
    assert regress.metric_direction("mystery", "") is None


def test_render_includes_rounds_and_verdicts(tmp_path):
    paths = _trajectory(tmp_path, [100.0, 101.0, 99.5, 100.5, 70.0])
    rep = regress.compare(regress.load_rounds(paths))
    text = regress.render_regress(rep)
    assert "REGRESSIONS: gpt_tokens_per_sec" in text
    assert "| t00.json | ok |" in text


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_over_real_rounds_runs_clean(capsys):
    from apex_tpu.monitor.__main__ import main
    rc = main(["regress", *ROUNDS, "--json"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["candidate"] == "r04"
    assert {r["round"]: r["status"] for r in rep["rounds"]}["r05"] == \
        regress.NO_EVIDENCE


def test_cli_exits_nonzero_only_on_confirmed_regression(tmp_path, capsys):
    from apex_tpu.monitor.__main__ import main
    paths = _trajectory(tmp_path, [100.0, 101.0, 99.5, 100.5, 70.0])
    assert main(["regress", *paths]) == 1
    capsys.readouterr()
    paths = _trajectory(tmp_path, [100.0, 101.0, 99.5, 100.5, 100.2])
    assert main(["regress", *paths]) == 0


def test_cli_against_flag(tmp_path, capsys):
    from apex_tpu.monitor.__main__ import main
    paths = _trajectory(tmp_path, [100.0, 101.0, 60.0])
    base = _mk_round(tmp_path, "base.json", {"gpt_tokens_per_sec": 99.5})
    assert main(["regress", *paths, "--against", base]) == 1


# the bench side of the schema contract: section stamping feeds this
# loader (see also the profile/units assertions in test_bench_stream)

def test_bench_section_units_roundtrip(tmp_path):
    import importlib
    bench = importlib.import_module("bench")
    units = bench._section_units(
        {"metric": "bench_smoke", "value": 3.0, "unit": "steps/sec",
         "o2_step_ms": 1.5, "gpt_tokens_per_sec": 5.0,
         "nested": {"x": 1}, "flag": True})
    assert units["value"] == "steps/sec"          # declared unit wins
    assert units["o2_step_ms"] == "ms"
    assert "aggregate" in units["gpt_tokens_per_sec"]
    assert "nested" not in units and "flag" not in units
