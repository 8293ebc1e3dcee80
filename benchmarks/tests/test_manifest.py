"""``BENCHMARK.json`` against the contract, and the command's refusals."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return manifest.load_manifest()


def _line(s, limit=200):
    return 1 <= len(s) <= limit and "\n" not in s and "\t" not in s


def test_top_level_keys_and_command(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["paths"] == ["benchmarks"]
    assert 1 <= len(man["command"]) <= 32
    assert all(_line(w) and not w.startswith("/") and ".." not in w
               for w in man["command"])
    assert os.path.exists(os.path.join(manifest.ROOT, man["command"][1]))
    size = os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells(man):
    rs = man["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_entries(man):
    names = []
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        for e in man[group]:
            assert set(e) == keys, e
            assert NAME.match(e["name"]) and _line(e["why"])
        names += [e["name"] for e in man[group]]
    for m in man["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in man["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
    for m in man["end_to_end"] + man["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for group in ("configs", "workloads"):
        ns = [e["name"] for e in man[group]]
        assert len(set(ns)) == len(ns)
    assert 1 <= len(man["end_to_end"]) <= 16
    assert 1 <= len(man["per_layer"]) <= 128
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in man["end_to_end"])


def test_every_cell_finds_its_files(man):
    cells = man["workloads"]
    assert 2 <= len(cells) <= 24
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(set(pairs)) == len(pairs)
    four = [c for c in cells if c["chips"] == 4]
    assert all(c["chips"] in (1, 4) for c in cells)
    assert len(four) <= max(1, len(cells) // 4)
    used = set()
    for c in cells:
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])
        config = manifest.load_config(man, c["config"])
        traffic = manifest.load_traffic(c["traffic"])
        assert traffic["kind"] in ("train", "serve")
        fam = manifest.load_family(config["family"])
        assert callable(getattr(fam, f"build_{traffic['kind']}"))
        assert os.path.exists(os.path.join(
            manifest.BENCH_DIR, "reference", config["family"] + ".py"))
        assert 0 < config["logit_tolerance"] < 0.05
        used.add(c["config"])
        if c["chips"] == 4:
            assert traffic["layout"]["chips"] == 4
    files = [c["file"] for c in man["configs"]]
    assert len(set(files)) == len(files)
    for c in man["configs"]:
        assert c["name"] in used, f"configuration {c['name']} has no cell"
        assert c["file"].startswith("benchmarks/") and PATH.match(c["file"])
        body = manifest.load_config(man, c["name"])
        assert body["source"] == c["source"] and _line(c["source"])
        assert body["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert "assumed" in body and "published" in body
        assert "deployment" in body and "departures" in body


def test_every_cell_reports_what_the_contract_asks(man):
    e2e = {m["name"] for m in man["end_to_end"]}
    for c in man["workloads"]:
        mine = [m["name"] for m in
                manifest.metrics_for(man, "end_to_end", c["name"])]
        assert "setup_s" in mine and len(mine) >= 2, c["name"]
        layers = manifest.metrics_for(man, "per_layer", c["name"])
        assert layers, c["name"]
        for m in layers:
            assert m["moves"] in e2e
            assert m["moves"] in mine, (c["name"], m["name"])
            manifest.load_layer_metric(m["name"])
    cells = {c["name"] for c in man["workloads"]}
    for m in man["end_to_end"] + man["per_layer"]:
        assert set(m.get("workloads", [])) <= cells, m["name"]


def test_file_names_under_paths():
    for base, _, files in os.walk(manifest.BENCH_DIR):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), manifest.ROOT)
            assert PATH.match(rel), rel
    for f in os.listdir(os.path.join(manifest.BENCH_DIR, "traffic")):
        assert f.endswith((".json", ".jsonl", ".toml", ".txt", ".csv")), f


def _run(cwd, *extra_env):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "gpt2m-train-s1024", "--seed", "0", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_refuses_a_machine_without_a_tpu():
    p = _run(manifest.ROOT)
    assert p.returncode == 2 and p.stdout == ""
    assert "not a TPU" in p.stderr


def test_command_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(manifest.BENCH_DIR, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0 and p.stdout == ""
    assert "apex_tpu" in p.stderr
