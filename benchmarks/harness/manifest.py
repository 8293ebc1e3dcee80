"""Reads ``BENCHMARK.json`` and finds every file a cell names.

The harness is driven by data: a cell names a configuration and a traffic
mix, a configuration names its family, a metric names its reader, and each
of those is a file of its own under ``benchmarks/`` found by that name. A
missing one is an error that says which file to add.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class ManifestError(RuntimeError):
    pass


def _load_json(path: str, what: str) -> dict:
    if not os.path.exists(path):
        raise ManifestError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"), "the manifest")


def find_workload(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(
        f"no workload {name!r} in BENCHMARK.json (has: "
        f"{[w['name'] for w in manifest['workloads']]})")


def load_config(manifest: dict, name: str, root: str = ROOT) -> dict:
    """The configuration's file, with ``sizes``: the published keys and
    the assumed ones in one flat dict, as the model is run."""
    for c in manifest["configs"]:
        if c["name"] == name:
            cfg = _load_json(os.path.join(root, c["file"]),
                             f"configuration {name!r}")
            cfg["sizes"] = {**cfg["published"],
                            **{k: v for k, v in cfg["assumed"].items()
                               if not k.endswith("_why")}}
            return cfg
    raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return _load_json(os.path.join(bench_dir, "traffic", f"{name}.json"),
                      f"traffic mix {name!r}")


def load_family(name: str):
    try:
        return importlib.import_module(f"benchmarks.families.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmarks.families.{name}":
            raise
        raise ManifestError(
            f"no benchmarks/families/{name}.py: add that file (nothing "
            f"else needs an edit)") from None


def load_layer_metric(name: str, bench_dir: str = BENCH_DIR):
    """The reader of the per-layer metric ``name``: the file named by the
    part of ``name`` before its first ``.``. What follows the dot only tells
    entries apart, since an entry names ONE end-to-end metric it moves:
    ``device_idle_share.train`` and ``device_idle_share.serve`` are two
    entries read by ``layer_metrics/device_idle_share.py``, and a cell with
    a new end-to-end metric reuses a reader by adding an entry, no file."""
    reader = name.split(".", 1)[0]
    path = os.path.join(bench_dir, "layer_metrics", f"{reader}.py")
    if not os.path.exists(path):
        raise ManifestError(
            f"no benchmarks/layer_metrics/{reader}.py for the metric "
            f"{name!r}: add that file with a compute(run) (nothing else "
            f"needs an edit)")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + reader.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "compute", None)):
        raise ManifestError(f"benchmarks/layer_metrics/{reader}.py has no "
                            f"compute(run)")
    return mod


def metrics_for(manifest: dict, group: str, workload: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics that exist in a cell."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]
