"""Tier-1 runs the instrument's program-facing tests.

``benchmarks/tests`` rehearses on the CPU, at tiny sizes, the code path
the driver runs on the chip in every cell: ``run_cell`` through the
families, the references, the manifest and the reductions that read the
program's ``apx:`` scopes and spans. A program PR that renames something
a family imports must learn of it here, not from the chip run's
``output_malformed``. Tier-1 collects ``tests/`` only and nothing under
``benchmarks/`` may be edited outside a ``benchmark`` PR, so each file
that imports ``apex_tpu`` or the ``_tiny`` rehearsal helpers runs as a
child process in the benchmark's own environment (four virtual devices,
no xdist). When a ``benchmark`` issue can move files, this bridge goes
(ROADMAP D9).
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


FILES = ("test_rehearsal.py", "test_deepseek.py", "test_longcat.py",
         "test_reference.py", "test_manifest.py", "test_flops_bytes.py",
         "test_span_reduce.py", "test_minicpm_sala.py",
         "test_host_round.py", "test_mellum.py", "test_qwen3_next.py")

#: ``test_longcat.py``'s manifest test also asserts that the benchmark has
#: SIX cells. PR 37 added the seventh and may not edit a benchmark file, so
#: that ONE test is deselected there and lives on, every other assertion of
#: it letter for letter, as ``test_minicpm_sala.py::
#: test_cell_6_is_in_the_manifest_with_its_traffic_letter_for_letter``. Not a
#: mechanism: the first thing a ``benchmark`` PR takes back (drop the count
#: in ``test_longcat.py``, empty this table, delete the copy; ROADMAP R-B)
DESELECT = {"test_longcat.py": (
    "test_the_cell_is_in_the_manifest_with_its_traffic_letter_for_letter",)}


@pytest.fixture(scope="module")
def children():
    """All eleven start together: under ``--dist loadfile`` this file is
    one worker's, and run one after another they are three minutes of
    it, the last of them after every other worker has finished."""
    # tier-1's XLA_FLAGS asks for eight devices; without it
    # benchmarks/tests/conftest.py sets the four its cells are laid out on
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    procs = {name: subprocess.Popen(
        [sys.executable, "-m", "pytest", f"benchmarks/tests/{name}", "-q",
         "-p", "no:cacheprovider"]
        + [f"--deselect=benchmarks/tests/{name}::{t}"
           for t in DESELECT.get(name, ())],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for name in FILES}
    yield procs
    for proc in procs.values():
        if proc.poll() is None:
            proc.kill()
            proc.wait()


@pytest.mark.parametrize("name", FILES)
def test_benchmark_tests_pass(children, name):
    out, _ = children[name].communicate(timeout=900)
    assert children[name].returncode == 0, out[-4000:]


# -- the per-layer metric PR 41 added: a reader of the program's counters -----

@pytest.mark.parametrize("counters,trace,want", [
    # cell 4: 3 x 144 calls, each a scatter that rings and a blocking gather
    ({"tp/sp_linear_ring": 432, "tp/sp_linear_blocking": 432}, {}, 50.0),
    ({"tp/sp_linear_ring": 108, "tp/sp_linear_blocking": 36}, {}, 75.0),
    ({"tp/sp_linear_blocking": 288}, {}, 0.0),  # a tree that fell back
    ({"tune/cache_miss": 3}, {}, None),       # world == 1, or the parent
    ({"tp/sp_linear_ring": 144}, None, None),  # no device trace: no share
])
def test_sp_ring_share_reads_the_layers_counters(counters, trace, want):
    """``sp_ring_share`` = ring / (ring + blocking) of the collectives of
    the SP linear calls the process traced, in cell 4 only, and nothing
    where the program has no such counter (the driver lays this PR's
    benchmark files over the parent's checkout too)."""
    import json
    sys.path.insert(0, ROOT)
    try:
        from benchmarks.layer_metrics import sp_ring_share
    finally:
        sys.path.remove(ROOT)
    assert sp_ring_share.compute({"counters": counters,
                                  "trace": trace}) == want
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "sp_ring_share"]
    assert entry == [{
        "name": "sp_ring_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "multi-chip",
        "moves": "train4_tokens_per_s",
        "workloads": ["gpt2l-train-4chip"]}]
