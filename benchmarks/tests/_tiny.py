"""A temporary copy of the benchmark's data with tiny cells added to it: what
a later PR does, here by a test. No harness file is edited: the copy holds
only ``BENCHMARK.json``, ``configs/``, ``traffic/`` and ``layer_metrics/``,
and the code is the repo's own ``benchmarks`` package."""

from __future__ import annotations

import copy
import json
import os
import shutil

from benchmarks.harness import manifest, peaks

#: not a device: the rehearsal computes shares of this and asserts none
FAKE_PEAK = peaks.Peak(bf16_flops=1e12, hbm_bytes_per_s=1e11,
                       hbm_bytes=10 ** 9, source="test")

TINY_GPT = {
    "name": "tiny-gpt", "family": "gpt", "source": "test",
    "published": {"model_type": "gpt2", "n_ctx": 64, "n_positions": 64,
                  "n_embd": 64, "n_head": 4, "n_layer": 2, "n_inner": None,
                  "vocab_size": 250, "layer_norm_epsilon": 1e-5},
    "assumed": {"padded_vocab_size": 256, "dtype": "bfloat16"},
    # twice the largest bf16 error the checks read at this size on the CPU
    # (0.0085 over the seeds tried), as the real files hold twice the chip's
    "logit_tolerance": 0.017,
    "reduced": [], "departures": [], "deployment": "a test"}

TINY_BERT = {
    "name": "tiny-bert", "family": "bert", "source": "test",
    "published": {"model_type": "bert", "hidden_size": 64,
                  "num_hidden_layers": 2, "num_attention_heads": 4,
                  "intermediate_size": 256, "max_position_embeddings": 64,
                  "type_vocab_size": 2, "vocab_size": 250,
                  "layer_norm_eps": 1e-12},
    "assumed": {"padded_vocab_size": 256, "dtype": "bfloat16"},
    "logit_tolerance": 0.017,
    "reduced": [], "departures": [], "deployment": "a test"}

TINY_TRAIN = {
    "kind": "train", "rate_metric": "train_tokens_per_s", "entry": "amp",
    "opt_level": "O2",
    "optimizer": {"name": "FusedAdam", "lr": 1e-3}, "batch": 2, "seq": 32,
    "ring": 2, "fetch_every": 2, "warmup_steps": 2, "trace_steps": 2,
    "check": {"shape": [2, 16]}}

# as cells 1 and 2: one group queued behind the one awaited, median rate
TINY_MLM = {**TINY_TRAIN, "optimizer": {"name": "FusedLAMB", "lr": 1e-3},
            "mask_share": 0.15, "groups_in_flight": 2,
            "rate_from": "median_group"}

TINY_4DEV = {
    "kind": "train", "rate_metric": "train4_tokens_per_s",
    "entry": "example_gpt",
    "optimizer": {"name": "FusedAdam", "lr": 1e-3, "master_weights": True},
    "layout": {"chips": 4, "tp": 4, "sequence_parallel": True},
    "batch": 4, "seq": 32, "ring": 2, "fetch_every": 1, "warmup_steps": 2,
    "trace_steps": 2, "check": {"shape": [2, 16]}}

TINY_2X2 = {**TINY_4DEV,
            "layout": {"chips": 4, "tp": 2, "sequence_parallel": True}}

TINY_SERVE = {
    "kind": "serve", "rate_metric": "serve_tokens_per_s",
    "arrival": {"process": "closed", "clients": 3},
    "prompt_len": {"dist": "loguniform", "lo": 4, "hi": 16},
    "output_len": {"dist": "loguniform", "lo": 2, "hi": 6},
    "engine": {"max_batch": 4, "max_seq_len": 32, "max_prompt_len": 16,
               "page_size": 8, "record_logits": False},
    "trace_seconds": 0.2, "check": {"shape": [2, 3]}}

# every request is over with its prefill's token
TINY_ONE = {**TINY_SERVE, "output_len": {"dist": "fixed", "value": 1}}

TINY_OPEN = {**TINY_SERVE,
             "arrival": {"process": "poisson", "rate_per_s": 20.0}}

EXTRA_METRIC = '''"""Added by the test: the program's spans that closed between the
window's markers (the recorder's events a later reader would take)."""


def compute(run):
    return float(sum(1 for e in run["window_events"]
                     if e["kind"] == "span_end"))
'''


def make_root(tmp_path, cells):
    """``cells``: {workload name: (config dict, traffic dict, chips)}.
    Returns ``(root, manifest)`` of a copy with those cells ADDED."""
    root = str(tmp_path)
    src = manifest.BENCH_DIR
    dst = os.path.join(root, "benchmarks")
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(src, sub), os.path.join(dst, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    man = copy.deepcopy(manifest.load_manifest())
    names = list(cells)
    for name, (config, traffic, chips) in cells.items():
        with open(os.path.join(dst, "configs", config["name"] + ".json"),
                  "w") as f:
            json.dump(config, f)
        with open(os.path.join(dst, "traffic", name + "-mix.json"),
                  "w") as f:
            json.dump(traffic, f)
        if config["name"] not in [c["name"] for c in man["configs"]]:
            man["configs"].append({
                "name": config["name"], "source": "test",
                "file": f"benchmarks/configs/{config['name']}.json",
                "reduced": [], "why": "test"})
        man["workloads"].append({"name": name, "config": config["name"],
                                 "traffic": name + "-mix", "chips": chips,
                                 "why": "test"})
    with open(os.path.join(dst, "layer_metrics", "spans_in_window.py"),
              "w") as f:
        f.write(EXTRA_METRIC)
    # a new cell joins the entries of the end-to-end metric its traffic
    # file reports and of the per-layer metrics that move it
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [n for n in names if cells[n][1]["rate_metric"]
                               == m.get("moves", m["name"])]
    man["per_layer"].append({
        "name": "spans_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry points",
        "moves": "setup_s"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return root, man


def run(root, man, workload, *, seed=0, seconds=0.3, trace=False, chips=1):
    """``run.py:run_cell`` on CPU devices; returns (detail lines, result)."""
    import jax
    from benchmarks import run as run_mod
    lines = []
    cell = manifest.find_workload(man, workload)
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    rc = run_mod.run_cell(root, man, cell, seed, seconds, trace, dev,
                          FAKE_PEAK, devs[:chips],
                          emit=lambda s, flush=True: lines.append(
                              json.loads(s)))
    assert rc == 0
    return lines[:-1], lines[-1]
