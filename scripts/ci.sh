#!/usr/bin/env bash
# The full CI gate: static analysis, tier-1 tests, and the monitor
# telemetry selfcheck — one command, fail-fast, suitable as-is for a PR
# gate.
#
#   scripts/ci.sh                 # everything
#   CI_SKIP_TESTS=1 scripts/ci.sh # lint + selfcheck only (quick loop)
#
# Stages:
#   1. lint        — scripts/lint.sh (AST rules APX001-APX007; jax-free)
#   1b. lint semantic — the traced jaxpr layer in one pass: collective-
#                    axis checks over every registered entrypoint, the
#                    APXJ101-105 semantic analyzers (unreduced shard_map
#                    outputs, loop-invariant collectives under scan,
#                    unbalanced ppermute rings, donation truth), the
#                    APXJ106-107 divergence analyzers (collectives under
#                    rank-divergent control flow), the APXP301-305
#                    precision-flow analyzers (lowp accumulation, loss
#                    -scale misuse, round-trip casts, fp8 amax, O2
#                    overflow-skip), and the APXR201-204 rules-table
#                    validation — DIFFERENTIAL against the committed
#                    lint_report.json baseline, so new code cannot add
#                    findings; the stage also asserts the gate actually
#                    covered the serve entrypoints and both rules tables
#                    (the bench-stream-keys pattern); on failure the
#                    gating findings are re-rendered as GitHub ::error
#                    annotations
#   1c. lint precision — asserts the v3 analyzer roster is dispatched
#                    and the amp O2 / fp8(O4) / zero3 / pipeline
#                    entrypoints that exercise it stayed registered
#   2. tier-1      — the ROADMAP tier-1 pytest command (CPU, 8 virtual
#                    devices, not-slow subset, 870 s budget)
#   3. selfcheck   — python -m apex_tpu.monitor selfcheck: records a
#                    synthetic 3-step amp run with a recorder attached
#                    and asserts the JSONL dump -> report round trip
#                    (per-step loss-scale/grad-norm/step-time fields,
#                    disabled-mode jaxpr purity)
#   4. bench smoke — python bench.py --smoke: tiny-shape CPU sections
#                    through the streaming-evidence pipeline, with one
#                    section FORCIBLY timed out; bench exits non-zero
#                    unless every expected section key (including the
#                    timed-out one) landed in the flushed JSONL — the
#                    guard against a repeat of the r5 evidence loss
#                    (BENCH_r05.json: rc=124, parsed: null)
#   4b. export     — python -m apex_tpu.monitor export --once --check:
#                    the smoke-bench recorder stream must render as
#                    valid Prometheus text exposition AND parse back to
#                    the same values (the scrape == aggregate
#                    self-check) INCLUDING the memory/ gauges the
#                    bench memory section samples; plus `monitor
#                    profile --model gpt` must report an MFU line from
#                    the per-device_kind peak table
#   4c. timeline   — python -m apex_tpu.monitor timeline: the smoke
#                    stream must fuse into a Chrome-trace/Perfetto JSON
#                    that passes an INDEPENDENT shape check (every event
#                    carries ph/pid + numeric ts off the metadata phase,
#                    per-(pid,tid) track timestamps monotonic, B/E
#                    begin/end balanced with unterminated B's allowed)
#                    and still contains span + compile + counter tracks
#   4d. memory     — python -m apex_tpu.monitor memory --model gpt
#                    --json: the unified byte surface must attribute
#                    the canonical step's analytic peak to a NAMED
#                    apx: scope, report a compiled footprint, and run
#                    the tune/vmem calibration rows
#   5. regress     — python -m apex_tpu.monitor regress: the smoke
#                    stream must load as an evidence round, and the
#                    r01-r10 rounds (r01-r05 as the cut fixtures under
#                    tests/fixtures/regress/, r06-r10 as committed at
#                    the root) must degrade exactly
#                    as documented (r05 no-evidence, r01 incomparable,
#                    cpu-host rounds unit-marked, memory byte keys
#                    registered lower-better) with no false regression
#                    verdict
set -uo pipefail
cd "$(dirname "$0")/.."
REPO_DIR="$(pwd)"

fail=0

echo "== ci: lint (AST layer) =="
bash scripts/lint.sh || fail=1

echo "== ci: lint semantic (jaxpr analyzers + rules tables, differential vs lint_report.json) =="
JAX_PLATFORMS=cpu XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
  python -m apex_tpu.lint apex_tpu --jaxpr --json \
    --baseline lint_report.json > /tmp/ci_lint_semantic.json || {
  fail=1
  # render the GATING findings as GitHub ::error annotations so a
  # differential failure lands on the PR diff under Actions
  python - /tmp/ci_lint_semantic.json <<'EOF'
import json, sys
from apex_tpu.lint.cli import github_lines
try:
    payload = json.load(open(sys.argv[1]))
except (OSError, json.JSONDecodeError):
    payload = {}
for line in github_lines(payload):
    print(line)
EOF
}
# coverage assertion, independent of the exit code (the bench-stream-keys
# pattern): a gate that silently analyzed nothing must not read green
python - /tmp/ci_lint_semantic.json <<'EOF' || fail=1
import json, sys
d = json.load(open(sys.argv[1]))
eps = set(d.get("entrypoints_analyzed", []))
tabs = set(d.get("rules_tables_checked", []))
missing_eps = {"serve_decode_step", "serve_prefill_step",
               "serve_verify_step", "fp8_weight_decode_step",
               "zero3_train_step", "fp8_train_step",
               "fused_layer_norm_step", "zero_fused_update_step",
               "memory_profiled_step", "amp_o2_master_step",
               "pp_1f1b_model_step"} - eps
missing_tabs = {"serve.GPT_PARAM_RULES", "serve.CACHE_RULES",
                "zero.DEFAULT_RULES"} - tabs
if missing_eps or missing_tabs:
    print(f"ci: lint semantic gate lost coverage: entrypoints "
          f"{sorted(missing_eps)}, tables {sorted(missing_tabs)}")
    raise SystemExit(1)
print(f"ci: lint semantic covered {len(eps)} entrypoints + "
      f"{len(tabs)} rules tables; "
      f"{len(d.get('new_findings', []))} new finding(s) vs baseline")
EOF

echo "== ci: lint precision (APXP/APXJ106 analyzer roster + amp/fp8/zero/pipeline coverage) =="
# the v3 analyzers must actually be in the dispatched roster AND the
# entrypoints that exercise their contracts (amp O2 master weights,
# fp8/O4, zero3, the pipeline schedules) must be in the traced set —
# a refactor that silently drops either must not read green
python - /tmp/ci_lint_semantic.json <<'EOF' || fail=1
import json, sys
d = json.load(open(sys.argv[1]))
roster = set(d.get("jaxpr_analyzers", []))
need = {f"APXP30{i}" for i in range(1, 6)} | {"APXJ106", "APXJ107"}
missing = need - roster
eps = set(d.get("entrypoints_analyzed", []))
need_eps = {"amp_train_step", "amp_o2_master_step", "fp8_train_step",
            "zero3_train_step", "pipeline_schedule",
            "pp_zero_bubble_step", "pp_1f1b_model_step"}
missing_eps = need_eps - eps
if missing or missing_eps:
    print(f"ci: lint precision gate lost coverage: analyzer codes "
          f"{sorted(missing)}, entrypoints {sorted(missing_eps)}")
    raise SystemExit(1)
print(f"ci: precision-flow + divergence analyzers "
      f"({', '.join(sorted(need))}) in roster over amp O2/fp8(O4)/"
      f"zero3/pipeline entrypoints")
EOF

if [[ "${CI_SKIP_TESTS:-0}" != "1" ]]; then
  echo "== ci: tier-1 tests =="
  ( set -o pipefail; rm -f /tmp/_t1.log; \
    timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
      -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
      -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log ) || fail=1
fi

echo "== ci: monitor selfcheck =="
JAX_PLATFORMS=cpu python -m apex_tpu.monitor selfcheck --quiet || fail=1

echo "== ci: bench streaming-evidence smoke =="
( cd /tmp && JAX_PLATFORMS=cpu PYTHONPATH="$REPO_DIR" \
    BENCH_STREAM_PATH=/tmp/ci_bench_smoke_stream.jsonl \
    python "$REPO_DIR/bench.py" --smoke > /tmp/ci_bench_smoke.json ) || fail=1

echo "== ci: overlap + zero-bubble + zero-sharded + fp8 + autotune + profile + serve bench sections in the evidence stream =="
# the PR-4 overlap sections, the PR-5 pp_zero_bubble section, the
# PR-6 zero_sharded_step section, the PR-7 fp8_step section, the
# PR-8 autotune section and the PR-10 profile section must land as
# flushed section lines (bench --smoke already asserts SMOKE_EXPECTED;
# this is the independent driver-side check of the same contract)
python - /tmp/ci_bench_smoke_stream.jsonl <<'EOF' || fail=1
import json, sys
seen = set()
for line in open(sys.argv[1]):
    ev = json.loads(line)
    if ev.get("kind") == "section":
        seen.add(ev.get("name"))
missing = {"tp_overlap", "ddp_bucket_overlap", "pp_zero_bubble",
           "zero_sharded_step", "fp8_step", "autotune", "fused_ln",
           "multi_tensor_update", "profile", "serve_decode",
           "serve_spec", "serve_fleet", "memory"} - seen
if missing:
    print(f"ci: sections missing from bench stream: {sorted(missing)}")
    raise SystemExit(1)
# the serve section's SLO numbers must now be SPAN-derived: the
# stream line carries the monitor.spans histogram keys, not just the
# legacy ad-hoc ones (acceptance criterion of the telemetry PR)
serve = next(ev.get("data") or {} for ev in
             map(json.loads, open(sys.argv[1]))
             if ev.get("kind") == "section"
             and ev.get("name") == "serve_decode")
span_keys = {"serve_p50_token_ms", "serve_p99_token_ms",
             "serve_ttft_ms"}
missing_slo = span_keys - set(serve)
if missing_slo and not any(k.endswith(("_error", "_skipped"))
                           for k in serve):
    print(f"ci: serve section lost span-derived SLO keys: "
          f"{sorted(missing_slo)} (have: {sorted(serve)[:20]})")
    raise SystemExit(1)
# the serve_spec section's claims must land with their evidence: the
# spec-vs-plain speedup AND the parity-checked throughputs AND the
# fp8 weight-byte ratio (measured through monitor.memory) — a
# speculative-decoding section that silently lost an assert input
# must not read green
spec = next(ev.get("data") or {} for ev in
            map(json.loads, open(sys.argv[1]))
            if ev.get("kind") == "section"
            and ev.get("name") == "serve_spec")
spec_keys = {"serve_spec_speedup_vs_plain", "serve_spec_accept_rate",
             "serve_spec_tokens_per_sec",
             "serve_spec_plain_tokens_per_sec",
             "serve_spec_draft_step_speedup",
             "serve_fp8_weight_bytes_ratio"}
missing_spec = spec_keys - set(spec)
if missing_spec and not any(k.endswith(("_error", "_skipped"))
                            for k in spec):
    print(f"ci: serve_spec section lost its evidence keys: "
          f"{sorted(missing_spec)} (have: {sorted(spec)[:20]})")
    raise SystemExit(1)
# the memory section's byte claims must come THROUGH monitor.memory:
# the stream line carries the re-derived ZeRO residency + pool keys
mem = next(ev.get("data") or {} for ev in
           map(json.loads, open(sys.argv[1]))
           if ev.get("kind") == "section"
           and ev.get("name") == "memory")
mem_keys = {"memory_zero_dense_bytes_per_chip",
            "memory_zero_zero3_bytes_per_chip",
            "memory_zero_dense_over_zero3_ratio",
            "memory_gpt_analytic_peak_bytes", "serve_pool_occupancy"}
missing_mem = mem_keys - set(mem)
if missing_mem and not any(k.endswith(("_error", "_skipped"))
                           for k in mem):
    print(f"ci: memory section lost its byte keys: "
          f"{sorted(missing_mem)} (have: {sorted(mem)[:20]})")
    raise SystemExit(1)
print("ci: tp_overlap + ddp_bucket_overlap + pp_zero_bubble + "
      "zero_sharded_step + fp8_step + autotune + fused_ln + "
      "multi_tensor_update + profile + serve_decode + serve_spec + "
      "serve_fleet + memory present in bench stream (serve SLO keys "
      "span-derived, spec speedup/parity/fp8-weight evidence present, "
      "memory byte keys re-derived through monitor.memory)")
EOF

echo "== ci: monitor export (Prometheus exposition) + profile MFU =="
# the smoke-bench recorder stream must render as valid exposition and
# round-trip (scrape -> parse -> values == aggregate): --check raises
# on any drift
python -m apex_tpu.monitor export /tmp/ci_bench_smoke_stream.jsonl \
    --once --check > /tmp/ci_export.txt || fail=1
grep -q "^apex_" /tmp/ci_export.txt || {
  echo "ci: export emitted no apex_ metrics"; fail=1; }
# the profile CLI reports MFU beside the FLOPs table (tiny default
# shapes; the cpu peak-table row makes the line concrete on CI hosts)
JAX_PLATFORMS=cpu python -m apex_tpu.monitor profile --model gpt \
    > /tmp/ci_profile_mfu.txt || fail=1
grep -q "^MFU: " /tmp/ci_profile_mfu.txt || {
  echo "ci: monitor profile lost its MFU line"; fail=1; }
# the bench memory section's sampler gauges must be scrapeable: the
# export of the smoke stream has to carry memory/ metrics
grep -q "^apex_memory_" /tmp/ci_export.txt || {
  echo "ci: export scrape carries no memory/ gauges"; fail=1; }

echo "== ci: monitor fleet (multi-replica aggregation + SLO burn-rate gate) =="
# both directions of the alert contract, driver-side: a healthy
# two-replica file pair must aggregate clean and exit 0; a starved
# replica (queue waits of 65-90 s against the 30 s objective + the
# admission_starvation pressure counter) must flip the exit code AND
# render the alert + scale_out decision — an alerting layer that can't
# fire, or that cries wolf on healthy traffic, must not read green
python - <<'EOF' || fail=1
from apex_tpu.monitor import export
from apex_tpu.monitor.recorder import Recorder

def replica(path, rid, counters, gauges, waits):
    rec = Recorder(traced_hooks=False, name=rid)
    for name, v in counters:
        rec.counter(name, v)
    for name, v in gauges:
        rec.gauge(name, v)
    for v in waits:
        rec.observe("serve/queue_wait_ms", v)
    text = export.render_prometheus(export.snapshot(recorder=rec),
                                    replica=rid)
    with open(path, "w") as f:
        f.write(text)

replica("/tmp/ci_fleet_h1.prom", "h1",
        [("serve/tokens_generated", 120)],
        [("serve/pages_in_use", 4.0), ("serve/queue_depth", 0.0)],
        [4.0, 9.0, 15.0])
replica("/tmp/ci_fleet_h2.prom", "h2",
        [("serve/tokens_generated", 80)],
        [("serve/pages_in_use", 7.0), ("serve/queue_depth", 1.0)],
        [3.0, 6.0, 11.0])
replica("/tmp/ci_fleet_starved.prom", "starved",
        [("serve/tokens_generated", 10),
         ("health/admission_starvation", 3)],
        [("serve/pages_in_use", 30.0), ("serve/queue_depth", 6.0)],
        [65000.0, 70000.0, 90000.0])
print("ci: fleet fixtures written (h1/h2 healthy, starved)")
EOF
python -m apex_tpu.monitor fleet \
    /tmp/ci_fleet_h1.prom /tmp/ci_fleet_h2.prom --once --json \
    > /tmp/ci_fleet_healthy.json || {
  echo "ci: fleet CLI flagged a HEALTHY pair (false alert)"; fail=1; }
python - /tmp/ci_fleet_healthy.json <<'EOF' || fail=1
import json, sys
v = json.load(open(sys.argv[1]))
assert v["n_up"] == 2 and v["n_replicas"] == 2, v
assert v["counters"]["apex_serve_tokens_generated_total"] == 200, \
    v["counters"]
assert "apex_serve_queue_wait_ms" in v["hist_summary"], \
    sorted(v["hist_summary"])
assert not v["alerts"] and not v["decisions"], (v["alerts"],
                                                v["decisions"])
print(f"ci: fleet healthy pair ok — 2/2 up, counters summed, "
      f"merged p99(queue_wait)="
      f"{v['hist_summary']['apex_serve_queue_wait_ms']['p99']:g} ms, "
      f"no alerts")
EOF
python -m apex_tpu.monitor fleet \
    /tmp/ci_fleet_h1.prom /tmp/ci_fleet_starved.prom --once \
    > /tmp/ci_fleet_starved.txt && {
  echo "ci: fleet CLI read green on a STARVED replica"; fail=1; }
grep -q "^ALERT \[" /tmp/ci_fleet_starved.txt || {
  echo "ci: starved fleet poll exited non-zero but rendered no ALERT"
  fail=1; }
grep -q "^DECISION \[scale_out\]" /tmp/ci_fleet_starved.txt || {
  echo "ci: starved fleet poll rendered no scale_out decision"
  fail=1; }
grep -E "^ALERT \[" /tmp/ci_fleet_starved.txt | head -2

echo "== ci: monitor timeline (Perfetto trace shape check) =="
# the smoke stream must fuse into a valid Chrome-trace JSON; the shape
# check below is deliberately independent of validate_timeline (the
# bench-stream-keys pattern: the gate re-derives the contract itself)
python -m apex_tpu.monitor timeline /tmp/ci_bench_smoke_stream.jsonl \
    -o /tmp/ci_trace.json || fail=1
python - /tmp/ci_trace.json <<'EOF' || fail=1
import json, sys
trace = json.load(open(sys.argv[1]))
evs = trace.get("traceEvents") or []
assert evs, "trace has no events"
last = {}
stacks = {}
for i, ev in enumerate(evs):
    assert ev.get("ph"), f"event {i} missing ph: {ev}"
    assert ev.get("pid") is not None, f"event {i} missing pid: {ev}"
    if ev["ph"] == "M":
        continue
    ts = ev.get("ts")
    assert isinstance(ts, (int, float)), f"event {i} bad ts: {ev}"
    key = (ev["pid"], ev.get("tid"))
    prev = last.get(key)
    assert prev is None or ts >= prev - 1e-6, \
        f"event {i}: ts {ts} < {prev} on track {key}"
    last[key] = max(ts, prev) if prev is not None else ts
    if ev["ph"] == "B":
        stacks.setdefault(key, []).append(ev.get("name"))
    elif ev["ph"] == "E":
        assert stacks.get(key), f"event {i}: E without B on {key}"
        stacks[key].pop()
# the smoke run's telemetry must actually land as tracks: spans from
# the serve section, compile timers, and the hbm counter series
phs = {e["ph"] for e in evs}
names = {e.get("name") for e in evs}
assert "X" in phs and "M" in phs, sorted(phs)
assert any(str(n).startswith("jax/compile/") for n in names), \
    "no compile events in trace"
assert any(e["ph"] == "C" for e in evs), "no counter tracks in trace"
threads = {(e.get("args") or {}).get("name") for e in evs
           if e["ph"] == "M" and e.get("name") == "thread_name"}
assert any(str(t).startswith("span/") for t in threads
           if t is not None), f"no span threads in trace: {threads}"
print(f"ci: timeline ok — {len(evs)} events, shape-checked "
      f"(ph/pid/ts, per-track monotonic, B/E balanced)")
EOF

echo "== ci: monitor memory (unified byte surface self-check) =="
# the memory CLI must answer "which module owns the peak" with a NAMED
# scope, report a compiled footprint, and run the vmem calibration
JAX_PLATFORMS=cpu python -m apex_tpu.monitor memory --model gpt --json \
    > /tmp/ci_memory.json || fail=1
python - /tmp/ci_memory.json <<'EOF' || fail=1
import json, sys
d = json.load(open(sys.argv[1]))
prof = d["profile"]
hw = prof["analytic"]
assert hw["peak_live_bytes"] > 0, hw
assert hw["peak_scope"] != "(unscoped)", \
    f"analytic peak lost its scope: {hw['peak_scope']}"
assert prof["compiled"].get("total_bytes", 0) > 0, prof["compiled"]
cal = d["vmem_calibration"]
assert cal["checked"] >= 3, cal
print(f"ci: monitor memory ok — peak {hw['peak_live_bytes']} B at "
      f"`{hw['peak_scope']}`, {cal['checked']} vmem configs "
      f"calibrated ({cal['mispredicts']} mispredicts)")
EOF

echo "== ci: bench-trajectory regression gate (monitor.regress) =="
# 1) the smoke stream must load as an evidence round without crashing
#    (single round: nothing to compare, but the loader + schema stamp
#    are exercised on every CI run)
python -m apex_tpu.monitor regress /tmp/ci_bench_smoke_stream.jsonl \
    --json > /tmp/ci_regress_smoke.json || fail=1
# 2) the rounds r01-r10 (r01-r05: tests/fixtures/regress/round_r0N.json,
#    the driver wrappers cut to rc + parsed; r06-r10: BENCH_rNN.json at
#    the root) must degrade exactly as documented:
#    r05 is a no-evidence row (rc=124), r01 is incomparable with r02+
#    (the unit-methodology change), the cpu-host rounds (r06-r10) are
#    unit-marked so platform-bound metrics never cross-compare, and no
#    false regression fires (two-digit round filenames from r10 on)
python - <<'EOF' || fail=1
import json, subprocess, sys
p = subprocess.run(
    [sys.executable, "-m", "apex_tpu.monitor", "regress",
     *[f"tests/fixtures/regress/round_r{i:02d}.json" for i in range(1, 6)],
     *[f"BENCH_r{i:02d}.json" for i in range(6, 11)], "--json"],
    capture_output=True, text=True)
if p.returncode != 0:
    print(f"ci: regress over committed rounds exited {p.returncode}:\n"
          f"{p.stdout}\n{p.stderr}")
    raise SystemExit(1)
rep = json.loads(p.stdout)
by = {r["round"]: r for r in rep["rounds"]}
assert by["r05"]["status"] == "no-evidence", by["r05"]
assert by["r09"]["status"] == "ok", by["r09"]
assert by["r10"]["status"] == "ok", by["r10"]
inc = rep["metrics"]["value"].get("incomparable") or []
assert any(i["round"] == "r01" for i in inc), rep["metrics"]["value"]
# the r13 kernel cost-model keys are platform-independent: they must be
# registered in the unit schema (not suffix-inferred driftable blanks)
units = {k: rep["metrics"][k]["unit"] for k in rep["metrics"]
         if k.startswith(("fused_ln_", "fused_ce_", "multi_tensor_"))}
missing = [k for k, u in units.items() if not u]
assert not missing, f"unregistered kernel metric units: {missing}"
# the r14 serve SLO / MFU keys must be unit-registered with a known
# gating direction (the regress direction table satellite)
from apex_tpu.monitor.regress import metric_direction
for k in [m for m in rep["metrics"]
          if m.startswith(("serve_ttft", "serve_p50", "serve_p99",
                           "serve_queue_wait", "serve_goodput",
                           "serve_spec_tokens", "serve_spec_speedup",
                           "serve_spec_draft_step_speedup",
                           "serve_fp8_weight_bytes"))
          or m == "profile_mfu_pct"]:
    u = rep["metrics"][k]["unit"]
    assert u, f"unregistered serve/MFU metric unit: {k}"
    assert metric_direction(k, u) is not None, \
        f"no gating direction for {k} ({u})"
# the r15 memory byte keys + serve_pool_occupancy must be registered
# with a known (lower-better) gating direction — bytes gate from r09 on
mem_keys = [m for m in rep["metrics"]
            if m.startswith("memory_") or m == "serve_pool_occupancy"]
assert "memory_zero_dense_bytes_per_chip" in mem_keys \
    and "serve_pool_occupancy" in mem_keys, \
    f"memory keys missing from the r09 candidate: {sorted(mem_keys)}"
for k in mem_keys:
    u = rep["metrics"][k]["unit"]
    assert u, f"unregistered memory metric unit: {k}"
    # capacity metrics gate lower-better; counts/config metadata
    # (world size, configs-checked) report without gating
    if any(s in k for s in ("bytes", "occupancy", "utilization",
                            "mispredict")):
        assert metric_direction(k, u) == "lower", \
            f"{k} must gate lower-better ({u})"
assert not rep["regressions"], rep["regressions"]
print("ci: regress gate ok over r01-r10 (r05 no-evidence, r01 "
      "incomparable, kernel + serve-SLO/MFU + memory byte metric "
      "units registered lower-better, no false regressions)")
EOF

if [[ "$fail" == "0" ]]; then
  echo "ci: all gates green"
else
  echo "ci: FAILED (see above)"
fi
exit $fail
