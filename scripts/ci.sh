#!/usr/bin/env bash
# The CI gate: static analysis and the two test commands, as the driver
# runs them — one command, suitable as-is for a PR gate.
#
#   scripts/ci.sh                 # everything
#   CI_SKIP_TESTS=1 scripts/ci.sh # lint only (quick loop)
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
#                    covered the serve entrypoints and both rules
#                    tables; on failure the gating findings are
#                    re-rendered as GitHub ::error annotations
#   1c. lint precision — asserts the v3 analyzer roster is dispatched
#                    and the amp O2 / fp8(O4) / zero3 / pipeline
#                    entrypoints that exercise it stayed registered
#   2. tier-1      — tests/ on the CPU (8 virtual devices, not-slow
#                    subset), 6 workers, one file a worker at a time
#   3. benchmark tests — benchmarks/tests in its own environment (four
#                    virtual devices): the instrument's reductions and
#                    tiny CPU rehearsals of every cell's run
set -uo pipefail
cd "$(dirname "$0")/.."

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
# coverage assertion, independent of the exit code: a gate that silently analyzed nothing must not read green
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
  JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
    --dist loadfile -p no:randomly || fail=1

  echo "== ci: benchmark tests =="
  JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q || fail=1
fi

if [[ "$fail" == "0" ]]; then
  echo "ci: all gates green"
else
  echo "ci: FAILED (see above)"
fi
exit $fail
