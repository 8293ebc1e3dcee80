"""The boundary between the program and its tools.

The packages a train step or a serve round runs through see only the
EMIT side of ``apex_tpu.monitor`` — ``_state``, ``hooks``, ``spans``,
``profile`` (for ``scope``) and ``flight`` (``trigger`` in two fault
paths). Everything that reads dumps, renders reports or serves HTTP is
the tool side and loads on first use. Two checks hold the line:

- an AST scan of each program package's imports (function-level ones
  too): nothing of ``monitor`` beyond the emit side, nothing of
  ``benchmarks``, ``examples`` or a root script, and of
  ``apex_tpu.lint`` only the three listed input checks;
- fresh interpreters: importing a program package loads no tool-side
  ``monitor`` module.
"""

import ast
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROGRAM_PACKAGES = ("amp", "data", "models", "ops", "optimizers",
                    "parallel", "serve", "transformer", "tune", "zero")
EMIT_SIDE = {"_state", "hooks", "spans", "profile", "flight"}
TOOL_SIDE = ("report", "health", "memory", "merge", "timeline", "trace",
             "xprof", "export", "fleet", "slo")
ROOT_SCRIPTS = {f[:-3] for f in os.listdir(ROOT) if f.endswith(".py")}
OFF_LIMITS = {"benchmarks", "examples"} | ROOT_SCRIPTS
#: The program's three calls into the linter, all input checks made when
#: a configuration is built (ROADMAP D4: the rules tables validate
#: themselves through ``lint/rules_tables.py``; the pipeline's debug
#: probe reads collective axes through ``lint/jaxpr_checks.py``). Listed
#: so that a fourth is a failure; the list shrinks with D4.
KNOWN_LINT_IMPORTS = {
    ("apex_tpu/serve/rules.py",
     "apex_tpu.lint.rules_tables.constructor_validate"),
    ("apex_tpu/zero/rules.py",
     "apex_tpu.lint.rules_tables.constructor_validate"),
    ("apex_tpu/transformer/pipeline_parallel/schedules.py",
     "apex_tpu.lint.jaxpr_checks.collective_axis_names"),
}


def _imports(path):
    """Every import in the file as one dotted target: ``import a.b`` and
    ``from a import b`` both give ``"a.b"``."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            for alias in node.names:
                yield f"{node.module}.{alias.name}"


def _violation(target):
    parts = target.split(".")
    if parts[0] in OFF_LIMITS:
        return "outside the package"
    if parts[:2] == ["apex_tpu", "lint"]:
        return "the linter"
    if parts[:2] == ["apex_tpu", "monitor"]:
        # the bare package counts: its attributes reach the tool side
        if len(parts) == 2 or parts[2] not in EMIT_SIDE:
            return "monitor beyond the emit side"
    return None


@pytest.mark.parametrize("package", PROGRAM_PACKAGES)
def test_program_package_imports_only_the_emit_side(package):
    bad = []
    for dirpath, _, files in os.walk(os.path.join(ROOT, "apex_tpu", package)):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, ROOT)
            for target in _imports(path):
                why = _violation(target)
                if why and (rel, target) not in KNOWN_LINT_IMPORTS:
                    bad.append(f"{rel}: {target} ({why})")
    assert not bad, "\n".join(bad)


@pytest.mark.parametrize("module", ("apex_tpu.amp", "apex_tpu.serve",
                                    "apex_tpu.ops.flash_attention"))
def test_importing_the_program_loads_no_monitor_tool(module):
    code = (f"import sys, {module}\n"
            f"tools = {TOOL_SIDE!r}\n"
            "print(','.join(t for t in tools\n"
            "               if 'apex_tpu.monitor.' + t in sys.modules))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "", \
        f"import {module} loaded monitor.{{{proc.stdout.strip()}}}"
