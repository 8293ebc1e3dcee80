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

Inside the program three more seams are held the same way: what a train
step imports loads no serving kernel (``ops/paged_attention.py``) and no
``apex_tpu.serve``; ``ops/flash_attention.py`` is imported for attention and
not for a rule (the platform rule's one home is ``_compat.py``); and
``monitor/profile.py``, which every program module imports for ``scope``,
imports nothing of the package but ``monitor/_state``.
"""

import ast
import functools
import importlib
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROGRAM_PACKAGES = ("amp", "data", "models", "ops", "optimizers",
                    "parallel", "serve", "transformer", "tune", "zero")
EMIT_SIDE = {"_state", "hooks", "spans", "profile", "flight"}
TOOL_SIDE = ("report", "health", "memory", "merge", "timeline", "trace",
             "xprof", "export", "attribution")
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


def _package_files(*subdirs):
    for sub in subdirs:
        for dirpath, _, files in os.walk(os.path.join(ROOT, "apex_tpu", sub)):
            for fname in sorted(files):
                if fname.endswith(".py"):
                    yield os.path.join(dirpath, fname)


def _parse(path):
    with open(path) as f:
        return ast.parse(f.read(), filename=path)


def _imports(path):
    """Every import in the file as one dotted target: ``import a.b`` and
    ``from a import b`` both give ``"a.b"``."""
    for node in ast.walk(_parse(path)):
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
    for path in _package_files(package):
        rel = os.path.relpath(path, ROOT)
        for target in _imports(path):
            why = _violation(target)
            if why and (rel, target) not in KNOWN_LINT_IMPORTS:
                bad.append(f"{rel}: {target} ({why})")
    assert not bad, "\n".join(bad)


@functools.lru_cache(maxsize=None)
def _loaded_by(*modules):
    """For each of ``modules``, the ``apex_tpu`` modules ONE fresh
    interpreter holds once it has imported that module and those before it
    (a line a module; whichever tests ask, the interpreter runs once)."""
    code = ("import importlib, sys\n"
            f"for name in {modules!r}:\n"
            "    importlib.import_module(name)\n"
            "    print(','.join(m for m in sys.modules\n"
            "                   if m.startswith('apex_tpu')))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return {m: frozenset(line.split(","))
            for m, line in zip(modules, proc.stdout.splitlines())}


#: what cells 1, 2, 4 and 8 (the train cells) import, in the order one
#: interpreter imports it: a module that loads serving code fails, and so do
#: those after it
TRAIN_SIDE = ("apex_tpu.ops.flash_attention", "apex_tpu.amp",
              "apex_tpu.models.gpt", "apex_tpu.models.bert",
              "apex_tpu.models.mellum")


def _loaded(module):
    if module in TRAIN_SIDE:
        return _loaded_by(*TRAIN_SIDE)[module]
    return _loaded_by(module)[module]


@pytest.mark.parametrize("module", ("apex_tpu.amp", "apex_tpu.serve",
                                    "apex_tpu.ops.flash_attention"))
def test_importing_the_program_loads_no_monitor_tool(module):
    tools = sorted(t for t in TOOL_SIDE
                   if "apex_tpu.monitor." + t in _loaded(module))
    assert not tools, f"import {module} loaded monitor.{{{','.join(tools)}}}"


@pytest.mark.parametrize("module", TRAIN_SIDE)
def test_importing_the_train_side_loads_no_serving_code(module):
    serving = sorted(m for m in _loaded(module)
                     if m == "apex_tpu.ops.paged_attention"
                     or m.split(".")[:2] == ["apex_tpu", "serve"])
    assert not serving, f"import {module} loaded {serving}"


#: the serving kernels' names that callers import, and the two jitted calls
#: whose names the benchmark's readers find in a trace: ``ops/paged_attention.py``
#: holds them, and ``ops/flash_attention.py`` (which the train cells run) no
#: name of their kind
PAGED_NAMES = (
    "paged_decode_attention", "paged_attention_reference",
    "paged_kv_write_rows", "paged_kv_write_pages",
    "_paged_decode_call", "_write_rows_call")


def test_the_paged_kernels_have_one_home():
    paged = importlib.import_module("apex_tpu.ops.paged_attention")
    # ``apex_tpu.ops.flash_attention`` the attribute is the function
    flash = importlib.import_module("apex_tpu.ops.flash_attention")
    missing = [n for n in PAGED_NAMES if not hasattr(paged, n)]
    assert not missing, missing
    both = [n for n in vars(flash)
            if any(mark in n for mark in ("paged", "_write_", "_kv_write"))]
    assert not both, both


def _flash_attention_reached_for_a_rule():
    """Imports of ``ops.flash_attention`` in ``ops/`` and ``zero/`` that take
    anything but attention or a reference of it, or hide in a function."""
    public = {"flash_attention", "mha_reference", "dropout_keep_reference"}
    home = "apex_tpu.ops.flash_attention"
    for path in _package_files("ops", "zero"):
        tree = _parse(path)
        top_level = set(map(id, tree.body))
        rel = os.path.relpath(path, ROOT)
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names if a.name == home]
                taken = names          # the module itself: every private name
            elif isinstance(node, ast.ImportFrom) and node.module == home:
                names = [a.name for a in node.names]
                taken = [n for n in names if n not in public]
            else:
                continue
            if taken:
                yield f"{rel}:{node.lineno}: takes {taken} of {home}"
            if names and id(node) not in top_level:
                yield f"{rel}:{node.lineno}: imports {home} in a function"


def _default_backend_outside_compat():
    for path in _package_files(""):
        rel = os.path.relpath(path, ROOT)
        if rel == os.path.join("apex_tpu", "_compat.py"):
            continue
        for node in ast.walk(_parse(path)):
            if isinstance(node, ast.Call) and "default_backend" in (
                    getattr(node.func, "attr", None),
                    getattr(node.func, "id", None)):
                yield (f"{rel}:{node.lineno}: asks the backend itself "
                       "(_compat.on_tpu is the one call)")


def _profile_imports_beyond_state():
    path = os.path.join(ROOT, "apex_tpu", "monitor", "profile.py")
    for target in _imports(path):
        if (target != "apex_tpu.monitor._state"
                and target.split(".")[0] not in sys.stdlib_module_names):
            yield f"apex_tpu/monitor/profile.py: {target}"


@pytest.mark.parametrize("found", (
    _flash_attention_reached_for_a_rule, _default_backend_outside_compat,
    _profile_imports_beyond_state), ids=lambda f: f.__name__.strip("_"))
def test_a_decision_has_one_home(found):
    bad = list(found())
    assert not bad, "\n".join(bad)
