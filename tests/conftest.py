"""Test harness config: force an 8-device virtual CPU mesh.

Mirrors the reference's testing doctrine (SURVEY §4): distributed code
paths are exercised in CI without real multi-chip hardware — apex fakes
multi-node at world_size=1 over NCCL
(``apex/transformer/tensor_parallel/tests/commons.py:45-78``); here we
fake an 8-chip mesh with XLA host devices, which runs the *real* collective
code.
"""

import os

# Force CPU: tests must exercise the 8-device virtual mesh, never a
# real chip.
os.environ["JAX_PLATFORMS"] = "cpu"
# No persistent compile cache under test, here or in the subprocesses
# tests start (chip_smoke.py, the examples and the monitor/ops mains enable
# it): a compile for a described-but-absent chip (test_tpu_compile.py)
# is written to it but cannot be read back.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import tempfile

# Autotune isolation: the kernels' default policy is autotune="cache",
# so a developer's user-level cache (~/.cache/apex_tpu/tune, written by
# `python -m apex_tpu.ops tune`) would otherwise leak tuned blocks into
# every test that asserts heuristic-default tilings/warnings. Point the
# whole suite at a fresh empty dir; cache-exercising tests monkeypatch
# their own over it.
os.environ["APEX_TPU_TUNE_CACHE"] = tempfile.mkdtemp(
    prefix="apex_tpu_test_tune_")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")


import importlib.util  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def chip_smoke():
    """``chip_smoke.py`` (a root script, not a package member) as a module:
    its phase functions and program builders, for the CPU rehearsal and the
    described-chip compile tests."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod    # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod
