"""What the entry points assume about the machine: one compile cache that
can be placed from outside, and a launcher whose parent holds no device."""

import inspect
import os
import re
import subprocess
import sys

import jax

from apex_tpu.parallel import multiproc
from apex_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_env_wins_and_code_sets_nothing(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_DIR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_one_place_sets_the_compile_cache_dir():
    """grep: a single ``jax_compilation_cache_dir`` update in the tree,
    in the helper, behind the env check."""
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith((".", "_chip"))
                   and d != "tests"]
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    if re.search(r"update\(\s*[\"']jax_compilation_cache_dir",
                                 f.read()):
                        hits.append(os.path.relpath(
                            os.path.join(root, name), REPO))
    assert hits == [os.path.join("apex_tpu", "utils", "compile_cache.py")]


def test_launcher_parent_never_calls_jax_and_defaults_to_one(monkeypatch):
    assert "jax" not in inspect.getsource(multiproc.main)
    started = []

    class FakeProc:
        def wait(self):
            return 0

    def fake_popen(cmd, env):
        started.append((cmd, env["JAX_NUM_PROCESSES"], env["JAX_PROCESS_ID"]))
        return FakeProc()

    monkeypatch.setattr(subprocess, "Popen", fake_popen)
    assert multiproc.main(["train.py", "--x"]) == 0
    assert started == [([sys.executable, "train.py", "--x", "--rank", "0",
                         "--world-size", "1"], "1", "0")]
    started.clear()
    assert multiproc.main(["--world-size", "3", "train.py"]) == 0
    assert [s[2] for s in started] == ["0", "1", "2"]
