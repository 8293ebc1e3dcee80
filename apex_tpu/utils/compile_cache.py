"""One persistent XLA compile cache for every entry point of the repo.

A cold full-width GPT train step is ~a minute of compilation, paid again
by every process and every chip-tool call unless the processes share
JAX's persistent compilation cache. The directory is part of each
entry's key, so a cache that moves never hits: entry points call
:func:`enable` and the directory is

- whatever ``JAX_COMPILATION_CACHE_DIR`` says when it is set — JAX reads
  that variable itself, so nothing is set in code and no code path can
  point the process anywhere else;
- otherwise ``<checkout>/.jax_cache``, derived from this package's
  location (git-ignored) — never a temp dir, a pid or a timestamp.

Library code never calls this; only ``__main__``-style entry points do
(``chip_smoke.py``, ``benchmarks/run.py``, the examples, ``python -m
apex_tpu.ops``, ``python -m apex_tpu.monitor profile|memory``). The test
suite turns the cache off (``tests/conftest.py``): a compile for a
described-but-absent chip is written but cannot be read back.

No JAX work at import (APX001).
"""

from __future__ import annotations

import os

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

#: the fixed in-checkout default (the directory that holds ``apex_tpu/``)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Point this process at the shared compile cache; returns the
    directory in use. Call before the first compilation."""
    env = os.environ.get(ENV_DIR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
