"""The benchmark's own tests. Run by hand, from the root of a checkout:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They run on the CPU (four virtual devices, Pallas kernels interpreted) at
tiny sizes: they check the harness's arithmetic and control flow, never a
speed. The repo's tier-1 command does not collect them."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="session")
def _no_compile_cache():
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    yield
