"""The platform rule's one home (``apex_tpu/_compat.py``).

``on_tpu`` is the package's one ``jax.default_backend()`` call (held by
``tests/test_layering.py``); ``resolve_interpret`` is what every Pallas op
derives from it and ``ServeEngine``'s default implementations ask ``on_tpu``
itself, so steering the kernels' rule for a deviceless compile
(``tests/test_tpu_compile.py``) leaves the engine's defaults where they were.
"""

import jax
import pytest

from apex_tpu import _compat
from apex_tpu.serve import engine as engine_mod


@pytest.fixture
def no_backend(monkeypatch):
    def asked():
        raise AssertionError("asked the backend")
    monkeypatch.setattr(_compat, "on_tpu", asked)


@pytest.mark.parametrize("interpret", (True, False))
def test_an_explicit_interpret_is_kept_and_no_backend_is_asked(
        interpret, no_backend):
    assert _compat.resolve_interpret(interpret) is interpret


@pytest.mark.parametrize("on_tpu", (True, False))
def test_the_default_interprets_everywhere_but_on_a_tpu(monkeypatch, on_tpu):
    monkeypatch.setattr(_compat, "on_tpu", lambda: on_tpu)
    assert _compat.resolve_interpret(None) is (not on_tpu)


def test_on_tpu_is_the_default_backend(monkeypatch):
    assert _compat.on_tpu() is False          # tier-1 runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _compat.on_tpu() is True


@pytest.mark.parametrize("on_tpu, impls", (
    (True, ("kernel", "flash")), (False, ("reference", "reference"))))
def test_the_engines_defaults_follow_the_backend(monkeypatch, on_tpu, impls):
    monkeypatch.setattr(_compat, "on_tpu", lambda: on_tpu)
    assert engine_mod._default_impls() == impls


def test_steering_the_kernels_rule_leaves_the_engines_defaults(monkeypatch):
    monkeypatch.setattr(_compat, "resolve_interpret",
                        lambda interpret: bool(interpret))
    assert engine_mod._default_impls() == ("reference", "reference")
