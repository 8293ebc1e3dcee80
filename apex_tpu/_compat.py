"""The three JAX spellings, and the one platform rule, the package routes
through one place.

Written for the one installation there is (jax 0.9): Pallas TPU
compiler params are ``pltpu.CompilerParams``, ``shard_map`` is
``jax.shard_map`` with ``check_vma``, and the bound-axis size is
``jax.lax.axis_size``. The names stay because every kernel and every
``shard_map`` call site imports them from here — the next rename is a
one-file edit — but there is no branch for an API the installed JAX
lacks: if one of them moves, the failure is an ``AttributeError`` at the
call, not a silent fallback.

``on_tpu`` is the one question "which backend" (``ServeEngine``'s default
implementations ask it), and ``resolve_interpret`` the rule every Pallas op
derives from it: "not on a TPU means interpret mode". Callers reach the rule
as ``_compat.resolve_interpret`` (the module's attribute, looked up at call
time), so that a deviceless compile for a described chip steers every kernel
from that one name and nothing else.

Import-time rule (enforced by ``apex_tpu.lint`` APX001): nothing here
constructs a JAX object or touches a backend at import.
"""

from __future__ import annotations

from typing import Any


def tpu_compiler_params(**kwargs: Any):
    """``pltpu.CompilerParams(**kwargs)``.

    Call it inside the function that issues the ``pallas_call`` — never at
    module level (APX001).
    """
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


def axis_size(axis_name):
    """``jax.lax.axis_size(axis_name)`` (a Python int at trace time;
    ``NameError`` on an unbound axis)."""
    import jax

    return jax.lax.axis_size(axis_name)


def shard_map(f, mesh=None, in_specs=None, out_specs=None, **kwargs):
    """``jax.shard_map`` taking mesh/in_specs/out_specs positionally,
    as the package's call sites pass them (``jax.shard_map`` itself is
    keyword-only after ``f``)."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def on_tpu() -> bool:
    """Whether the default backend is a TPU: the package's one
    ``jax.default_backend()`` call."""
    import jax

    return jax.default_backend() == "tpu"


def resolve_interpret(interpret):
    """``interpret`` if given, else whether the default backend is not a
    TPU (a Pallas kernel then runs in interpret mode)."""
    return interpret if interpret is not None else not on_tpu()
