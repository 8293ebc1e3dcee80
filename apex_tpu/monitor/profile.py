"""Profile scopes: the emit side of per-module cost attribution.

``apex`` ships ``pyprof`` because "is it faster" is unanswerable without
per-layer attribution; the trace layer (``monitor.trace``) records *that*
time was spent, the scopes here record *where*.

:func:`scope` tags a region of (possibly traced) code with a profile scope
name. Inside a trace it pushes a ``jax.named_scope`` carrying the ``apx:``
prefix, so every equation traced under it is attributable; at the host
level (and under ``attribution.measured_profile``'s eager mode) it also
times the block through the existing recorder timer events
(``profile/<path>``). Scopes nest: the innermost enclosing scope is charged.
The package threads scopes through the TP layers, the amp and zero train
steps, the pipeline ticks and the Pallas ops, so a stock train step is
attributable out of the box.

The tools that READ the scopes (the analytic jaxpr walk, the measured
profile, the MFU table, ``python -m apex_tpu.monitor profile``) are
:mod:`apex_tpu.monitor.attribution`, loaded on first use. This module sits
below the program: it imports ``monitor._state`` and the standard library,
nothing else, at any depth (``tests/test_layering.py``).

Purity contract (same as the rest of ``monitor``): ``scope`` inserts
**no operations** — ``jax.named_scope`` only annotates equation
metadata, so the jaxpr of a scoped program is byte-identical to the
unscoped one, recorder attached or not (asserted by
``tests/test_profile.py``). With no recorder attached and jax not
imported, ``scope`` is a stack push/pop and nothing else.
"""

from __future__ import annotations

import contextlib
import sys
import threading

from apex_tpu.monitor import _state

# named-scope prefix marking OUR scopes: flax module scopes and user
# jax.named_scope calls share the same name stack, and the attributor
# must only credit regions the profile vocabulary claimed
SCOPE_PREFIX = "apx:"

UNSCOPED = "(unscoped)"

_local = threading.local()


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def current_scope() -> str:
    """The host-side scope path at the call site ('' outside any)."""
    return "/".join(_stack())


@contextlib.contextmanager
def scope(name: str):
    """Tag a region for per-module cost attribution.

    ``name`` is one path component (no '/'; slashes are folded to '_').
    Nesting builds the path: ``scope("attn")`` inside ``scope("amp_grad")``
    attributes to ``amp_grad/attn``. Safe everywhere: inside jit traces
    it annotates metadata only (jaxpr-pure); at host level it times the
    block when a recorder is attached and measuring is armed
    (``attribution.measured_profile``); with jax not even imported it degrades
    to a plain stack push.
    """
    name = str(name).replace("/", "_")
    st = _stack()
    st.append(name)
    try:
        jax = sys.modules.get("jax")
        cm = (jax.named_scope(SCOPE_PREFIX + name) if jax is not None
              else contextlib.nullcontext())
        rec = _state.recorder
        if rec is not None and getattr(_local, "measure", False):
            with cm, rec.timer("profile/" + "/".join(st)):
                yield
        else:
            with cm:
                yield
    finally:
        st.pop()


@contextlib.contextmanager
def measuring():
    """Arm per-scope host timing for the block (used by
    :func:`attribution.measured_profile`; composable for custom loops)."""
    prev = getattr(_local, "measure", False)
    _local.measure = True
    try:
        yield
    finally:
        _local.measure = prev


def scoped(name: str):
    """Decorator form of :func:`scope`."""
    def deco(fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco
