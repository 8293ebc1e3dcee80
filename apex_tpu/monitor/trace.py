"""Trace layer: annotations, XProf sessions, compile events, memory.

This subsumes ``apex_tpu.pyprof`` (which is now a thin re-export shim):

- :func:`annotate` / :func:`wrap` / :func:`init` — the NVTX-parity
  surface (``apex/pyprof/nvtx/nvmarker.py``): ``jax.named_scope`` tags
  the HLO (per-op in XProf), ``jax.profiler.TraceAnnotation`` tags the
  host timeline. When a recorder is attached, ``wrap`` also times the
  wrapped call as a host timer event.
- :func:`trace` — capture an XProf session (the nvprof-session analog);
  feed the logdir to :mod:`apex_tpu.monitor.xprof` or the CLI report.
- :func:`cost_analysis` / :func:`flop_report` — XLA's own FLOP/byte
  accounting for a compiled program (the ``pyprof.prof`` analog).
- :func:`install_compile_logging` — registers ``jax.monitoring``
  listeners once; afterwards every jaxpr trace, MLIR lowering and
  backend compile (plus compilation-cache hits/misses) is recorded into
  whichever recorder is attached at the time it happens. Idempotent,
  and a no-op while monitoring is disabled (the listener checks the
  guard per event).
- :func:`device_memory_snapshot` / :func:`memory_analysis` —
  DEPRECATED re-export shims over :mod:`apex_tpu.monitor.memory`, the
  one memory surface (compiled footprints, analytic high water, the
  live HBM sampler).

All jax imports are deferred to call time: importing this module (and
therefore ``apex_tpu.monitor``) does no jax work (APX001 discipline).
"""

from __future__ import annotations

import contextlib
import functools
import json

from apex_tpu.monitor import _state

# jax.monitoring event keys worth surfacing (jax/_src/dispatch.py and
# jax/_src/compilation_cache.py); durations are recorded as timer
# events under the mapped name, point events as counters.
_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax/compile/trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax/compile/lower",
    "/jax/core/compile/backend_compile_duration": "jax/compile/backend",
}
_POINT_EVENTS = {
    "/jax/compilation_cache/cache_misses": "jax/compile/cache_miss",
    "/jax/compilation_cache/cache_hits": "jax/compile/cache_hit",
}

_compile_logging_installed = False


def init(enable: bool = True):
    """Parity shim for ``pyprof.nvtx.init()``: JAX needs no global
    patching — annotation is opt-in via :func:`annotate`/:func:`wrap`."""
    return enable


@contextlib.contextmanager
def annotate(name: str, **metadata):
    """Named range visible in the XProf host timeline and HLO op names.

    The named scope rides :func:`apex_tpu.monitor.profile.scope`, so an
    ``annotate``-tagged region also appears as a row in the per-module
    cost attribution table (``monitor.attribution.analytic_profile``)."""
    import jax
    from apex_tpu.monitor import profile as _profile
    payload = name if not metadata else \
        f"{name}|{json.dumps(metadata, default=str)}"
    with jax.profiler.TraceAnnotation(payload):
        with _profile.scope(name):
            yield


def _describe_args(args, kwargs):
    def one(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return f"{x.dtype}{list(x.shape)}"
        return type(x).__name__
    return {
        "args": [one(a) for a in args],
        "kwargs": {k: one(v) for k, v in kwargs.items()},
    }


def wrap(fn, name: str | None = None):
    """Decorate ``fn`` with an annotation carrying the op name and arg
    shapes (the ``add_wrapper`` payload, ``nvmarker.py:206``); with a
    recorder attached the call is also timed as ``trace/<name>``."""
    label = name or getattr(fn, "__name__", "fn")

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        rec = _state.recorder
        with annotate(label, **_describe_args(args, kwargs)):
            if rec is None:
                return fn(*args, **kwargs)
            with rec.timer(f"trace/{label}"):
                return fn(*args, **kwargs)

    return wrapper


@contextlib.contextmanager
def trace(logdir: str, create_perfetto_link: bool = False):
    """Capture an XProf trace of the block (the nvprof-session analog);
    parse with :mod:`apex_tpu.monitor.xprof` or view in TensorBoard."""
    import jax
    jax.profiler.start_trace(logdir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# XLA cost accounting (the pyprof.prof analog)
# ---------------------------------------------------------------------------

def cost_analysis(fn, *args, **kwargs) -> dict:
    """Compile ``fn`` and return XLA's cost analysis dict
    (``flops``, ``bytes accessed``, per-memory-space breakdowns)."""
    import jax
    lowered = jax.jit(fn).lower(*args, **kwargs)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca) if ca else {}


def flop_report(fn, *args, step_time_s: float | None = None,
                peak_flops: float | None = None, **kwargs) -> dict:
    """FLOPs/bytes + arithmetic intensity (+ MFU when timings given) —
    the summary ``pyprof.prof`` prints per kernel, at whole-program
    granularity."""
    ca = cost_analysis(fn, *args, **kwargs)
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    rep = {
        "flops": flops,
        "bytes_accessed": byts,
        "arithmetic_intensity": flops / byts if byts else float("inf"),
    }
    if step_time_s:
        rep["achieved_flops_per_s"] = flops / step_time_s
        if peak_flops:
            rep["mfu"] = flops / step_time_s / peak_flops
    return rep


# ---------------------------------------------------------------------------
# compile-event and jit-cache logging
# ---------------------------------------------------------------------------

def install_compile_logging() -> bool:
    """Register ``jax.monitoring`` listeners feeding the attached
    recorder. Install once per process (idempotent); events arriving
    while no recorder is attached are discarded by the listener, so the
    disabled-mode guarantee holds. Returns True when the listeners are
    (now) installed."""
    global _compile_logging_installed
    if _compile_logging_installed:
        return True
    import jax.monitoring as jmon

    def on_duration(event: str, duration: float, **kw):
        rec = _state.recorder
        if rec is None:
            return
        name = _DURATION_EVENTS.get(event)
        if name is not None:
            rec.timer_event(name, float(duration))

    def on_event(event: str, **kw):
        rec = _state.recorder
        if rec is None:
            return
        name = _POINT_EVENTS.get(event)
        if name is not None:
            rec.counter(name)

    jmon.register_event_duration_secs_listener(on_duration)
    jmon.register_event_listener(on_event)
    _compile_logging_installed = True
    return True


def compile_seconds(recorder=None) -> float:
    """Total backend-compile seconds accumulated in ``recorder`` (or the
    attached one) since it was created — the compile-vs-steady split the
    bench embeds. Requires :func:`install_compile_logging`."""
    rec = recorder if recorder is not None else _state.recorder
    if rec is None:
        return 0.0
    return float(rec.counters().get("jax/compile/backend/total_s", 0.0))


# ---------------------------------------------------------------------------
# memory — moved to apex_tpu.monitor.memory (thin re-export shims)
# ---------------------------------------------------------------------------

def device_memory_snapshot(devices=None) -> list[dict]:
    """DEPRECATED location: use
    :func:`apex_tpu.monitor.memory.device_memory_snapshot` — the ONE
    memory surface (the pyprof/xentropy re-export precedent). This shim
    delegates; new callers get the extended rows (nominal degradation
    on stats-less backends, limit/utilization, the headline
    ``memory/hbm_bytes_in_use`` gauge)."""
    from apex_tpu.monitor import memory as _memory
    return _memory.device_memory_snapshot(devices)


def memory_analysis(fn, *args, **kwargs) -> dict:
    """DEPRECATED location: use
    :func:`apex_tpu.monitor.memory.compiled_memory_profile` — same
    compiled breakdown plus the ``total_bytes`` envelope and the
    ``record=`` path into ``report.aggregate()["memory"]``. This shim
    delegates (key set is a superset of the historical one)."""
    from apex_tpu.monitor import memory as _memory
    return _memory.compiled_memory_profile(fn, *args, **kwargs)
