"""apex_tpu.monitor — structured training telemetry for TPU training.

The observability subsystem the reference never had on TPU: a typed-event
:class:`Recorder` (counters, gauges, timers, per-step records in a ring
buffer, JSONL/JSON output, crash-resilient ``stream=`` incremental
flush), instrumentation hooks threaded through amp, optimizers, the
collective mappings, the pipeline schedules and the data loader, a
trace layer subsuming ``apex_tpu.pyprof`` (XProf annotations,
compile-event and jit-cache logging, device-memory snapshots), a
cross-host merge layer (``monitor.merge``: rank-tagged shards +
``python -m apex_tpu.monitor merge`` + in-mesh ``allgather_summaries``),
a training-health :class:`Watchdog` (``monitor.health``: NaN/overflow-
storm/divergence/plateau/starvation/straggler detection as typed
``health_event`` records), per-module cost attribution
(``monitor.profile``: :func:`scope` tags; ``monitor.attribution``: the
analytic jaxpr attributor + measured wall-time sampling,
``python -m apex_tpu.monitor profile``), request-level span
tracing + O(1)-memory log-scale latency histograms (``monitor.spans``:
the serve SLO evidence layer — per-request queue-wait/prefill/decode
traces with preempt/re-admit annotations, rendered as the ``serve``
block of the report), a pull-based Prometheus text-exposition endpoint
(``monitor.export``: lazily imported, ``python -m apex_tpu.monitor
export``), MFU/goodput accounting (``monitor.attribution.mfu`` over the
analytic FLOPs walk + a per-device-kind peak table), the unified
memory surface (``monitor.memory``: compiled-footprint attribution,
the analytic high-water walk charged per ``apx:`` scope, the live
:class:`MemorySampler` HBM timeline, ZeRO/serve capacity reports and
the tuner's ``vmem_calibration`` feedback loop,
``python -m apex_tpu.monitor memory``), a crash-safe flight recorder
(``monitor.flight``: SIGTERM/SIGINT/atexit/fatal-watchdog triggers dump
the ring tail + open-span stack atomically to rank-tagged
``flight-<rank>.jsonl`` black boxes), a Chrome-trace/Perfetto exporter
(``monitor.timeline``: shards + flight dumps fused into one cross-rank
timeline with clock alignment and a straggler overlay,
``python -m apex_tpu.monitor timeline``), and a CLI report
(``python -m apex_tpu.monitor report run.jsonl``).

Quick start::

    from apex_tpu import monitor

    rec = monitor.Recorder()
    monitor.trace.install_compile_logging()      # optional: compile events
    with monitor.attached(rec):                  # enables package hooks
        for batch in loader:
            with rec.step():
                state = train_step(state, batch)
    rec.dump_jsonl("run.jsonl")                  # → monitor report CLI
    print(monitor.render_report(rec.records()))

Guarantees (details: docs/observability.md):

- **disabled = free**: with no recorder attached every hook is one
  global read + compare; traced programs are byte-identical to the
  uninstrumented ones (no inserted ops, no retrace).
- **attach = one retrace**: hot paths that thread the monitoring
  guard (``amp.make_train_step``, the stateful optimizer ``step``)
  switch between two cached programs — instrumented/uninstrumented —
  so a flip costs at most one trace and cycles never grow the cache.
- **zero deps**: importing this package (and recording host events)
  touches no jax; jax is imported lazily by the traced hooks and the
  trace layer (APX001-clean).
"""

from __future__ import annotations

import contextlib

from apex_tpu.monitor import _state
from apex_tpu.monitor import hooks  # noqa: F401
from apex_tpu.monitor import profile  # noqa: F401
from apex_tpu.monitor import spans  # noqa: F401
from apex_tpu.monitor.hooks import enabled, epoch  # noqa: F401
from apex_tpu.monitor.profile import scope  # noqa: F401
from apex_tpu.monitor.recorder import Recorder  # noqa: F401
from apex_tpu.monitor.spans import LogHistogram  # noqa: F401

# The emit side above is all the program packages import (held by
# tests/test_layering.py). The tool side loads on first use: a process
# that only trains or serves never pays for a module that reads dumps,
# and never for http.server (tests/test_export.py).
_LAZY_MODULES = ("attribution", "export", "flight", "health", "memory",
                 "merge", "report", "timeline", "trace", "xprof")
_LAZY_NAMES = {
    "Watchdog": "health", "MemorySampler": "memory",
    **{name: "report" for name in (
        "aggregate", "load_jsonl", "render_cross_host", "render_memory", "render_report", "render_serve", "render_steps",
        "selfcheck")},
}


def __getattr__(name: str):
    import importlib
    if name in _LAZY_MODULES:
        return importlib.import_module(f"apex_tpu.monitor.{name}")
    if name in _LAZY_NAMES:
        value = getattr(importlib.import_module(
            f"apex_tpu.monitor.{_LAZY_NAMES[name]}"), name)
        globals()[name] = value
        return value
    raise AttributeError(f"module 'apex_tpu.monitor' has no attribute "
                         f"{name!r}")


def get_recorder() -> Recorder | None:
    """The attached recorder, or None when monitoring is disabled."""
    return _state.recorder


def attach(recorder: Recorder) -> Recorder:
    """Enable monitoring: route all package hooks to ``recorder``.

    Guard-threaded jitted steps pick up the instrumentation on their
    next call (at most one trace per guard flip); attach before first
    use of other jitted code if you want its trace-time events
    (collective accounting) captured. Device callbacks route to
    whichever recorder is attached when a program runs.
    """
    _state.recorder = recorder
    _state.epoch += 1
    return recorder


def detach() -> Recorder | None:
    """Disable monitoring; returns the previously attached recorder."""
    rec, _state.recorder = _state.recorder, None
    _state.epoch += 1
    return rec


@contextlib.contextmanager
def attached(recorder: Recorder):
    """``with monitor.attached(rec): ...`` — attach for the block."""
    prev = _state.recorder
    attach(recorder)
    try:
        yield recorder
    finally:
        if prev is None:
            detach()
        else:
            attach(prev)
