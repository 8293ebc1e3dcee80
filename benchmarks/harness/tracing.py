"""The traced run's tools: the recorder of the program's own spans and
counters, host annotations on the profiler's timeline, and a profiler
session over a short steady sub-window. A run with ``--trace 0`` touches
none of this."""

from __future__ import annotations

import contextlib
import os
import shutil

#: the host annotations the harness writes, by which idle gaps are named
ANNOTATIONS = ("dispatch", "fetch-loss", "serve-step", "admit")


class Tracer:
    """``on=False`` makes every method a no-op, so the measured loop is the
    same code with and without tracing."""

    def __init__(self, on: bool, logdir: str):
        self.on = bool(on)
        self.logdir = logdir
        self.recorder = None
        self.active = False
        self.done = False
        self._null = contextlib.nullcontext()

    # -- the program's spans and counters -------------------------------------
    def attach_recorder(self):
        """A host-only recorder (``traced_hooks=False``: the compiled
        programs stay as they are)."""
        from apex_tpu import monitor
        self.recorder = monitor.Recorder(name="benchmark", capacity=4_000_000,
                                         traced_hooks=False)
        monitor.attach(self.recorder)
        return self.recorder

    def detach_recorder(self):
        if self.recorder is not None:
            from apex_tpu import monitor
            monitor.detach()

    def mark(self, name: str):
        """A marker event in the recorder's ring: the readers take the
        events between ``window-start`` and ``window-end``."""
        if self.recorder is not None:
            self.recorder.emit("benchmark", name, None)

    # -- host annotations -------------------------------------------------------
    def annotate(self, name: str):
        if not self.on:
            return self._null
        import jax
        return jax.profiler.TraceAnnotation(name)

    # -- the profiler session -----------------------------------------------------
    def start(self):
        import jax
        shutil.rmtree(self.logdir, ignore_errors=True)
        os.makedirs(self.logdir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the Python tracer slows the host
        opts.host_tracer_level = 2       # TraceAnnotation events
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self.active = True

    def stop(self):
        import jax
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def reduce(self) -> dict:
        from . import trace_reduce
        path = trace_reduce.newest_xplane(self.logdir)
        out = trace_reduce.reduce_file(path, ANNOTATIONS)
        out["xplane_bytes"] = os.path.getsize(path)
        return out
