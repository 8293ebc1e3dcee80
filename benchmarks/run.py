"""The benchmark's one command.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, no ``PYTHONPATH``, no network. One process:
checks the device (no TPU, an unknown ``device_kind`` or too few chips is
exit code 2 and no result line), points JAX at the compile cache, builds the
cell's weights on the device from ``--seed``, warms the cell's own shapes,
checks the program against the plain reference, measures for ``--seconds``
and prints, as the LAST line of stdout, the one result object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``). Every earlier line is a JSON detail line that names the
platform, ``device_kind`` and device count.

``--trace 0`` reports the cell's end-to-end metrics with the profiler and
the recorder detached. ``--trace 1`` attaches the program's recorder, wraps
the harness's own calls in ``jax.profiler.TraceAnnotation``, takes a
profiler trace of a short steady stretch (serving: a few seconds inside the
window; training: a few steps right after it) and reports the cell's
per-layer metrics, each computed by ``benchmarks/layer_metrics/<reader>.py``
from the ``run`` dict assembled below (the reader is the metric's name up to
its first ``.``).

Everything that belongs to one cell is data found by name (see
``harness/manifest.py``): this file has no list of cells, configurations,
traffic mixes or metrics.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class CompileWatch:
    """Counts backend compilations with the time of each, on
    ``jax.monitoring`` (cheap, always on): nothing may compile inside the
    measured window, traced or not. The one counter of compilations: it
    decides ``correct`` and is what ``compiles_in_window`` reports."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as jmon
        self.times = []
        jmon.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.times.append((time.perf_counter(), float(duration)))

    def between(self, lo, hi):
        return sum(1 for t, _ in self.times if lo <= t <= hi)

    def seconds(self):
        return sum(d for _, d in self.times)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="benchmarks/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = p.parse_args(argv)

    from benchmarks.harness import manifest, peaks

    man = manifest.load_manifest()
    cell = manifest.find_workload(man, args.workload)

    # the program's own helper: $JAX_COMPILATION_CACHE_DIR when set, else
    # <checkout>/.jax_cache -- a fixed path, so the second run of a cell in
    # a checkout finds every program
    from apex_tpu.utils import compile_cache
    cache_dir = compile_cache.enable()
    import jax
    # small programs (init, the ring, the reference) are cached too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        print(f"benchmark: JAX came up on {dev['platform']!r}, not a TPU; "
              f"there is no CPU fallback", file=sys.stderr)
        return 2
    try:
        peak = peaks.peak_for(dev["kind"])
    except peaks.UnknownDevice as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    chips = int(cell["chips"])
    if len(devs) < chips:
        print(f"benchmark: cell {cell['name']!r} needs {chips} chips, JAX "
              f"reports {len(devs)}", file=sys.stderr)
        return 2
    return run_cell(ROOT, man, cell, args.seed, args.seconds,
                    bool(args.trace), dev, peak, devs[:chips], cache_dir)


def run_cell(root, man, cell, seed, seconds, trace_on, dev, peak, used,
             cache_dir=None, emit=print) -> int:
    """One cell on the devices ``used``; ``main`` has already held them to
    the TPU gate. ``benchmarks/tests`` rehearse this function at a tiny size
    on the CPU, from a temporary ``root``; the command never gets here
    without a TPU."""
    import jax
    from benchmarks.harness import manifest
    from benchmarks.harness.tracing import Tracer

    bench_dir = os.path.join(root, man["paths"][0])
    config = manifest.load_config(man, cell["config"], root)
    traffic = manifest.load_traffic(cell["traffic"], bench_dir)
    family = manifest.load_family(config["family"])
    e2e_names = [m["name"] for m in
                 manifest.metrics_for(man, "end_to_end", cell["name"])]
    layer_metrics = manifest.metrics_for(man, "per_layer", cell["name"])
    readers = {m["name"]: manifest.load_layer_metric(m["name"], bench_dir)
               for m in layer_metrics}
    units = {m["name"]: m["unit"]
             for m in man["end_to_end"] + man["per_layer"]}
    chips = len(used)

    def log(phase, **fields):
        emit(json.dumps({"phase": phase, "platform": dev["platform"],
                         "device_kind": dev["kind"],
                         "device_count": dev["count"], **fields},
                        default=str), flush=True)

    log("start", workload=cell["name"], config=config["name"],
        traffic=cell["traffic"], chips=chips, seed=seed,
        seconds=seconds, trace=int(trace_on), jax=jax.__version__,
        compile_cache_dir=cache_dir)

    t_imported = time.perf_counter()
    watch = CompileWatch()
    tracer = Tracer(trace_on,
                    os.path.join(root, ".bench_trace", cell["name"]))
    rec = tracer.attach_recorder() if tracer.on else None

    # -- set-up: weights, programs, reference check ------------------------------
    kind = traffic["kind"]
    if kind == "train":
        from benchmarks.harness.train import run_train as run_window
    elif kind == "serve":
        from benchmarks.harness.serve import run_serve as run_window
    else:
        raise SystemExit(f"traffic kind {kind!r}: no runner")
    prog = getattr(family, f"build_{kind}")(config, traffic, seed)
    t_built = time.perf_counter()
    log("built", seconds=t_built - T_PROCESS_START,
        memory=getattr(prog, "memory", None),
        layout=getattr(prog, "layout", None),
        info=getattr(prog, "info", None),
        notes=getattr(prog, "notes", None),
        imports_and_device_s=t_imported - T_PROCESS_START,
        backend_compiles=len(watch.times),
        backend_compile_s=watch.seconds())
    check = prog.check()
    check["ok"] = bool(check["finite"]
                       and check["rel_err"] <= check["tolerance"])
    log("reference-check", seconds=time.perf_counter() - t_built, **check)

    # -- the window ----------------------------------------------------------------
    res = run_window(prog, traffic, seed, seconds, tracer, log)
    t_lo = res["t_setup_end"]
    t_hi = t_lo + res["window_s"]
    setup_s = t_lo - T_PROCESS_START
    compiles = watch.between(t_lo, t_hi)
    stats = [d.memory_stats() or {} for d in used]
    peak_bytes = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    device = {**dev, "memory_peak_bytes": peak_bytes}
    log("window", window_s=res["window_s"], setup_s=setup_s,
        compiles_in_window=compiles, backend_compiles=len(watch.times),
        backend_compile_s=watch.seconds(),
        peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats],
        end_to_end=res["end_to_end"])

    correct = bool(res["correct"] and check["ok"] and compiles == 0)
    end_to_end = {**res["end_to_end"], "setup_s": setup_s}
    missing = [n for n in e2e_names if n not in end_to_end]
    if missing:
        log("missing-metrics", names=missing,
            why="the runner reported none of that name: see the traffic "
                "file's rate_metric")
        correct = False

    def result(metrics, **more):
        emit(json.dumps({"correct": correct, "attempted": res["attempted"],
                         "failed": res["failed"], "metrics": metrics,
                         "device": device, **more}), flush=True)
        return 0

    if not tracer.on:
        return result({n: {"value": end_to_end[n], "unit": units[n]}
                       for n in e2e_names if n in end_to_end})

    # -- traced run: per-layer metrics from spans, counters and the trace ---------
    tracer.detach_recorder()
    t_red = time.perf_counter()
    trace = tracer.reduce() if tracer.done else None
    if trace is not None and not trace["devices"]:
        trace = None                # no device operation is in the trace
    events = rec.records()
    marks = [i for i, e in enumerate(events) if e["kind"] == "benchmark"]
    run = {
        "workload": cell["name"], "kind": kind, "chips": chips,
        "device_kind": dev["kind"], "peak": peak,
        "window_s": res["window_s"], "end_to_end": end_to_end,
        "tokens": res["tokens"], "program": prog, "traced": res["traced"],
        "trace": trace, "compiles_in_window": compiles,
        "counters": rec.counters(),
        "window_events": events[marks[0] + 1:marks[-1]] if len(marks) >= 2
        else [],
        "decode_step_times": res.get("decode_step_times"),
        "notes": {},
    }
    metrics = {}
    for m in layer_metrics:
        if m["moves"] not in end_to_end:
            continue
        value = readers[m["name"]].compute(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if trace is None:
        log("trace", error="no profiler session ran or no device operation "
                           "is in it", traced=res["traced"])
        correct = False
        return result(metrics)
    device["busy_s"] = trace["busy_s"]
    device["window_s"] = trace["window_s"]
    log("trace", reduce_s=time.perf_counter() - t_red,
        xplane_bytes=trace["xplane_bytes"], traced=res["traced"],
        window_s=trace["window_s"], busy_s=trace["busy_s"],
        per_chip=trace["devices"], kernel_s=trace["kernel_s"],
        pallas_s=trace["pallas_s"], collective_s=trace["collective_s"],
        collective_exposed_s=trace["collective_exposed_s"],
        whole_periods=trace["whole_periods"],
        ops_busy_s=trace["ops_busy_s"], module_s=trace["module_s"],
        host_annotations_found=trace["host_annotations_found"],
        n_device_ops=trace["n_device_ops"], notes=run["notes"],
        recorder_events=len(events), recorder_dropped=rec.dropped)
    return result(metrics, breakdown=trace["breakdown"])


if __name__ == "__main__":
    sys.exit(main())
