"""CLI: render recorder dumps and smoke-test the telemetry pipeline.

    python -m apex_tpu.monitor report run.jsonl [--json] [--max-rows N]
    python -m apex_tpu.monitor merge SHARD... [--json] [-o OUT.json]
    python -m apex_tpu.monitor timeline DUMP... [-o trace.json]
                                       [--no-align] [--validate-only]
    python -m apex_tpu.monitor profile [--model gpt|mlp] [--measured]
    python -m apex_tpu.monitor memory [--model gpt|mlp|zero|serve]
                                      [--live] [--json]
    python -m apex_tpu.monitor export run.jsonl [--once [--check]|--port N]
    python -m apex_tpu.monitor selfcheck [--steps N]

``report`` renders the per-step and aggregate tables from a
``Recorder.dump_jsonl`` file (the ``pyprof.prof`` analog — per-step
training telemetry instead of per-kernel nvprof records). ``merge``
combines rank-tagged shards (``monitor-<rank>.jsonl`` files, glob
patterns, or a directory holding them; flight dumps work too) from a
multi-process run into one cross-host view: collective bytes summed
across ranks, per-rank timer distributions with straggler percentiles,
per-rank step-time skew — and exits non-zero with a clear message when
zero shards match. ``timeline`` fuses the same shards and/or crash
``flight-<rank>.jsonl`` dumps (``apex_tpu.monitor.flight``) into one
Chrome-trace/Perfetto JSON — span trees, compile events, ``memory/
hbm_*`` counter tracks, health instants, one process track per rank,
cross-rank clock alignment on step boundaries, and a per-step
straggler overlay; open the output in https://ui.perfetto.dev or
chrome://tracing. ``profile``
builds a model train step (GPT by default; shape knobs below) and
prints the per-module cost attribution table — analytic FLOPs/bytes
per profile scope, optionally merged with measured eager wall times
(``--measured``) and an XProf per-op table (``--per-op``, subsuming
the old ``scripts/profile_gpt.py``). ``export`` renders a recorder
JSONL dump/stream as Prometheus text exposition — ``--once`` to stdout (``--check``
additionally parses the output back and asserts scrape == aggregate),
otherwise served over HTTP with
the file re-read per scrape.
``selfcheck`` records a synthetic 3-step amp run on CPU and asserts
the dump → report round trip.

``profile`` also reports **MFU** (model FLOPs utilization): the
analytic step FLOPs divided by measured wall time and the
per-``device_kind`` peak-FLOPs table (``--peak-tflops`` overrides the
table; ``--no-mfu`` skips the timed execution).

``memory`` is the unified byte view (``monitor.memory``): for
``--model gpt|mlp`` it prints the compiled footprint
(``Compiled.memory_analysis``) and the analytic high-water walk's
per-scope peak table for the canonical train step (the ``profile``
recipe), plus the ``vmem_calibration`` tuner feedback rows;
``--live`` additionally runs the step under a :class:`MemorySampler`
and reports the HBM timeline. ``--model zero`` prints the ZeRO
dense/zero2/zero3 per-chip residency split measured through
``memory.resident_bytes`` (the PR 6 ratio, re-derived live);
``--model serve`` prints the KV-pool occupancy/capacity accounting.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m apex_tpu.monitor")
    sub = p.add_subparsers(dest="cmd", required=True)

    pr = sub.add_parser("report", help="render a recorder JSONL dump")
    pr.add_argument("path", help="JSONL file from Recorder.dump_jsonl")
    pr.add_argument("--json", action="store_true",
                    help="print the aggregate as JSON instead of tables")
    pr.add_argument("--max-rows", type=int, default=50,
                    help="per-step table row cap")

    pm = sub.add_parser("merge",
                        help="merge rank-tagged shards into a "
                             "cross-host report")
    pm.add_argument("shards", nargs="+",
                    help="monitor-<rank>.jsonl files, glob patterns, "
                         "or one directory containing shards")
    pm.add_argument("--json", action="store_true",
                    help="print the merged view as JSON")
    pm.add_argument("-o", "--out", default=None,
                    help="also write the merged JSON here")

    pt = sub.add_parser("timeline",
                        help="fuse shards/flight dumps into one "
                             "Chrome-trace (Perfetto) JSON")
    pt.add_argument("dumps", nargs="+",
                    help="monitor-<rank>.jsonl / flight-<rank>.jsonl "
                         "files, glob patterns, or directories")
    pt.add_argument("-o", "--out", default="trace.json",
                    help="output trace path (default: trace.json)")
    pt.add_argument("--no-align", action="store_true",
                    help="skip cross-rank clock alignment")
    pt.add_argument("--straggler-ratio", type=float, default=None,
                    help="per-step slowest/median bar for straggler "
                         "instants (default 1.5)")
    pt.add_argument("--validate-only", action="store_true",
                    help="build + shape-check without writing the "
                         "trace (the CI gate mode)")

    pp = sub.add_parser("profile",
                        help="per-module cost attribution for a model "
                             "train step")
    pp.add_argument("--model", choices=("gpt", "mlp"), default="gpt")
    pp.add_argument("--batch", type=int, default=2)
    pp.add_argument("--seq", type=int, default=64)
    pp.add_argument("--hidden", type=int, default=64)
    pp.add_argument("--layers", type=int, default=2)
    pp.add_argument("--heads", type=int, default=2)
    pp.add_argument("--vocab", type=int, default=256)
    pp.add_argument("--dtype", choices=("float32", "bfloat16"),
                    default="float32")
    pp.add_argument("--attention", choices=("fused_softmax", "flash"),
                    default="fused_softmax",
                    help="fused_softmax keeps every matmul visible to "
                         "the analytic FLOP model; flash traces the "
                         "Pallas kernel (0 analytic FLOPs)")
    pp.add_argument("--fused-lm-head", action="store_true",
                    help="fuse the LM-head CE kernel (Pallas; 0 "
                         "analytic FLOPs for the head)")
    pp.add_argument("--measured", action="store_true",
                    help="also sample per-scope wall time eagerly "
                         "(jax.disable_jit)")
    pp.add_argument("--repeats", type=int, default=3,
                    help="eager repeats for --measured")
    pp.add_argument("--per-op", action="store_true",
                    help="also run an XProf trace and print the per-op "
                         "table (needs a device; the old "
                         "scripts/profile_gpt.py output)")
    pp.add_argument("--json", action="store_true")
    pp.add_argument("--max-rows", type=int, default=40)
    pp.add_argument("--mfu-repeats", type=int, default=3,
                    help="timed executions of the step for the MFU "
                         "wall-time denominator (median taken)")
    pp.add_argument("--peak-tflops", type=float, default=None,
                    help="peak TFLOP/s override for the MFU "
                         "denominator (default: the per-device_kind "
                         "table in monitor.profile)")
    pp.add_argument("--no-mfu", action="store_true",
                    help="skip the timed step execution + MFU line")

    pmem = sub.add_parser("memory",
                          help="unified memory view: compiled "
                               "footprint + analytic high water per "
                               "scope (+ZeRO/serve capacity reports)")
    pmem.add_argument("--model", choices=("gpt", "mlp", "zero", "serve"),
                      default="gpt")
    pmem.add_argument("--live", action="store_true",
                      help="also execute the step under a "
                           "MemorySampler and report the HBM timeline "
                           "(gpt/mlp models)")
    pmem.add_argument("--steps", type=int, default=3,
                      help="steps to execute under --live")
    pmem.add_argument("--interval", type=float, default=0.05,
                      help="sampler interval seconds for --live")
    pmem.add_argument("--no-calibration", action="store_true",
                      help="skip the tune/vmem calibration rows")
    pmem.add_argument("--json", action="store_true")
    pmem.add_argument("--max-rows", type=int, default=30)

    pe = sub.add_parser("export",
                        help="Prometheus text exposition from a "
                             "recorder JSONL dump/stream")
    pe.add_argument("path", help="Recorder.dump_jsonl file or "
                                 "recorder stream")
    pe.add_argument("--once", action="store_true",
                    help="render one snapshot to stdout and exit")
    pe.add_argument("--check", action="store_true",
                    help="with --once: parse the emitted text back and "
                         "assert scrape == aggregate (CI self-check)")
    pe.add_argument("--port", type=int, default=9464)
    pe.add_argument("--addr", default="127.0.0.1")

    ps = sub.add_parser("selfcheck",
                        help="record a synthetic run; assert round-trip")
    ps.add_argument("--steps", type=int, default=3)
    ps.add_argument("--quiet", action="store_true")

    args = p.parse_args(argv)
    from apex_tpu.monitor import report as report_mod

    from apex_tpu.monitor.recorder import json_safe

    if args.cmd == "report":
        header, events = report_mod.load_jsonl(args.path)
        if args.json:
            print(json.dumps(
                json_safe(report_mod.aggregate(events, header=header)),
                indent=2))
        else:
            print(report_mod.render_report(events, header=header,
                                           max_rows=args.max_rows))
        return 0

    if args.cmd == "merge":
        from apex_tpu.monitor import merge as merge_mod
        from apex_tpu.monitor.timeline import _expand
        if len(args.shards) == 1 and os.path.isdir(args.shards[0]):
            shards = args.shards[0]   # directory; merge_shards resolves
            missing_msg = (f"no monitor shards found: no "
                           f"monitor-<rank>.jsonl or flight-<rank>."
                           f"jsonl in directory {args.shards[0]!r}")
        else:
            shards = _expand(args.shards)   # globs + files, deduped
            missing_msg = (f"no monitor shards found: nothing matched "
                           f"{' '.join(args.shards)!r}")
        try:
            merged = json_safe(merge_mod.merge_shards(shards))
        except ValueError as e:
            if "no monitor shards" in str(e):
                print(missing_msg, file=sys.stderr)
                return 2
            raise
        if args.out:
            with open(args.out, "w") as f:
                json.dump(merged, f, indent=2)
        if args.json:
            print(json.dumps(merged, indent=2))
        else:
            print(report_mod.render_cross_host(merged))
        return 0

    if args.cmd == "timeline":
        from apex_tpu.monitor import timeline as timeline_mod
        sources = timeline_mod.load_sources(args.dumps)
        if not sources:
            print(f"no recorder dumps found: nothing matched "
                  f"{' '.join(args.dumps)!r}", file=sys.stderr)
            return 2
        kw = {}
        if args.straggler_ratio is not None:
            kw["straggler_ratio"] = args.straggler_ratio
        trace = timeline_mod.build_timeline(
            sources, align=not args.no_align, **kw)
        problems = timeline_mod.validate_timeline(trace)
        if problems:
            for pr_ in problems[:20]:
                print(f"timeline shape error: {pr_}", file=sys.stderr)
            return 1
        n_ev = len(trace["traceEvents"])
        if args.validate_only:
            print(f"timeline ok: {n_ev} events across "
                  f"{len(sources)} rank(s) (not written)")
            return 0
        timeline_mod.write_timeline(trace, args.out)
        print(f"timeline: {n_ev} events across {len(sources)} rank(s) "
              f"-> {args.out} (open in https://ui.perfetto.dev or "
              f"chrome://tracing)")
        return 0

    if args.cmd == "export":
        from apex_tpu.monitor import export as export_mod
        return export_mod.main(args)

    if args.cmd in ("profile", "memory"):
        # the two subcommands that compile a model
        from apex_tpu.utils import compile_cache
        compile_cache.enable()

    if args.cmd == "profile":
        return _run_profile(args)

    if args.cmd == "memory":
        return _run_memory(args)

    # selfcheck needs a backend; default to CPU unless the caller chose
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    report_mod.selfcheck(n_steps=args.steps, verbose=not args.quiet)
    return 0


def _run_profile(args) -> int:
    from apex_tpu.monitor import attribution as profile_mod
    from apex_tpu.monitor.recorder import json_safe

    step, step_args = profile_mod.demo_train_step(
        args.model, batch=args.batch, seq=args.seq, hidden=args.hidden,
        layers=args.layers, heads=args.heads, vocab=args.vocab,
        dtype=args.dtype, attention=args.attention,
        fused_lm_head=args.fused_lm_head)
    prof = profile_mod.analytic_profile(step, *step_args)
    measured = None
    if args.measured:
        measured = profile_mod.measured_profile(step, *step_args,
                                                repeats=args.repeats)
    mfu_row = None
    if not args.no_mfu:
        peak = (args.peak_tflops * 1e12
                if args.peak_tflops is not None else None)
        mfu_row = profile_mod.measured_mfu(
            step, step_args, flops=prof["total"]["flops"], peak=peak,
            repeats=args.mfu_repeats)
    if args.json:
        print(json.dumps(json_safe(
            {"analytic": prof, "measured": measured,
             "mfu": mfu_row}), indent=2))
    else:
        print(profile_mod.render_profile(prof, measured=measured,
                                         max_rows=args.max_rows))
        if mfu_row is not None:
            print(profile_mod.render_mfu(mfu_row))
    if args.per_op:
        # with --json, stdout must stay ONE parseable document: the
        # human-readable per-op table moves to stderr
        _profile_per_op(step, step_args,
                        out=sys.stderr if args.json else sys.stdout)
    return 0


def _run_memory(args) -> int:
    from apex_tpu import monitor
    from apex_tpu.monitor import memory as memory_mod
    from apex_tpu.monitor import attribution as profile_mod
    from apex_tpu.monitor.recorder import json_safe

    out: dict = {"model": args.model}
    rendered: list = []
    if args.model == "zero":
        out["zero"] = memory_mod.zero_memory_report()
        pc = out["zero"]["per_chip_bytes"]
        rendered.append("# memory: ZeRO residency split (per-chip "
                        "resident param+opt bytes, measured)")
        rendered.append("| config | per-chip bytes | compiled temp |\n"
                        "|---|---|---|")
        for which in ("dense", "zero2", "zero3"):
            temp = (out["zero"]["compiled"].get(which) or {}).get(
                "temp_size_in_bytes", "")
            rendered.append(f"| {which} | {pc[which]} | {temp} |")
        rendered.append(
            f"\ndense/zero3 ratio: "
            f"{out['zero']['dense_over_zero3_ratio']} at world="
            f"{out['zero']['world_size']} (~world# within padding + "
            f"replicated-bias slack)")
    elif args.model == "serve":
        out["serve_pool"] = memory_mod.serve_pool_report()
        sp = out["serve_pool"]
        rendered.append("# memory: serve KV-pool accounting")
        rendered.append(
            f"pool {sp['pool_bytes']} B ({sp['usable_pages']} usable "
            f"pages x {sp['bytes_per_page']} B); occupancy "
            f"{sp['occupancy']} ({sp['pages_in_use']} pages, "
            f"{sp['bytes_in_use']} B in use)")
        rendered.append(
            f"capacity at the same pool budget: bf16 "
            f"{sp['bf16_seqs_at_budget']} vs fp8 "
            f"{sp['fp8_seqs_at_budget']} concurrent seqs "
            f"(ratio {sp['fp8_capacity_ratio']})")
    else:
        step, step_args = profile_mod.demo_train_step(args.model)
        prof = memory_mod.memory_profile(step, *step_args,
                                         label=f"{args.model}_step")
        out["profile"] = prof
        rendered.append(memory_mod.render_memory_profile(
            prof, max_rows=args.max_rows))
        if args.live:
            import jax
            rec = monitor.Recorder(name="memory-cli",
                                   traced_hooks=False)
            with monitor.attached(rec), \
                    memory_mod.MemorySampler(args.interval):
                for _ in range(max(1, args.steps)):
                    step_out = step(*step_args)
                jax.block_until_ready(step_out)
            agg = rec.aggregate()
            out["live"] = {"memory": agg.get("memory"),
                           "histograms": agg.get("histograms")}
            from apex_tpu.monitor import report as report_mod
            live_render = report_mod.render_memory(agg)
            if live_render:
                rendered.append("\n# live HBM timeline "
                                "(MemorySampler)\n")
                rendered.append(live_render)
    if not args.no_calibration and args.model in ("gpt", "mlp"):
        cal = memory_mod.vmem_calibration()
        out["vmem_calibration"] = cal
        rendered.append(f"\nvmem calibration: {cal['checked']} kernel "
                        f"config(s) checked, {cal['mispredicts']} "
                        f"envelope mispredict(s)")
        for row in cal["rows"]:
            rendered.append(
                f"- {row['kernel']} [{row['source']}] "
                f"{row['config']}: predicted "
                f"{row['predicted_vmem_bytes']} B vs compiled temp "
                f"{row['measured_temp_bytes']} B"
                f"{'  ** MISPREDICT **' if row['mispredict'] else ''}")
    if args.json:
        print(json.dumps(json_safe(out), indent=2))
    else:
        print("\n".join(rendered))
    return 0


def _profile_per_op(step, step_args, out=None):
    """XProf per-op table (the old ``scripts/profile_gpt.py`` body):
    trace one warm step, parse the op stats. Degrades with a notice
    when the platform yields no parseable trace."""
    import tempfile

    from apex_tpu import monitor

    out = out if out is not None else sys.stdout
    try:
        _block(step(*step_args))        # compile + warm
        d = tempfile.mkdtemp(prefix="apx_profile_")
        with monitor.trace.trace(d):
            _block(step(*step_args))
        rows = monitor.xprof.op_stats(d)
        tot = sum(r["total_self_time_us"] or 0 for r in rows)
        print(f"\ntotal device self time: {tot / 1e3:.2f} ms", file=out)
        print(f"{'self_us':>10} {'pct':>6} {'bound':>8}  operation",
              file=out)
        for r in rows[:45]:
            print(f"{r['total_self_time_us'] or 0:10.0f} "
                  f"{r['device_self_time_pct'] or 0:6.2f} "
                  f"{str(r['bound_by'] or ''):>8}  "
                  f"{r['operation'][:110]}", file=out)
    except Exception as e:                              # noqa: BLE001
        print(f"\n(per-op XProf table unavailable here: "
              f"{type(e).__name__}: {e})", file=sys.stderr)


def _block(out):
    import jax
    jax.block_until_ready(out)


if __name__ == "__main__":
    sys.exit(main())
