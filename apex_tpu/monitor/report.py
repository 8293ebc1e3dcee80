"""Aggregate and render recorder dumps (the ``pyprof.prof`` CLI analog).

``python -m apex_tpu.monitor report run.jsonl`` renders the per-step
table and the aggregate summary this module computes; ``aggregate`` is
also what ``Recorder.aggregate()`` and the bench JSON embed. Pure
stdlib — reports render anywhere, including hosts with no jax.
"""

from __future__ import annotations

import json
import warnings
from typing import Iterable, Optional


def load_jsonl(path_or_file) -> tuple[dict, list[dict]]:
    """Read a ``Recorder.dump_jsonl`` file → (header, events).

    A truncated *trailing* line (a process killed mid-append to a
    streamed file) is dropped with a warning instead of raising — a
    crash must never produce a dump the merge/report CLIs choke on.
    Corruption anywhere else still raises: that is a damaged file, not
    an interrupted append."""
    if hasattr(path_or_file, "read"):
        lines = path_or_file.read().splitlines()
    else:
        with open(path_or_file) as f:
            lines = f.read().splitlines()
    header: dict = {}
    events: list[dict] = []
    nonempty = [ln.strip() for ln in lines if ln.strip()]
    for i, ln in enumerate(nonempty):
        try:
            obj = json.loads(ln)
        except json.JSONDecodeError:
            if i == len(nonempty) - 1:
                warnings.warn(
                    f"dropping truncated trailing line ({len(ln)} bytes) "
                    f"from {getattr(path_or_file, 'name', path_or_file)}",
                    RuntimeWarning, stacklevel=2)
                break
            raise
        if obj.get("kind") == "header" and not header:
            header = obj
        else:
            events.append(obj)
    return header, events


def _dist(xs: list[float]) -> dict:
    xs = sorted(xs)
    n = len(xs)
    med = xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])
    return {"n": n, "min": xs[0], "max": xs[-1],
            "mean": sum(xs) / n, "median": med}


def aggregate(events: Iterable[dict], header: Optional[dict] = None) -> dict:
    """Aggregate a recorder event stream.

    Returns: ``steps`` (count + step-time distribution + first/last
    values of the per-step gauges), ``counters`` (final totals),
    ``gauges`` (last values), ``timers`` (count/total/mean per name),
    ``collectives`` (final per-``op@axis`` count/bytes table), any
    recorded pipeline ``schedules``, and ``health`` (the watchdog's
    typed ``health_event`` records, in order).
    """
    counters: dict[str, float] = {}
    gauges: dict[str, float] = {}
    timers: dict[str, dict] = {}
    collectives: dict[str, dict] = {}
    schedules: dict[str, dict] = {}
    utilization: dict[str, dict] = {}
    profile_rows: dict[str, dict] = {}
    histograms: dict[str, dict] = {}   # cumulative snapshots; last wins
    span_ends: list[dict] = []
    span_events: dict[str, int] = {}
    memory_rows: dict[str, dict] = {}    # per-program footprints
    memory_scopes: dict[str, dict] = {}  # per-scope analytic peaks
    gauge_series: dict[str, list] = {}   # trajectory-tracked gauges
    _TRACKED_GAUGES = ("serve/queue_depth", "serve/batch_fill",
                       "memory/hbm_bytes_in_use")
    steps: list[dict] = []
    health: list[dict] = []
    for ev in events:
        kind = ev.get("kind")
        name = ev.get("name", "")
        if kind == "counter":
            counters[name] = ev.get("total", counters.get(name, 0)
                                    + ev.get("value", 0))
        elif kind == "gauge":
            gauges[name] = ev.get("value")
            if name in _TRACKED_GAUGES:
                gauge_series.setdefault(name, []).append(
                    (ev.get("t"), ev.get("value")))
        elif kind == "histogram":
            # cumulative LogHistogram snapshot (spans.LogHistogram):
            # later emissions strictly contain earlier ones
            histograms[name] = {k: ev.get(k) for k in
                                ("lo", "hi", "buckets_per_decade", "sum",
                                 "min", "max", "underflow", "overflow",
                                 "counts")}
            histograms[name]["count"] = ev.get("value")
        elif kind == "span_end":
            span_ends.append(ev)
        elif kind in ("span_start", "span_event"):
            span_events[f"{kind}:{name}"] = \
                span_events.get(f"{kind}:{name}", 0) + 1
        elif kind == "timer":
            t = timers.setdefault(name, {"n": 0, "total_s": 0.0})
            t["n"] += 1
            t["total_s"] += float(ev.get("value") or 0.0)
        elif kind == "collective":
            slot = collectives.setdefault(name, {"count": 0, "bytes": 0})
            slot["count"] += int(ev.get("value") or 0)
            slot["bytes"] += int(ev.get("bytes") or 0)
        elif kind == "schedule":
            schedules[name] = {
                "total_ticks": ev.get("value"),
                "n_stages": ev.get("n_stages"),
                "n_microbatches": ev.get("n_microbatches"),
                "bubble_fraction": ev.get("bubble_fraction")}
        elif kind == "tick_mark":
            # measured slot occupancy: one mark per (tick, rank), one
            # boolean per executed unit slot (f/b/w) — see
            # hooks.traced_tick_marks
            rank = str(ev.get("rank", 0))
            row = utilization.setdefault(name, {}).setdefault(
                rank, {"ticks": 0, "slots_total": 0, "slots_valid": 0,
                       "by_slot": {}})
            row["ticks"] += 1
            for slot, valid in (ev.get("slots") or {}).items():
                row["slots_total"] += 1
                s = row["by_slot"].setdefault(slot, {"total": 0, "valid": 0})
                s["total"] += 1
                if valid:
                    s["valid"] += 1
                    row["slots_valid"] += 1
        elif kind == "profile":
            # per-scope analytic attribution rows (monitor.profile,
            # analytic_profile(record=True)); last emission wins
            row = {"flops": ev.get("value")}
            for k in ("hbm_bytes", "collective_bytes", "eqns",
                      "pallas_calls", "flops_scope_coverage"):
                if ev.get(k) is not None:
                    row[k] = ev[k]
            profile_rows[name] = row
        elif kind == "memory":
            # per-program footprint rows (monitor.memory,
            # memory_profile/compiled_memory_profile(record=True));
            # last emission wins
            row = {"total_bytes": ev.get("value")}
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes",
                      "analytic_peak_bytes", "peak_scope", "estimated",
                      "argument_bytes", "output_bytes"):
                if ev.get(k) is not None:
                    row[k] = ev[k]
            memory_rows[name] = row
        elif kind == "memory_scope":
            # per-scope analytic peak-live-bytes rows
            # (monitor.memory.analytic_high_water(record=True))
            memory_scopes[name] = {"peak_live_bytes": ev.get("value"),
                                   "eqns": ev.get("eqns")}
        elif kind == "step":
            steps.append(ev)
        elif kind == "health_event":
            health.append({k: ev.get(k) for k in
                           ("name", "value", "step", "severity",
                            "diagnosis", "gauge", "rank", "t")
                           if ev.get(k) is not None})
    out: dict = {}
    if header:
        out["run"] = {k: header.get(k) for k in ("name", "dropped", "meta")
                      if header.get(k) is not None}
    if steps:
        times = [float(s.get("step_time_s") or s.get("value") or 0.0)
                 for s in steps]
        gkeys = sorted({k for s in steps for k in (s.get("gauges") or {})})
        series = {}
        for k in gkeys:
            vals = [s["gauges"][k] for s in steps
                    if k in (s.get("gauges") or {})]
            if vals:
                series[k] = {"first": vals[0], "last": vals[-1],
                             "n": len(vals)}
        out["steps"] = {"count": len(steps), "step_time_s": _dist(times),
                        "gauges": series}
    for t in timers.values():
        t["total_s"] = round(t["total_s"], 6)
        t["mean_s"] = round(t["total_s"] / t["n"], 6) if t["n"] else 0.0
    out["counters"] = {k: counters[k] for k in sorted(counters)}
    out["gauges"] = {k: gauges[k] for k in sorted(gauges)}
    out["timers"] = {k: timers[k] for k in sorted(timers)}
    out["collectives"] = {k: collectives[k] for k in sorted(collectives)}
    if schedules:
        out["schedules"] = schedules
    if utilization:
        for sched, ranks in utilization.items():
            tot = val = 0
            for row in ranks.values():
                row["idle_fraction"] = round(
                    1.0 - row["slots_valid"] / row["slots_total"], 6) \
                    if row["slots_total"] else 0.0
                tot += row["slots_total"]
                val += row["slots_valid"]
            ranks["all"] = {
                "slots_total": tot, "slots_valid": val,
                "idle_fraction": round(1.0 - val / tot, 6) if tot else 0.0}
        out["pipeline_utilization"] = utilization
    measured = {k[len("profile/"):]: dict(v) for k, v in timers.items()
                if k.startswith("profile/")}
    if profile_rows or measured:
        prof: dict = {}
        if profile_rows:
            prof["analytic"] = {k: profile_rows[k]
                                for k in sorted(profile_rows)}
        if measured:
            prof["measured"] = measured
        out["profile"] = prof
    if histograms:
        from apex_tpu.monitor import spans as spans_mod
        out["histograms"] = {k: spans_mod.hist_summary(histograms[k])
                             for k in sorted(histograms)}
    if span_ends or span_events:
        per_name: dict[str, dict] = {}
        for e in span_ends:
            row = per_name.setdefault(e.get("name", ""),
                                      {"n": 0, "total_s": 0.0})
            row["n"] += 1
            row["total_s"] = round(row["total_s"]
                                   + float(e.get("value") or 0.0), 6)
        for row in per_name.values():
            row["mean_s"] = round(row["total_s"] / row["n"], 6) \
                if row["n"] else 0.0
        out["spans"] = {"by_name": {k: per_name[k]
                                    for k in sorted(per_name)}}
        if span_events:
            out["spans"]["events"] = {k: span_events[k]
                                      for k in sorted(span_events)}
    serve = _serve_block(span_ends, histograms, gauges, gauge_series,
                         counters)
    if serve:
        out["serve"] = serve
    mem = _memory_block(memory_rows, memory_scopes, gauges, gauge_series)
    if mem:
        out["memory"] = mem
    if health:
        out["health"] = health
    return out


def _downsample(series: list, cap: int = 64) -> list:
    if len(series) <= cap:
        return [list(p) for p in series]
    stride = len(series) / cap
    picked = [series[int(i * stride)] for i in range(cap - 1)]
    picked.append(series[-1])
    return [list(p) for p in picked]


def _serve_block(span_ends, histograms, gauges, gauge_series, counters):
    """The request-level serve telemetry view: per-request table from
    ``serve/request`` span ends, SLO percentiles from the streaming
    histograms (``Recorder.observe``), pool-occupancy gauges, the
    queue-depth trajectory, and the scheduler counters."""
    requests = [e for e in span_ends if e.get("name") == "serve/request"]
    serve_hists = {k: v for k, v in histograms.items()
                   if k.startswith("serve/")}
    serve_gauges = {k: v for k, v in gauges.items()
                    if k.startswith("serve/")}
    serve_counters = {k: v for k, v in counters.items()
                      if k.startswith("serve/")}
    if not (requests or serve_hists or serve_gauges or serve_counters):
        return None
    from apex_tpu.monitor import spans as spans_mod
    out: dict = {}
    if requests:
        rows = []
        for e in requests:
            row = {"seq_id": e.get("seq_id"),
                   "e2e_ms": round(1e3 * float(e.get("value") or 0.0), 3)}
            for k in ("prompt_tokens", "new_tokens", "preemptions",
                      "ttft_ms", "queue_wait_ms", "error"):
                if e.get(k) is not None:
                    row[k] = e[k]
            rows.append(row)
        rows.sort(key=lambda r: (r["seq_id"] is None, r["seq_id"]))
        out["requests"] = rows
    slo = {}
    for key in ("token_latency_ms", "ttft_ms", "queue_wait_ms"):
        snap = serve_hists.get(f"serve/{key}")
        if snap:
            slo[key] = spans_mod.hist_summary(snap, percentiles=(50, 95, 99))
    if slo:
        out["slo"] = slo
    pool = {k[len("serve/"):]: serve_gauges[k] for k in
            ("serve/pages_in_use", "serve/pages_free", "serve/pages_total",
             "serve/pool_bytes_in_use") if k in serve_gauges}
    if pool:
        out["pool"] = pool
    depth = gauge_series.get("serve/queue_depth")
    if depth:
        vals = [v for _, v in depth]
        out["queue_depth"] = {"max": max(vals), "last": vals[-1],
                              "trajectory": _downsample(depth)}
    fill = gauge_series.get("serve/batch_fill")
    if fill:
        vals = [v for _, v in fill]
        out["batch_fill_mean"] = round(sum(vals) / len(vals), 4)
    if serve_counters:
        out["counters"] = serve_counters
    if "serve/goodput_tokens_per_sec_chip" in serve_gauges:
        out["goodput_tokens_per_sec_chip"] = \
            serve_gauges["serve/goodput_tokens_per_sec_chip"]
    return out


def _memory_block(memory_rows, memory_scopes, gauges, gauge_series):
    """The unified memory view: per-program compiled footprints
    (``memory`` events), per-scope analytic peaks (``memory_scope``
    events), the live gauges, and the downsampled HBM timeline from
    the sampler's ``memory/hbm_bytes_in_use`` step gauge."""
    mem_gauges = {k: v for k, v in gauges.items()
                  if k.startswith("memory/")}
    if not (memory_rows or memory_scopes or mem_gauges):
        return None
    out: dict = {}
    if memory_rows:
        out["programs"] = {k: memory_rows[k]
                           for k in sorted(memory_rows)}
    if memory_scopes:
        # ties (the top jaxpr's output equation sees the same live
        # bytes under no scope) resolve to the NAMED scope
        from apex_tpu.monitor.profile import UNSCOPED
        peak = max(memory_scopes.items(),
                   key=lambda kv: (kv[1].get("peak_live_bytes") or 0,
                                   kv[0] != UNSCOPED))
        out["analytic"] = {
            "peak_live_bytes": peak[1].get("peak_live_bytes"),
            "peak_scope": peak[0],
            "scopes": {k: memory_scopes[k]
                       for k in sorted(memory_scopes)}}
    if mem_gauges:
        out["gauges"] = mem_gauges
    series = gauge_series.get("memory/hbm_bytes_in_use")
    if series:
        vals = [v for _, v in series]
        out["timeline"] = {"samples": len(vals), "max": max(vals),
                           "last": vals[-1],
                           "trajectory": _downsample(series)}
    return out


def measured_idle_fraction(agg: dict, schedule: str):
    """Convenience: the measured all-rank idle-slot fraction of one
    pipeline schedule from an :func:`aggregate` result (``None`` when
    the schedule recorded no tick marks). ``schedule`` matches the
    tick-mark name, e.g. ``"pipeline/zb1"``."""
    ranks = (agg.get("pipeline_utilization") or {}).get(schedule)
    if not ranks:
        return None
    return ranks["all"]["idle_fraction"]


def _fmt(v) -> str:
    if isinstance(v, float):
        if v == 0:
            return "0"
        if abs(v) >= 1e5 or abs(v) < 1e-4:
            return f"{v:.3e}"
        return f"{v:.6g}"
    return str(v)


def render_steps(events: list[dict], max_rows: int = 50) -> str:
    """Markdown per-step table: step index, step time, and every gauge
    column observed (loss scale, grad norm, ...)."""
    steps = [e for e in events if e.get("kind") == "step"]
    if not steps:
        return "(no step records)"
    gkeys = sorted({k for s in steps for k in (s.get("gauges") or {})})
    hdr = ["step", "time_ms"] + gkeys + ["collectives"]
    lines = ["| " + " | ".join(hdr) + " |",
             "|" + "---|" * len(hdr)]
    for s in steps[:max_rows]:
        colls = s.get("collectives") or {}
        ncoll = sum(c.get("count", 0) for c in colls.values())
        row = [str(s.get("step")),
               f"{1e3 * float(s.get('step_time_s') or 0.0):.3f}"]
        row += [_fmt(s["gauges"][k]) if k in (s.get("gauges") or {}) else ""
                for k in gkeys]
        row.append(str(ncoll))
        lines.append("| " + " | ".join(row) + " |")
    if len(steps) > max_rows:
        lines.append(f"... ({len(steps) - max_rows} more steps)")
    return "\n".join(lines)


def render_serve(agg: dict, max_rows: int = 50) -> Optional[str]:
    """Render the ``serve`` block of an :func:`aggregate` result: SLO
    percentiles (span-derived), pool occupancy, queue trajectory, and
    the per-request span table. ``None`` when no serve telemetry was
    recorded. Used by ``render_report`` and ``examples/serve_gpt.py
    --monitor``."""
    sv = agg.get("serve")
    if not sv:
        return None
    parts = ["## serve (request-level telemetry)\n"]
    if sv.get("goodput_tokens_per_sec_chip") is not None:
        parts.append(f"goodput: "
                     f"{_fmt(sv['goodput_tokens_per_sec_chip'])} "
                     f"tokens/sec/chip")
    slo = sv.get("slo") or {}
    for key, label in (("token_latency_ms", "token latency"),
                       ("ttft_ms", "time to first token"),
                       ("queue_wait_ms", "queue wait")):
        row = slo.get(key)
        if row:
            parts.append(
                f"{label} ms: p50 {_fmt(row.get('p50'))}  "
                f"p95 {_fmt(row.get('p95'))}  p99 {_fmt(row.get('p99'))}  "
                f"(n={row.get('count')}, mean {_fmt(row.get('mean'))})")
    pool = sv.get("pool") or {}
    if pool:
        total = pool.get("pages_total")
        used = pool.get("pages_in_use")
        pct = f" ({100.0 * used / total:.1f}%)" \
            if total and used is not None else ""
        nbytes = pool.get("pool_bytes_in_use")
        tail = f", {_fmt(nbytes)} bytes" if nbytes is not None else ""
        parts.append(f"pool: {used}/{total} pages in use{pct}{tail}")
    qd = sv.get("queue_depth")
    line = []
    if qd:
        line.append(f"queue depth: max {_fmt(qd['max'])} "
                    f"last {_fmt(qd['last'])}")
    if sv.get("batch_fill_mean") is not None:
        line.append(f"batch fill mean {sv['batch_fill_mean']}")
    pre = (sv.get("counters") or {}).get("serve/preemptions")
    if pre is not None:
        line.append(f"preemptions {_fmt(pre)}")
    if line:
        parts.append("; ".join(line))
    reqs = sv.get("requests") or []
    if reqs:
        parts.append("")
        parts.append("| request | prompt | new tokens | queue ms | "
                     "ttft ms | e2e ms | preempts |\n"
                     "|---|---|---|---|---|---|---|")
        for r in reqs[:max_rows]:
            parts.append(
                f"| {r.get('seq_id')} | {r.get('prompt_tokens', '')} "
                f"| {r.get('new_tokens', '')} "
                f"| {_fmt(r.get('queue_wait_ms', ''))} "
                f"| {_fmt(r.get('ttft_ms', ''))} "
                f"| {_fmt(r.get('e2e_ms', ''))} "
                f"| {r.get('preemptions', 0)} |")
        if len(reqs) > max_rows:
            parts.append(f"... ({len(reqs) - max_rows} more requests)")
    return "\n".join(parts)


def _fmt_bytes(v) -> str:
    if v is None or v == "":
        return ""
    v = float(v)
    for unit, div in (("GiB", 1 << 30), ("MiB", 1 << 20), ("KiB", 1 << 10)):
        if abs(v) >= div:
            return f"{v / div:.2f}{unit}"
    return f"{v:.0f}B"


def render_memory(agg: dict, max_rows: int = 30) -> Optional[str]:
    """Render the ``memory`` block of an :func:`aggregate` result:
    per-program footprint table, per-scope analytic peaks, the live
    gauges and the HBM timeline summary. ``None`` when no memory
    telemetry was recorded. Used by ``render_report`` and the
    ``python -m apex_tpu.monitor memory`` CLI."""
    mem = agg.get("memory")
    if not mem:
        return None
    parts = ["## memory\n"]
    progs = mem.get("programs") or {}
    if progs:
        parts.append("| program | total | argument | output | temp | "
                     "analytic peak | peak scope |\n"
                     "|---|---|---|---|---|---|---|")
        for name in sorted(progs):
            row = progs[name]
            parts.append(
                f"| {name} | {_fmt_bytes(row.get('total_bytes'))} "
                f"| {_fmt_bytes(row.get('argument_size_in_bytes', row.get('argument_bytes')))} "
                f"| {_fmt_bytes(row.get('output_size_in_bytes', row.get('output_bytes')))} "
                f"| {_fmt_bytes(row.get('temp_size_in_bytes'))} "
                f"| {_fmt_bytes(row.get('analytic_peak_bytes'))} "
                f"| {row.get('peak_scope', '')} |")
    analytic = mem.get("analytic") or {}
    scopes = analytic.get("scopes") or {}
    if scopes:
        parts.append(
            f"\nanalytic high water: "
            f"{_fmt_bytes(analytic.get('peak_live_bytes'))} at scope "
            f"`{analytic.get('peak_scope')}`\n")
        parts.append("| scope | peak live | eqns |\n|---|---|---|")
        order = sorted(scopes.items(),
                       key=lambda kv: -(kv[1].get("peak_live_bytes")
                                        or 0))
        for name, row in order[:max_rows]:
            parts.append(f"| {name} "
                         f"| {_fmt_bytes(row.get('peak_live_bytes'))} "
                         f"| {row.get('eqns', '')} |")
        if len(order) > max_rows:
            parts.append(f"... ({len(order) - max_rows} more scopes)")
    tl = mem.get("timeline")
    if tl:
        parts.append(f"\nhbm timeline: {tl['samples']} samples, "
                     f"max {_fmt_bytes(tl['max'])}, "
                     f"last {_fmt_bytes(tl['last'])}")
    g = mem.get("gauges") or {}
    line = []
    if "memory/hbm_bytes_in_use" in g:
        line.append(f"in use {_fmt_bytes(g['memory/hbm_bytes_in_use'])}")
    if "memory/hbm_limit_bytes" in g:
        line.append(f"limit {_fmt_bytes(g['memory/hbm_limit_bytes'])}")
    if "memory/hbm_utilization" in g:
        line.append(f"utilization "
                    f"{100.0 * g['memory/hbm_utilization']:.2f}%")
    if line:
        parts.append("hbm: " + ", ".join(line))
    return "\n".join(parts)


def render_report(events: list[dict], header: Optional[dict] = None,
                  max_rows: int = 50) -> str:
    """Full human-readable report: per-step table + aggregates."""
    agg = aggregate(events, header=header)
    parts = []
    run = agg.get("run", {})
    title = run.get("name") or "run"
    parts.append(f"# monitor report: {title}")
    if run.get("dropped"):
        parts.append(f"(ring buffer dropped {run['dropped']} events)")
    if agg.get("health"):
        parts.append("\n## health\n")
        for ev in agg["health"][:max_rows]:
            loc = f"step {ev['step']}" if ev.get("step") is not None else \
                (f"rank {ev['rank']}" if ev.get("rank") is not None else "-")
            parts.append(f"- **{ev.get('name')}** [{ev.get('severity')}] "
                         f"({loc}): {ev.get('diagnosis')}")
    serve = render_serve(agg, max_rows=max_rows)
    if serve:
        parts.append("\n" + serve)
    mem = render_memory(agg, max_rows=max_rows)
    if mem:
        parts.append("\n" + mem)
    parts.append("\n## per-step\n")
    parts.append(render_steps(events, max_rows=max_rows))
    if "steps" in agg:
        st = agg["steps"]["step_time_s"]
        parts.append(
            f"\nsteps: {agg['steps']['count']}  "
            f"step time ms: median {1e3 * st['median']:.3f}  "
            f"mean {1e3 * st['mean']:.3f}  "
            f"min {1e3 * st['min']:.3f}  max {1e3 * st['max']:.3f}")
    if agg.get("collectives"):
        parts.append("\n## collectives (per traced program)\n")
        parts.append("| collective | count | bytes |\n|---|---|---|")
        for k, v in agg["collectives"].items():
            parts.append(f"| {k} | {v['count']} | {v['bytes']} |")
    if agg.get("schedules"):
        parts.append("\n## pipeline schedules\n")
        parts.append("| schedule | stages | microbatches | ticks | "
                     "bubble |\n|---|---|---|---|---|")
        for k, v in agg["schedules"].items():
            parts.append(
                f"| {k} | {v.get('n_stages')} | {v.get('n_microbatches')} "
                f"| {v.get('total_ticks')} | {v.get('bubble_fraction')} |")
    if agg.get("pipeline_utilization"):
        parts.append("\n## pipeline utilization (measured slot "
                     "occupancy)\n")
        parts.append("| schedule | rank | ticks | slots | valid | "
                     "per-slot valid/total | idle |\n"
                     "|---|---|---|---|---|---|---|")
        for sched, ranks in agg["pipeline_utilization"].items():
            order = sorted((r for r in ranks if r != "all"), key=int)
            for rank in order + ["all"]:
                row = ranks[rank]
                per = " ".join(
                    f"{s}:{v['valid']}/{v['total']}"
                    for s, v in sorted(row.get("by_slot", {}).items()))
                parts.append(
                    f"| {sched} | {rank} | {row.get('ticks', '')} "
                    f"| {row['slots_total']} | {row['slots_valid']} "
                    f"| {per} | {row['idle_fraction']} |")
    if agg.get("profile"):
        prof = agg["profile"]
        parts.append("\n## profile (per-module cost attribution)\n")
        analytic = prof.get("analytic") or {}
        measured = prof.get("measured") or {}
        names = sorted(set(analytic) | set(measured),
                       key=lambda n: -(analytic.get(n, {}).get("flops")
                                       or 0))
        parts.append("| scope | flops | hbm bytes | coll bytes | "
                     "wall ms (measured) |\n|---|---|---|---|---|")
        for n in names[:max_rows]:
            a = analytic.get(n, {})
            m = measured.get(n)
            wall = f"{1e3 * m['mean_s']:.3f}" if m else ""
            parts.append(
                f"| {n} | {_fmt(a.get('flops', ''))} "
                f"| {_fmt(a.get('hbm_bytes', ''))} "
                f"| {_fmt(a.get('collective_bytes', ''))} | {wall} |")
    if agg.get("timers"):
        parts.append("\n## timers\n")
        parts.append("| timer | n | total s | mean s |\n|---|---|---|---|")
        for k, v in agg["timers"].items():
            parts.append(f"| {k} | {v['n']} | {_fmt(v['total_s'])} | "
                         f"{_fmt(v['mean_s'])} |")
    if agg.get("counters"):
        parts.append("\n## counters\n")
        parts.append("| counter | total |\n|---|---|")
        for k, v in agg["counters"].items():
            parts.append(f"| {k} | {_fmt(v)} |")
    return "\n".join(parts)


def render_cross_host(merged: dict, max_rows: int = 50) -> str:
    """Human-readable render of a ``merge.merge_summaries`` cross-host
    view: summed collective table, per-rank step-time skew, straggler
    percentiles for the host timers, and any health events."""
    parts = [f"# monitor cross-host report: {merged.get('n_ranks')} ranks "
             f"{merged.get('ranks')}"]
    if merged.get("health_events"):
        parts.append("\n## health\n")
        for ev in merged["health_events"][:max_rows]:
            parts.append(f"- **{ev.get('name')}** [{ev.get('severity')}] "
                         f"(rank {ev.get('rank')}): {ev.get('diagnosis')}")
    st = merged.get("steps")
    if st:
        sk = st["skew"]
        parts.append("\n## step-time skew per rank\n")
        parts.append("| rank | steps | median ms | x global median |\n"
                     "|---|---|---|---|")
        for rank in sorted(st["by_rank"], key=int):
            d = st["by_rank"][rank]
            ratio = (sk.get("per_rank_ratio") or {}).get(rank)
            parts.append(f"| {rank} | {d.get('count')} "
                         f"| {1e3 * d['median']:.3f} | {ratio} |")
        parts.append(f"\nslowest rank: {sk.get('slowest_rank')}  "
                     f"(max/median = {sk.get('max_over_median')})")
    if merged.get("collectives"):
        parts.append("\n## collectives (summed across ranks, "
                     "per traced program)\n")
        parts.append("| collective | count | bytes |\n|---|---|---|")
        for k, v in merged["collectives"].items():
            parts.append(f"| {k} | {v['count']} | {v['bytes']} |")
    if merged.get("timers"):
        parts.append("\n## timers (per-rank means, straggler "
                     "percentiles)\n")
        parts.append("| timer | median mean_s | max mean_s | max/median "
                     "| slowest rank |\n|---|---|---|---|---|")
        for k, v in merged["timers"].items():
            parts.append(
                f"| {k} | {_fmt(v.get('mean_s_median'))} "
                f"| {_fmt(v.get('mean_s_max'))} "
                f"| {v.get('max_over_median')} "
                f"| {v.get('slowest_rank')} |")
    return "\n".join(parts)


# ---------------------------------------------------------------------------
# selfcheck: the CI smoke for the whole pipeline
# ---------------------------------------------------------------------------

def selfcheck(n_steps: int = 3, verbose: bool = True) -> dict:
    """Record a synthetic ``n_steps``-step amp training run on CPU with
    a recorder attached, dump + reload the JSONL, and assert the report
    round-trips with the per-step fields the acceptance contract names
    (loss scale, grad norm, step time, collective table). Returns the
    aggregate. Raises AssertionError on any missing piece — run by
    ``tests/test_monitor.py``."""
    import io
    import jax.numpy as jnp
    from apex_tpu import monitor
    from apex_tpu.amp import scaler as scaler_mod
    from apex_tpu.optimizers import FusedSGD

    def loss_fn(params, x, y):
        h = jnp.tanh(x @ params["w1"])
        return jnp.mean((h @ params["w2"] - y) ** 2)

    from apex_tpu import amp
    opt = FusedSGD(lr=0.05)
    params = {"w1": jnp.ones((4, 8), jnp.float32) * 0.1,
              "w2": jnp.ones((8, 2), jnp.float32) * 0.1}
    opt_state = opt.init(params)
    sstate = scaler_mod.init_state(2.0 ** 8)
    step = amp.make_train_step(loss_fn, opt, donate=False)
    x = jnp.ones((2, 4), jnp.float32)
    y = jnp.ones((2, 2), jnp.float32)

    rec = monitor.Recorder(name="selfcheck")
    monitor.trace.install_compile_logging()
    with monitor.attached(rec):
        for _ in range(n_steps):
            with rec.step():
                params, opt_state, sstate, loss = step(
                    params, opt_state, sstate, x, y)

    buf = io.StringIO()
    rec.dump_jsonl(buf)
    buf.seek(0)
    header, events = load_jsonl(buf)
    steps = [e for e in events if e["kind"] == "step"]
    assert len(steps) == n_steps, (len(steps), n_steps)
    for s in steps:
        assert "step_time_s" in s and s["step_time_s"] > 0, s
        assert "amp/loss_scale" in s["gauges"], s["gauges"]
        assert "optim/grad_norm" in s["gauges"], s["gauges"]
        assert "collectives" in s, s
    agg = aggregate(events, header=header)
    assert agg["steps"]["count"] == n_steps
    assert "amp/loss_scale" in agg["steps"]["gauges"]
    rendered = render_report(events, header=header)
    assert "monitor report" in rendered and "amp/loss_scale" in rendered
    # disabled-mode guarantee: a fresh trace with no recorder attached
    # carries no callback effects
    import jax
    jaxpr = str(jax.make_jaxpr(
        lambda p, o, s, x, y: scaler_mod.update(
            s, jnp.asarray(False), dynamic=True))(
                params, opt_state, sstate, x, y))
    assert "callback" not in jaxpr, "hooks active while detached"
    if verbose:
        print(rendered)
        print(f"\nmonitor selfcheck ok: {n_steps} steps, "
              f"{len(events)} events round-tripped")
    return agg
