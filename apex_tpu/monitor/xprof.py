"""Per-op profiling tables from an XProf trace (the ``pyprof.parse`` +
``pyprof.prof`` pipeline as code; moved here from ``apex_tpu/pyprof/
parse.py``, which now re-exports this module).

Reference: ``apex/pyprof/parse/parse.py`` reads the nvprof SQLite DB and
``apex/pyprof/prof/prof.py`` maps each kernel to op semantics with
FLOPs/bytes — an automated trace → per-op table pipeline. The TPU
equivalent parses the ``framework_op_stats`` tool from an
``xplane.pb`` trace (captured with ``jax.profiler.trace`` /
``apex_tpu.monitor.trace.trace``) WITHOUT TensorBoard: each row carries
the op's self time, its share of device time, whether it is HBM- or
compute-bound, and the measured FLOP rate / memory bandwidth — richer
than the reference's name-based reconstruction because the profiler
measured the real kernels after XLA fusion.

Typical use::

    from apex_tpu import monitor
    with monitor.trace.trace("/tmp/tr"):
        step(...); jax.block_until_ready(out)
    for row in monitor.xprof.op_stats("/tmp/tr")[:10]:
        print(row["operation"], row["avg_self_time_us"], row["bound_by"])
"""

from __future__ import annotations

import glob
import json
import os
from typing import List, Optional

# Stable snake_case view of the framework_op_stats columns we surface
# (input ids on the left as produced by xprof's gviz tables).
_COLUMNS = {
    "host_or_device": "host_or_device",
    "type": "op_type",
    "operation": "operation",
    "occurrences": "occurrences",
    "total_time": "total_time_us",
    "avg_time": "avg_time_us",
    "total_self_time": "total_self_time_us",
    "avg_self_time": "avg_self_time_us",
    "device_total_self_time_percent": "device_self_time_pct",
    "host_total_self_time_percent": "host_self_time_pct",
    "measured_flop_rate": "measured_flop_rate",
    "measured_memory_bw": "measured_memory_bw_gbps",
    "operational_intensity": "operational_intensity",
    "bound_by": "bound_by",
}


def _xplane_paths(logdir: str) -> List[str]:
    """xplane files of the NEWEST profile session under ``logdir``.

    ``jax.profiler.trace`` writes one timestamped session dir per
    capture; xprof's converter returns None when handed planes from
    different sessions, so re-used logdirs must resolve to one session
    (all files of that session are kept — multi-host captures have one
    per worker)."""
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(
            f"no *.xplane.pb under {logdir!r} — capture one with "
            f"apex_tpu.monitor.trace.trace(logdir)")
    by_session = {}
    for p in paths:
        by_session.setdefault(os.path.dirname(p), []).append(p)
    latest = max(by_session, key=os.path.getmtime)
    return sorted(by_session[latest])


def _gviz_tables(raw) -> List[List[dict]]:
    """Parse xprof's gviz JSON into per-table lists of dicts keyed by
    column id. ``framework_op_stats`` emits a combined (host+device)
    table and a device-only table over the SAME ops — they must not be
    concatenated (ops would double-count)."""
    if isinstance(raw, bytes):
        raw = raw.decode()
    tables = json.loads(raw)
    if isinstance(tables, dict):
        tables = [tables]
    out = []
    for table in tables:
        ids = [c.get("id") for c in table.get("cols", [])]
        rows = []
        for row in table.get("rows", []) or []:
            rows.append({i: (cell or {}).get("v")
                         for i, cell in zip(ids, row.get("c", []))})
        out.append(rows)
    return out


def op_stats_from_raw(raw, host: bool = False, include_idle: bool = False,
                      top: Optional[int] = None) -> List[dict]:
    """:func:`op_stats` on already-converted ``framework_op_stats``
    bytes/str (gviz JSON) — the parsing/ranking stage, separable for
    tests and for saved tool dumps."""
    tables = _gviz_tables(raw)
    want = "Host" if host else "Device"

    def placements(t):
        return {r.get("host_or_device") for r in t if r.get("type") != "IDLE"}

    # prefer a table dedicated to the wanted placement (xprof emits a
    # combined table AND a device-only table over the same ops); fall
    # back to filtering the combined one
    sel = None
    for t in tables:
        if t and placements(t) == {want}:
            sel = list(t)
            break

    def filter_all_tables(placement):
        # fall back across ALL tables (not just the first: converter
        # versions differ in emission order — advisor r3). Dedup is
        # CROSS-table only — the combined and device-only tables repeat
        # the same ops — while same-named rows within one table (e.g.
        # the same fusion in two compiled programs) are all kept.
        seen, rows = set(), []
        for t in tables:
            table_keys = set()
            for r in t:
                key = (r.get("operation"), r.get("host_or_device"))
                if r.get("host_or_device") == placement and key not in seen:
                    table_keys.add(key)
                    rows.append(r)
            seen |= table_keys
        return rows

    if sel is None:
        sel = filter_all_tables(want)
    if not sel and not host:
        sel = filter_all_tables("Host")
    if not include_idle:
        sel = [r for r in sel if r.get("type") != "IDLE"]
    out = []
    for r in sel:
        out.append({new: r.get(old) for old, new in _COLUMNS.items()})
    out.sort(key=lambda r: r.get("total_self_time_us") or 0.0, reverse=True)
    return out[:top] if top else out


def op_stats(logdir: str, host: bool = False,
             include_idle: bool = False,
             top: Optional[int] = None) -> List[dict]:
    """Per-op table from the trace in ``logdir``.

    Returns a list of dicts (keys: ``operation``, ``op_type``,
    ``occurrences``, ``total_self_time_us``, ``avg_self_time_us``,
    ``device_self_time_pct``, ``bound_by``, ``measured_flop_rate``,
    ``measured_memory_bw_gbps``, ``operational_intensity``, ...) sorted
    by total self time, descending. ``host=False`` selects device rows
    (falling back to host rows when the trace has no device activity —
    note CPU-only traces carry no framework ops at all, this tool is
    for TPU traces); ``top`` truncates.
    """
    from xprof.convert import raw_to_tool_data as rtd

    raw, _ = rtd.xspace_to_tool_data(_xplane_paths(logdir),
                                     "framework_op_stats", {})
    return op_stats_from_raw(raw, host=host, include_idle=include_idle,
                             top=top)


def top_ops(logdir: str, n: int = 5, host: bool = False) -> List[list]:
    """Compact ``[op name, self-time % of device total, bound_by]``
    triples for the n heaviest ops.
    The share is computed from the self-time column (xprof's own
    percent column is unreliable across converter versions)."""
    rows = op_stats(logdir, host=host)
    total = sum(float(r.get("total_self_time_us") or 0.0) for r in rows)
    total = total or 1.0
    return [[r["operation"],
             round(100.0 * float(r.get("total_self_time_us") or 0.0)
                   / total, 2),
             r.get("bound_by") or ""] for r in rows[:n]]


def format_table(rows: List[dict], max_rows: int = 20) -> str:
    """Render rows as a markdown table, one row an op. The share
    column is computed from the rows' self-times (same policy as
    :func:`top_ops` — xprof's own percent column is unreliable)."""
    total = sum(float(r.get("total_self_time_us") or 0.0)
                for r in rows) or 1.0
    hdr = ("| op | type | n | self ms | self % | bound by | GF/s | GB/s |\n"
           "|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows[:max_rows]:
        self_us = float(r.get("total_self_time_us") or 0.0)
        lines.append(
            "| {op} | {ty} | {n} | {ms:.3f} | {pct:.1f} | {bb} | {fr:.1f} "
            "| {bw:.1f} |".format(
                op=str(r.get("operation"))[:48],
                ty=r.get("op_type") or "",
                n=int(r.get("occurrences") or 0),
                ms=self_us / 1000.0,
                pct=100.0 * self_us / total,
                bb=r.get("bound_by") or "",
                fr=float(r.get("measured_flop_rate") or 0.0) / 1e9,
                bw=float(r.get("measured_memory_bw_gbps") or 0.0)))
    return "\n".join(lines)
