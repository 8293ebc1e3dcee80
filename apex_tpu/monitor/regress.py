"""Bench-trajectory regression detection over evidence rounds.

The repo accumulates one ``BENCH_r<NN>.json`` per round (a driver
wrapper: ``{"n", "cmd", "rc", "tail", "parsed"}``) plus streaming
``bench_stream.jsonl`` evidence. This module turns that pile into a
mechanical verdict:

- **Loader** (:func:`load_round`): ingests driver wrappers, assembled
  bench JSON, and raw evidence streams. Degrades *per round*, never
  crashes: a killed round (``rc != 0`` or ``parsed: null`` — the r05
  shape), a corrupt file, or a missing path becomes an explicit
  ``no-evidence`` row with the reason attached.
- **Versioned schema**: from schema 2 on, bench stamps ``schema`` and a
  per-metric ``units`` map on every section result. Older rounds get
  units from a documented legacy-inference table; in particular, a
  round with only the four contract keys (``metric/value/unit/
  vs_baseline`` — the r01 shape) predates the round-2 timing
  methodology (r01 stopped the clock at dispatch, not at device
  completion, so every r01 number is a *dispatch* rate), and ALL its
  metrics are stamped with a ``(r1 dispatch methodology)`` unit —
  overriding the file's own optimistic ``unit`` field. r01 vs r02+ is
  therefore ``incomparable`` (a unit change), not a fake 50x
  regression.
- **Noise-aware verdicts** (:func:`compare`): per metric, the prior
  comparable rounds form a median/MAD band; the candidate regresses
  only when it falls outside ``max(nmad * MAD, rel_tol * |median|)``
  in the metric's bad direction AND at least ``min_history`` prior
  comparable values exist (two points cannot define noise). Metrics
  with unknown direction never gate.

CLI::

    python -m apex_tpu.monitor regress BENCH_r*.json \
        [--against BASELINE.json] [--json] [--nmad 3] [--rel-tol 0.05] \
        [--min-history 3]

Exit status is non-zero ONLY on a confirmed ``regression`` verdict —
``no-evidence``, ``incomparable`` and ``insufficient-history`` are
report rows, not failures. Wired into ``scripts/ci.sh`` as a gate over
the smoke-bench stream and the committed rounds.

Pure stdlib (no jax): verdicts render anywhere, including the driver
host.
"""

from __future__ import annotations

import json
import math
import os
from typing import Iterable, Optional

# the schema bench.py stamps from this PR on (see bench RESULT_SCHEMA)
CURRENT_SCHEMA = 2

NO_EVIDENCE = "no-evidence"

# keys that are bookkeeping, not metrics
_NON_METRIC_KEYS = frozenset({
    "schema", "n", "rc", "sections_completed", "timing",
})


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------

def _numeric_metrics(data: dict) -> dict:
    out = {}
    for k, v in data.items():
        if k in _NON_METRIC_KEYS or k.endswith(("_error", "_skipped")):
            continue
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)) and math.isfinite(v):
            out[k] = float(v)
    return out


def suffix_unit(name: str) -> str:
    if name.endswith("_ms") or "_ms_" in name:
        return "ms"
    if name.endswith("_s"):
        return "s"
    if "tokens_per_sec" in name:
        return "tokens/sec"
    if "imgs_per_sec" in name:
        return "imgs/sec/chip"
    if "mfu" in name:
        return "mfu"
    if name.endswith("_pct"):
        return "%"
    if "speedup" in name or name == "vs_baseline":
        return "ratio"
    if "loss" in name:
        return "loss"
    # byte metrics (r15 on: the monitor.memory layer registers its
    # bench keys here so `monitor regress` gates them lower-better)
    if name.endswith(("_bytes", "_bytes_per_chip", "_bytes_per_page",
                      "_bytes_in_use")) or "_bytes_" in name:
        return "bytes"
    if "occupancy" in name:
        return "fraction (pool occupancy)"
    # fleet metrics (r18 on: monitor.fleet bench keys — replica/alert/
    # decision counts gate as counts, latency/goodput resolve above)
    if name.endswith(("_replicas", "_replicas_up", "_alerts",
                      "_decisions", "_polls")):
        return "count"
    return ""


def _legacy_units(metrics: dict, declared_unit: Optional[str],
                  raw_keys=None) -> tuple:
    """(schema, units) for a round that predates schema stamping.

    The inference table (documented, mechanical):

    - **schema 0** — only the four contract keys (the r01 shape: no
      ``o2_step_ms``, no per-model throughputs). Round 1 predates the
      round-2 timing methodology: its clock stopped at dispatch, not
      at device completion, so its numbers are dispatch rates. Every
      metric's unit gets the
      ``(r1 dispatch methodology)`` marker — the file's own ``unit``
      field is overridden because it is exactly the silent drift this
      loader exists to surface.
    - **schema 1** — anything else unstamped (r02-r05 era): the
      declared headline unit is honored and the rest come from the
      name-suffix table.
    """
    methodology_keys = {"o2_step_ms", "gpt_tokens_per_sec",
                        "bert_tokens_per_sec", "timing"}
    # detection runs over the RAW result keys, not the numeric metrics:
    # "timing" is a dict (a marker, not a metric) and would otherwise
    # never match, misclassifying a partial r02+ round as schema 0
    legacy_v0 = not (methodology_keys
                     & (set(metrics) if raw_keys is None
                        else set(raw_keys)))
    units = {k: suffix_unit(k) for k in metrics}
    units["value"] = declared_unit or units.get("value", "")
    if legacy_v0:
        units = {k: f"{u or 'unknown'} (r1 dispatch methodology)"
                 for k, u in units.items()}
        return 0, units
    return 1, units


def _round_from_data(data: dict, path: str, n=None) -> dict:
    metrics = _numeric_metrics(data)
    if not metrics:
        return _no_evidence(path, "no numeric metrics in evidence", n=n)
    if "schema" in data:
        schema = int(data["schema"])
        units = {k: str(v) for k, v in (data.get("units") or {}).items()}
        for k in metrics:
            units.setdefault(k, suffix_unit(k))
    else:
        schema, units = _legacy_units(metrics, data.get("unit"),
                                      raw_keys=set(data))
    rec = {"path": path, "round": n, "status": "ok", "schema": schema,
           "metrics": metrics, "units": units}
    if data.get("interrupted") or data.get("error"):
        rec["partial"] = str(data.get("interrupted") or data.get("error"))
    return rec


def _no_evidence(path: str, reason: str, n=None) -> dict:
    return {"path": path, "round": n, "status": NO_EVIDENCE,
            "reason": reason, "schema": None, "metrics": {}, "units": {}}


def _round_from_stream(lines: list, path: str) -> dict:
    data: dict = {}
    units: dict = {}
    schema = None
    sections = 0
    for obj in lines:
        if obj.get("kind") != "section":
            continue
        sections += 1
        data.update(obj.get("data") or {})
        units.update(obj.get("units") or {})
        if obj.get("schema") is not None:
            schema = obj["schema"]
    if not sections:
        return _no_evidence(path, "stream holds no section lines")
    if schema is not None:
        data["schema"] = schema
        data["units"] = units
    return _round_from_data(data, path)


def load_round(path: str) -> dict:
    """One evidence round from ``path`` — a driver ``BENCH_r*.json``
    wrapper, an assembled bench JSON, or a ``bench_stream.jsonl``
    evidence stream. Never raises: unreadable/corrupt/killed rounds
    come back as ``no-evidence`` rows carrying the reason."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        return _no_evidence(path, f"unreadable: {e}")
    try:
        obj = json.loads(text)
    except ValueError:
        # not one JSON document: maybe a JSONL evidence stream
        lines = []
        for ln in text.splitlines():
            ln = ln.strip()
            if not ln:
                continue
            try:
                parsed = json.loads(ln)
            except ValueError:
                continue
            if isinstance(parsed, dict):
                lines.append(parsed)
        if lines:
            return _round_from_stream(lines, path)
        return _no_evidence(path, "corrupt JSON (neither document nor "
                                  "JSONL stream)")
    if not isinstance(obj, dict):
        return _no_evidence(path, f"expected a JSON object, got "
                                  f"{type(obj).__name__}")
    if "rc" in obj and "parsed" in obj:
        # driver wrapper round
        n = obj.get("n")
        rc = obj.get("rc")
        parsed = obj.get("parsed")
        if rc not in (0, None):
            return _no_evidence(
                path, f"rc={rc}, parsed: "
                      f"{'null' if not parsed else 'partial'}", n=n)
        if not parsed:
            return _no_evidence(path, "rc=0 but parsed: null", n=n)
        return _round_from_data(parsed, path, n=n)
    if "kind" in obj:
        return _round_from_stream([obj], path)
    return _round_from_data(obj, path)


def load_rounds(paths: Iterable[str]) -> list:
    return [load_round(p) for p in paths]


# ---------------------------------------------------------------------------
# verdicts
# ---------------------------------------------------------------------------

def metric_direction(name: str, unit: str) -> Optional[str]:
    """"higher"/"lower" = which way is better; None = unknown (such a
    metric can be reported but never gates)."""
    base = unit.split(" (")[0]
    if base in ("ms", "s", "bytes") or name.endswith(("_ms", "_s")) \
            or "_ms_" in name or "idle" in name or "bubble" in name \
            or "bytes" in name or "loss" in name or base == "loss" \
            or "ttft" in name or "queue_wait" in name \
            or "occupancy" in name or "mispredict" in name \
            or "utilization" in name or "alert" in name:
        return "lower"
    if name.endswith(("_replicas_up",)):
        return "higher"
    if "/sec" in base or base in ("mfu", "ratio") or "per_sec" in name \
            or "speedup" in name or "mfu" in name or name == "vs_baseline" \
            or "goodput" in name or "capacity_ratio" in name:
        return "higher"
    return None


def _median(xs: list) -> float:
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def _label(rnd: dict) -> str:
    if rnd.get("round") is not None:
        return f"r{int(rnd['round']):02d}"
    return os.path.basename(str(rnd.get("path", "?")))


def compare(rounds: list, against: Optional[dict] = None,
            nmad: float = 3.0, rel_tol: float = 0.05,
            min_history: int = 3) -> dict:
    """Verdict report over ``rounds`` (chronological order; the last
    round WITH evidence is the candidate). ``against`` (an extra
    round record, e.g. a pinned baseline) is prepended to the history.

    Returns ``{"rounds", "candidate", "metrics", "regressions",
    "exit_code"}`` where each metric row carries ``verdict`` in
    {``ok``, ``regression``, ``improvement``, ``insufficient-history``,
    ``unknown-direction``} plus the band arithmetic, and rounds whose
    unit for that metric differs from the candidate's are listed under
    ``incomparable`` instead of entering the band."""
    summaries = []
    for r in rounds:
        row = {"round": _label(r), "status": r["status"],
               "schema": r.get("schema"), "path": r.get("path")}
        if r["status"] != "ok":
            row["reason"] = r.get("reason")
        elif r.get("partial"):
            row["partial"] = r["partial"]
        summaries.append(row)

    evidence = [r for r in rounds if r["status"] == "ok"]
    report: dict = {"rounds": summaries, "metrics": {}, "regressions": [],
                    "candidate": None, "exit_code": 0}
    if not evidence:
        report["note"] = "no round with evidence; nothing to compare"
        return report
    candidate = evidence[-1]
    history = ([] if against is None or against.get("status") != "ok"
               else [against]) + evidence[:-1]
    report["candidate"] = _label(candidate)

    for name in sorted(candidate["metrics"]):
        value = candidate["metrics"][name]
        unit = candidate["units"].get(name, "")
        prior, incomparable = [], []
        for r in history:
            if name not in r["metrics"]:
                continue
            r_unit = r["units"].get(name, "")
            if r_unit != unit:
                incomparable.append(
                    {"round": _label(r), "unit": r_unit})
            else:
                prior.append((_label(r), r["metrics"][name]))
        row: dict = {"unit": unit, "value": value,
                     "history": [{"round": lb, "value": v}
                                 for lb, v in prior]}
        if incomparable:
            row["incomparable"] = incomparable
        direction = metric_direction(name, unit)
        if direction is None:
            row["verdict"] = "unknown-direction"
        elif not prior or len(prior) < min_history:
            # `not prior` matters independently: min_history=0 must not
            # send an empty trajectory into the band arithmetic
            row["verdict"] = "insufficient-history"
            row["note"] = (f"{len(prior)} comparable prior round(s); "
                           f"need {min_history} for a noise band")
        else:
            vals = [v for _, v in prior]
            med = _median(vals)
            mad = _median([abs(v - med) for v in vals])
            band = max(nmad * mad, rel_tol * abs(med))
            delta = value - med
            row.update({"median": med, "mad": mad, "band": band,
                        "delta": delta, "direction": direction})
            worse = delta < -band if direction == "higher" else delta > band
            better = delta > band if direction == "higher" else delta < -band
            row["verdict"] = ("regression" if worse
                              else "improvement" if better else "ok")
            if worse:
                report["regressions"].append(name)
        report["metrics"][name] = row
    report["exit_code"] = 1 if report["regressions"] else 0
    return report


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

def _fmt(v) -> str:
    if v is None:
        return ""
    if isinstance(v, float):
        if v == 0:
            return "0"
        if abs(v) >= 1e5 or abs(v) < 1e-3:
            return f"{v:.3e}"
        return f"{v:.4g}"
    return str(v)


def render_regress(report: dict, max_history: int = 8) -> str:
    """Human-readable verdict tables."""
    parts = ["# bench trajectory"]
    parts.append("\n## rounds\n")
    parts.append("| round | status | schema | detail |\n|---|---|---|---|")
    for row in report["rounds"]:
        detail = row.get("reason") or row.get("partial") or ""
        parts.append(f"| {row['round']} | {row['status']} "
                     f"| {row.get('schema') if row.get('schema') is not None else ''} "
                     f"| {detail} |")
    if report.get("note"):
        parts.append(f"\n{report['note']}")
        return "\n".join(parts)
    parts.append(f"\ncandidate round: **{report['candidate']}**")
    parts.append("\n## metrics\n")
    parts.append("| metric | unit | history | median | band | value | "
                 "verdict |\n|---|---|---|---|---|---|---|")
    order = sorted(
        report["metrics"].items(),
        key=lambda kv: ({"regression": 0, "improvement": 1, "ok": 2,
                         "insufficient-history": 3,
                         "unknown-direction": 4}.get(kv[1]["verdict"], 5),
                        kv[0]))
    for name, row in order:
        hist = " ".join(_fmt(h["value"])
                        for h in row["history"][-max_history:])
        verdict = row["verdict"]
        if row.get("incomparable"):
            inc = ",".join(i["round"] for i in row["incomparable"])
            verdict += f" (incomparable: {inc})"
        parts.append(
            f"| {name} | {row['unit']} | {hist} | {_fmt(row.get('median'))} "
            f"| {_fmt(row.get('band'))} | {_fmt(row['value'])} "
            f"| {verdict} |")
    if report["regressions"]:
        parts.append(f"\nREGRESSIONS: {', '.join(report['regressions'])}")
    else:
        parts.append("\nno confirmed regressions")
    return "\n".join(parts)
