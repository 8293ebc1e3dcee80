"""The serve round on the host: how much of a round's period the host is
busy, and which of the device's idle time is the host's lateness.

``serve/engine.py:_decode_round`` tells a round's DISPATCH
(``serve/decode_dispatch``: the host's work; while it runs late the device
starves) from its WAIT (``serve/token_wait``: the host has nothing else to
do until the previous round's values are here; while it is long the host is
not the limit). The four readers built on this file (``host_busy_share``,
``decode_dispatch_ms_p50``, ``rounds_overlapped_share``,
``idle_host_late_share``) share its arithmetic, and the first and the last
their rule: a program without ``serve/token_wait`` reports neither, because
without the wait told apart every second of a round would read as busy and
every idle gap as the host's.

**Busy share.** Over the ``serve/round`` spans found in BOTH sinks by id:
so inside the profiler session, on the device's clock, and clear of the
seconds the session's start and stop take inside the recorder's window. For
each round whose successor is there too, the period is the next round's
start minus its own, and busy is the period minus the ``serve/token_wait``
time that BEGAN in it (under ``serve/decode_step``, under ``serve/sample``,
or in a drain between two steps). The harness's own work between two steps
(``admit``) counts as busy, on purpose: that too keeps the next dispatch
from going out. At 100% the host sets the pace.

**Late share.** ``span_reduce`` names each device idle gap by the innermost
program span or harness annotation over its midpoint
(``notes.idle_by_span``). Under ``serve/decode_dispatch`` or
``serve/prefill`` a gap IS the in-span dispatch gap; under
``serve/token_wait`` the device idles while the host waits for it (the
runtime's latency, not the host's lateness), and gaps under 20 us are the
device's own between two operations: both are left out.
"""

from __future__ import annotations

import bisect
import collections
from typing import Dict, List, Optional, Sequence, Tuple

from . import span_reduce as sr
from . import trace_reduce as tr
from .stats import median, percentile

ROUND = "serve/round"
STEP = "serve/decode_step"
DISPATCH = "serve/decode_dispatch"
WAIT = "serve/token_wait"
#: the names of ``notes.idle_by_span`` that are not the host's lateness
NOT_LATE = (WAIT, f"gaps-under-{sr.MIN_GAP_S * 1e6:g}us")


def round_periods(spans: Sequence[dict],
                  plane: Sequence[Tuple[float, float, str, int]]
                  ) -> List[Tuple[float, List[float]]]:
    """``[(period, [token-wait seconds that began in it])]`` on the plane's
    clock, one entry for each round of ``spans`` (the recorder's closed
    spans, which give the rounds' order) that is on ``plane`` (the host
    plane's events with a span id) together with its successor."""
    names = {s["id"]: s["name"] for s in spans}
    on = {sid: (s, e) for s, e, name, sid in plane if names.get(sid) == name}
    rounds = [on.get(s["id"]) for s in sorted(spans, key=lambda s: s["t0"])
              if s["name"] == ROUND]
    waits = sorted(on[i] for i, name in names.items()
                   if name == WAIT and i in on)
    began = [s for s, _ in waits]
    out = []
    for a, b in zip(rounds, rounds[1:]):
        if a and b and b[0] > a[0]:
            lo, hi = (bisect.bisect_left(began, r[0]) for r in (a, b))
            out.append((b[0] - a[0], [e - s for s, e in waits[lo:hi]]))
    return out


def step_rest(spans: Sequence[dict]) -> Dict[str, float]:
    """What the window's ``serve/decode_step`` spans hold beside their
    dispatch and their wait (the recorder's durations): the spans' own
    cost, and the check that the two children cover the step."""
    covered: Dict[int, float] = {}
    for s in spans:
        if s["name"] in (DISPATCH, WAIT):
            covered[s["parent"]] = covered.get(s["parent"], 0.0) + s["dur"]
    rest = [s["dur"] - covered.get(s["id"], 0.0) for s in spans
            if s["name"] == STEP]
    if not rest:
        return {}
    return {"step_rest_us_p50": 1e6 * median(rest),
            "step_rest_us_min": 1e6 * min(rest),
            "step_rest_us_max": 1e6 * max(rest)}


def has_wait(run: dict) -> bool:
    """Whether the program tells its wait from its dispatch: its
    ``serve/token_wait`` spans are on the trace's host plane with the
    recorder's ids."""
    red = sr.reduce_run(run)
    return bool(red and red["link"]["joined"].get(WAIT))


def session_rounds(run: dict) -> Optional[list]:
    """:func:`round_periods` of one traced run, computed once and kept on
    ``run``; None unless the run's session shows ``serve/token_wait``."""
    if "_host_round" not in run:
        out = None
        if has_wait(run):
            plane = sr.program_spans(sr.host_lines(
                tr.load(sr.trace_path(run))))
            out = round_periods(sr.reduce_run(run)["window_spans"], plane)
        run["_host_round"] = out or None
    return run["_host_round"]


def host_busy_share(run: dict) -> Optional[float]:
    rounds = session_rounds(run)
    if not rounds:
        return None
    waits = [w for _, ws in rounds for w in ws]
    busy = [p - sum(ws) for p, ws in rounds]
    run["notes"]["host_round"] = {
        "rounds": len(rounds),
        "period_ms_p50": 1e3 * median([p for p, _ in rounds]),
        "busy_ms_p50": 1e3 * median(busy),
        **step_rest(sr.reduce_run(run)["window_spans"])}
    if waits:
        # a stall of the machine inside a wait shows in ms_max, not in
        # the median
        run["notes"]["token_wait"] = {
            "n": len(waits), "ms_p50": 1e3 * median(waits),
            "ms_p99": 1e3 * percentile(waits, 99),
            "ms_max": 1e3 * max(waits)}
    return 100.0 * median([b / p for b, (p, _) in zip(busy, rounds)])


def decode_dispatch_ms_p50(run: dict) -> Optional[float]:
    spans = sr.window_spans(run, DISPATCH)
    ts = [s["dur"] for s in spans or [] if s["name"] == DISPATCH]
    return 1e3 * median(ts) if ts else None


def rounds_overlapped_share(run: dict) -> Optional[float]:
    spans = sr.window_spans(run, STEP)
    steps = sum(s["name"] == STEP for s in spans or [])
    if not steps:
        return None
    counters = [e for e in run.get("window_events") or []
                if e["kind"] == "counter"]
    run["notes"]["pipeline_drains"] = dict(sorted(collections.Counter(
        e.get("reason") for e in counters
        if e["name"] == "serve/pipeline_drains").items()))
    return 100.0 * sum(e["name"] == "serve/rounds_overlapped"
                       for e in counters) / steps


def idle_host_late_share(run: dict) -> Optional[float]:
    trace = run.get("trace")
    if not trace or not trace["window_s"] or not has_wait(run):
        return None
    idle = run["notes"].get("idle_by_span")     # ``reduce_run`` wrote it
    if idle is None:
        return None
    return 100.0 * sum(v for k, v in idle.items()
                       if k not in NOT_LATE) / trace["window_s"]
