"""From a profiler trace to numbers: device busy and idle time, time by
operation, kernel time, collective time and its exposed part, and the idle
gaps by what the host was doing.

Reads ``*.xplane.pb`` through ``jax.profiler.ProfileData`` (needs only JAX).
What a trace of a TPU v5e looks like (looked at by hand, PR 22): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
executed program), ``XLA Ops`` (one event per HLO instruction, named by the
instruction's whole text: ``%fusion.97 = bf16[...] fusion(...)``) and
``Async XLA Ops`` (the span from an asynchronous op's start to its done);
and one plane ``/host:CPU`` whose lines are host threads, where a
``jax.profiler.TraceAnnotation("name")`` is an event called ``name``.
Device and host events share one time base to within a millisecond or two
(a device program was seen to start 1.3 ms before the host event that
enqueued it), so a gap shorter than that is attributed with that error.

A Pallas kernel is an ``XLA Ops`` event whose text has
``custom_call_target="tpu_custom_call"``; its instruction name is the
kernel's name (``%apx_flash_attention.4``). The ``apx:`` profile scopes of
the program are op metadata and are NOT in the event. An ``XLA Modules``
event is named ``jit_<function>(<fingerprint>)``.

Two bases, kept apart. Shares OF THE WINDOW (busy, idle, collectives, idle
gaps) are taken over whole periods: per chip from the start of its first
executed program to the start of its last one, so that every program in the
window is there with the gap that follows it (first op to last op would hold
n programs and n-1 gaps, and understate the idle share of a loop that awaits
every step). Sums BY OPERATION (``op_s``, ``kernel_s``, ``pallas_s``,
``ops_busy_s``) are over every traced operation, so that they go with what
the host counted between the profiler's start and stop.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all",
                    "collective-broadcast")
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'

_NAME = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?(?:\s*=\s*(.*))?$", re.S)
_SHAPE = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")

Interval = Tuple[float, float]


# -- reading -----------------------------------------------------------------

def newest_xplane(logdir: str) -> str:
    """The ``.xplane.pb`` of the newest session under ``logdir``."""
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir!r}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    """``ProfileData`` from an ``.xplane.pb`` or, for hand-written test
    fixtures, an XSpace text proto (``.textproto``)."""
    from jax.profiler import ProfileData
    if path.endswith(".textproto"):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def base_name(event_name: str) -> str:
    """``%fusion.97 = bf16[..] fusion(..)`` -> ``fusion``."""
    m = _NAME.match(event_name.strip())
    return m.group(1) if m else event_name


def op_label(event_name: str) -> str:
    """A short stable label for the breakdown: the instruction's base name
    and, where the text gives it, the shape it produces."""
    m = _NAME.match(event_name.strip())
    if not m:
        return event_name[:80]
    base, rest = m.group(1), m.group(2) or ""
    sm = _SHAPE.match(rest)
    return f"{base} {sm.group(1)}" if sm else base


def is_collective(event_name: str) -> bool:
    """By the instruction's name. On the chip a reduce-scatter was seen as
    ``%reduce_scatter.N`` (a fusion XLA names with an underscore), so
    underscores count as hyphens."""
    b = base_name(event_name).replace("_", "-")
    return any(b == k or b.startswith(k + "-") for k in COLLECTIVE_KINDS)


def is_pallas(event_name: str) -> bool:
    return PALLAS_MARK in event_name


def module_name(event_name: str) -> str:
    """``jit_prefill(4034058567281636267)`` -> ``jit_prefill``."""
    return event_name.split("(", 1)[0]


# -- interval arithmetic -----------------------------------------------------

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of ``intervals`` inside ``[lo, hi]``."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The part of the union of ``a`` not covered by the union of ``b``."""
    out: List[Interval] = []
    bm = merge(b)
    j = 0
    for s, e in merge(a):
        cur = s
        while j < len(bm) and bm[j][1] <= cur:
            j += 1
        k = j
        while k < len(bm) and bm[k][0] < e:
            if bm[k][0] > cur:
                out.append((cur, bm[k][0]))
            cur = max(cur, bm[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: Sequence[Tuple[float, float, str]]
               ) -> List[Tuple[str, float]]:
    """``(name, self seconds)`` per event of one line: an event's duration
    minus that of the events nested directly inside it (a ``while`` or
    ``call`` on the ops line encloses its body's events)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    self_t = [e[1] - e[0] for e in events]
    stack: List[int] = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            self_t[stack[-1]] -= e - s
        stack.append(i)
    return [(events[i][2], max(0.0, self_t[i])) for i in range(len(events))]


# -- the reduction -----------------------------------------------------------

def _line_events(plane, line_name: str) -> List[Tuple[float, float, str]]:
    out = []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for ev in line.events:
            s = ev.start_ns * 1e-9
            out.append((s, s + ev.duration_ns * 1e-9, ev.name))
    return out


def _host_annotations(profile, names: Sequence[str]
                      ) -> List[Tuple[float, float, str]]:
    want = set(names)
    out = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in want:
                    s = ev.start_ns * 1e-9
                    out.append((s, s + ev.duration_ns * 1e-9, ev.name))
    return out


def _attribute_gaps(gaps: Sequence[Interval],
                    annotations: Sequence[Tuple[float, float, str]],
                    min_gap_s: float) -> Dict[str, float]:
    """Each idle gap goes to the innermost host annotation that covers its
    midpoint, or to ``outside-annotations``."""
    by_name: Dict[str, float] = {}
    for s, e in gaps:
        if e - s < min_gap_s:
            name = f"gaps-under-{min_gap_s * 1e6:g}us"
        else:
            mid = 0.5 * (s + e)
            cover = [a for a in annotations if a[0] <= mid <= a[1]]
            name = (min(cover, key=lambda a: a[1] - a[0])[2] if cover
                    else "outside-annotations")
        by_name[name] = by_name.get(name, 0.0) + (e - s)
    return by_name


def reduce_profile(profile, annotations: Sequence[str] = (),
                   min_gap_s: float = 20e-6, top: int = 10) -> dict:
    """All the trace-derived numbers of one traced window (module doc: the
    two bases). Per chip the window is ``[start of its first executed
    program, start of its last one]``; a chip whose trace holds fewer than
    two programs falls back to the first op .. the last op of all chips and
    the summary says so (``whole_periods`` false). Times "by operation" are self times, so they
    add up to ``ops_busy_s`` where operations do not overlap. Values at the
    top level are means over the chips.
    """
    devices = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        ops = _line_events(plane, OPS_LINE)
        if ops:
            devices.append((int(m.group(1)), plane.name, ops,
                            _line_events(plane, ASYNC_LINE),
                            sorted(_line_events(plane, MODULES_LINE))))
    if not devices:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": [],
                "n_device_ops": 0}
    devices.sort()
    first_op = min(s for _, _, ops, _, _ in devices for s, _, _ in ops)
    last_op = max(e for _, _, ops, _, _ in devices for _, e, _ in ops)
    host = _host_annotations(profile, annotations)

    per_dev = []
    op_time: Dict[str, float] = {}
    kernel_time: Dict[str, float] = {}
    module_times: Dict[str, List[float]] = {}
    for idx, name, ops, async_ops, modules in devices:
        whole = len(modules) >= 2
        w0, w1 = (modules[0][0], modules[-1][0]) if whole \
            else (first_op, last_op)
        busy_iv = merge(clip(((s, e) for s, e, _ in ops), w0, w1))
        busy = sum(e - s for s, e in busy_iv)
        coll_iv = clip([(s, e) for s, e, n in ops if is_collective(n)] +
                       [(s, e) for s, e, n in async_ops if is_collective(n)],
                       w0, w1)
        compute_iv = [(s, e) for s, e, n in ops if not is_collective(n)]
        pallas = 0.0
        for n, t in self_times(ops):
            lbl = op_label(n)
            op_time[lbl] = op_time.get(lbl, 0.0) + t
            if is_pallas(n):
                pallas += t
                b = base_name(n)
                kernel_time[b] = kernel_time.get(b, 0.0) + t
        for s, e, n in modules:
            module_times.setdefault(module_name(n), []).append(e - s)
        gaps = subtract([(w0, w1)], busy_iv)
        per_dev.append({
            "device": idx, "plane": name, "n_ops": len(ops),
            "programs": len(modules), "whole_periods": whole,
            "window_s": w1 - w0, "busy_s": busy,
            "idle_s": (w1 - w0) - busy,
            "ops_busy_s": measure((s, e) for s, e, _ in ops),
            "pallas_s": pallas, "collective_s": measure(coll_iv),
            "collective_exposed_s": measure(subtract(coll_iv, compute_iv)),
            "longest_gap_s": max((e - s for s, e in gaps), default=0.0),
            "gaps": _attribute_gaps(gaps, host, min_gap_s),
        })
    n = len(per_dev)

    def mean(key):
        return sum(d[key] for d in per_dev) / n

    gap_names = sorted({k for d in per_dev for k in d["gaps"]})
    idle_gaps = sorted(
        ((k, sum(d["gaps"].get(k, 0.0) for d in per_dev) / n)
         for k in gap_names), key=lambda kv: -kv[1])[:top]
    device_ops = sorted(((k, v / n) for k, v in op_time.items()),
                        key=lambda kv: -kv[1])
    return {
        "window_s": mean("window_s"),
        "whole_periods": all(d["whole_periods"] for d in per_dev),
        "busy_s": mean("busy_s"),
        "idle_s": mean("idle_s"),
        "collective_s": mean("collective_s"),
        "collective_exposed_s": mean("collective_exposed_s"),
        "ops_busy_s": mean("ops_busy_s"),
        "pallas_s": mean("pallas_s"),
        "kernel_s": {k: v / n for k, v in sorted(kernel_time.items())},
        "op_s": dict(device_ops),
        "module_s": module_times,
        "n_device_ops": sum(d["n_ops"] for d in per_dev),
        "host_annotations_found": len(host),
        "devices": [{k: v for k, v in d.items() if k != "gaps"}
                    for d in per_dev],
        "breakdown": {
            "device_ops": [[k, v] for k, v in device_ops[:top]],
            "idle_gaps": [[k, v] for k, v in idle_gaps],
        },
    }


def reduce_file(path: str, annotations: Sequence[str] = (), **kw) -> dict:
    return reduce_profile(load(path), annotations, **kw)


def kernel_seconds(summary: dict, pattern: str) -> Optional[float]:
    """Device seconds (mean over chips) of the Pallas kernels whose name
    matches ``pattern``; None when the trace has no such kernel."""
    rx = re.compile(pattern)
    hits = [v for k, v in summary.get("kernel_s", {}).items()
            if rx.search(k)]
    return sum(hits) if hits else None


def module_seconds(summary: dict, pattern: str) -> List[float]:
    """Device durations of the executed programs (``XLA Modules`` events,
    all chips) whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return [t for k, ts in summary.get("module_s", {}).items()
            if rx.search(k) for t in ts]
