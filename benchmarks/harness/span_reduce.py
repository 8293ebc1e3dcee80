"""The program's own spans and scopes, read out of a traced run.

``trace_reduce.py`` reads the device trace from OUTSIDE the program: by
instruction name. This file reads what the program says about itself, on
the same ``.xplane.pb``:

**Device work by scope.** On the chip an ``XLA Ops`` event carries the
instruction's text and no metadata, so the ``apx:`` scopes
(``apex_tpu/monitor/profile.py:scope``) are joined back in three steps: the
event's instruction name (``%fusion.97``) -> the ``op_name`` of that
instruction in the optimized HLO text of the program whose ``XLA Modules``
event contains the operation -> the ``apx:`` components of that path. The
text comes from the loaded executables themselves
(``backend.live_executables()``), so it is the text of what ran, compiled
here or loaded from the compile cache. Inside ``apx:amp_grad`` an
``op_name`` with ``transpose(`` is backward, one without is forward. A
fusion that spans two scopes keeps ONE ``op_name`` (a small weight update
was seen fused into the gradient's ``add_any`` and labelled backward):
nothing corrects for that. An operation without ``op_name`` (a layout copy
XLA put in) is ``unattributed``. So is a clone XLA makes to rematerialise a
value (``%fusion.4.remat_compressed = copy(%fusion.4)``,
``.remat_uncompressed``, ``.remat2``): it carries no metadata and no scope
counts it. Because it is named after its source, the clones' time is ALSO
kept apart, by the source's scopes and phase (``remat_clone_s``): in the
decode program the copies of the whole K/V pool that follow each K/V write
are such clones, and their time beside the write's own says which of the
two a change moved. That table rests on XLA's naming; no metric reads it.
Times are self times over every traced operation (``trace_reduce``'s "by
operation" base), mean over the chips, so forward + backward + update +
other + unattributed is the whole.

**Host work by span.** ``apex_tpu/monitor/spans.py:span`` writes every block
span to the recorder AND, as a ``TraceAnnotation`` with the stat ``span=<id>``,
to the ``/host:CPU`` plane. The id joins the two: the recorder keeps parent
links and attributes, the plane is on the device's clock. A span metric is
reported only where its spans are found in both. Each device idle gap is
named by the innermost program span or harness annotation over its midpoint
(``trace_reduce``'s rule, with finer names), and an annotation is split by
the host events nested in it (program spans and the runtime's own
``PjitFunction(..)``, ``PJRT_LoadedExecutable_Execute``, ...).

Two hazards. A reader gets only ``run``: the trace is found from
``ROOT``, this checkout, where ``run.py`` put it (tests point ``ROOT``
elsewhere). And an executable loaded from a compile cache that another
commit filled keeps THAT commit's instruction names and metadata (the cache
key leaves both out): a scope or kernel name this commit added is then not
in the run, and its reader reports nothing.
"""

from __future__ import annotations

import bisect
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace_reduce as tr
from .stats import median
from .tracing import ANNOTATIONS

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

GRAD_SCOPE = "amp_grad"
UPDATE_SCOPES = ("amp_unscale", "amp_optimizer", "amp_scaler")
PHASES = ("forward", "backward", "update", "other", "unattributed")
MIN_GAP_S = 20e-6
TOP_OPS = 8             # rows of the notes' tables by operation
TOP_NESTED = 12         # rows of an annotation's split

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?(\S+)\s+=\s")
_OP_NAME = re.compile(r'(?<![A-Za-z_])metadata=\{[^}]*?op_name="([^"]*)"')
_SCOPE = re.compile(r"apx:([^/()]+)")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")


# -- the join: instruction name -> op_name -> apx: path ----------------------

def parse_op_names(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: op_name}`` over every computation of one
    optimized HLO module; instructions without ``op_name`` are left out."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            meta = _OP_NAME.search(line)
            if meta:
                out[m.group(1)] = meta.group(1)
    return out


def hlo_module_name(hlo_text: str) -> Optional[str]:
    m = _MODULE.match(hlo_text.lstrip())
    return m.group(1) if m else None


def instruction_name(event_name: str) -> str:
    """``%fusion.97 = bf16[..] fusion(..)`` -> ``fusion.97``."""
    return event_name.strip().split(None, 1)[0].lstrip("%")


def phase_of(op_name: Optional[str]) -> str:
    if not op_name:
        return "unattributed"
    scopes = _SCOPE.findall(op_name)
    if GRAD_SCOPE in scopes:
        return "backward" if "transpose(" in op_name else "forward"
    return "update" if any(s in UPDATE_SCOPES for s in scopes) else "other"


def live_hlo_texts(wanted: Sequence[str]) -> List[str]:
    """Optimized HLO text of every loaded executable whose module is named
    in ``wanted``."""
    from jax.extend import backend
    wanted = set(wanted)
    texts = []
    for exe in backend.get_backend().live_executables():
        try:
            mods = exe.hlo_modules()
        except RuntimeError:        # a backend that keeps no text for it
            continue
        texts += [m.to_string() for m in mods if m.name in wanted]
    return texts


def remat_source(table: Dict[str, str], instruction: str) -> Optional[str]:
    """The ``op_name`` of the operation that a rematerialisation clone
    without one of its own (``<source>.remat...``) copies; None for any
    other instruction (module doc)."""
    if ".remat" not in instruction or instruction in table:
        return None
    return table.get(instruction.split(".remat", 1)[0])


def _best_table(tables: List[Dict[str, str]], names: set) -> Dict[str, str]:
    """Of several modules with one name, the one that holds the most of the
    instruction names the trace shows under that module."""
    return max(tables, key=lambda t: len(names & t.keys()), default={})


def device_scopes(profile, hlo_texts: Sequence[str]) -> dict:
    """Seconds by phase and by scope component over every traced operation
    (self times, mean over the chips), and of the remat clones among the
    unattributed by their source's phase and scopes. ``{}`` without a
    device plane."""
    tables: Dict[str, List[Dict[str, str]]] = {}
    for text in hlo_texts:
        tables.setdefault(hlo_module_name(text), []).append(
            parse_op_names(text))
    phase_s = dict.fromkeys(PHASES, 0.0)
    scope_s: Dict[str, float] = {}
    clone_s: Dict[str, float] = {}
    by_label: Dict[Tuple[str, str], float] = {}
    n_dev = 0
    for plane in profile.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        ops = tr._line_events(plane, tr.OPS_LINE)
        if not ops:
            continue
        n_dev += 1
        modules = sorted(tr._line_events(plane, tr.MODULES_LINE))
        starts = [m[0] for m in modules]
        per_module: Dict[str, list] = {}
        for (s, _, name), (_, self_t) in zip(ops, tr.self_times(ops)):
            i = bisect.bisect_right(starts, s) - 1
            mod = modules[i][2] if i >= 0 and s < modules[i][1] else None
            per_module.setdefault(mod, []).append((name, self_t))
        for mod, rows in per_module.items():
            seen = {instruction_name(n) for n, _ in rows}
            table = _best_table(
                tables.get(tr.module_name(mod), []) if mod else [], seen)
            for name, self_t in rows:
                instr = instruction_name(name)
                op_name = table.get(instr)
                phase = phase_of(op_name)
                phase_s[phase] += self_t
                for sc in set(_SCOPE.findall(op_name or "")):
                    scope_s[sc] = scope_s.get(sc, 0.0) + self_t
                source = remat_source(table, instr)
                if source:
                    for key in {phase_of(source), *_SCOPE.findall(source)}:
                        clone_s[key] = clone_s.get(key, 0.0) + self_t
                key = (tr.op_label(name), phase)
                by_label[key] = by_label.get(key, 0.0) + self_t
    if not n_dev:
        return {}
    busy = sum(phase_s.values())

    def top_of(phases):
        rows = sorted(((v / n_dev, k[0], k[1]) for k, v in by_label.items()
                       if k[1] in phases), reverse=True)[:TOP_OPS]
        return [[label, phase, secs] for secs, label, phase in rows]

    return {
        "busy_s": busy / n_dev,
        "phase_s": {k: v / n_dev for k, v in phase_s.items()},
        "scope_s": {k: v / n_dev for k, v in sorted(scope_s.items())},
        "remat_clone_s": {k: v / n_dev for k, v in sorted(clone_s.items())},
        "attributed_share": 100.0 * (1.0 - phase_s["unattributed"] / busy)
        if busy else None,
        "top_ops": top_of(PHASES),
        "top_unattributed": top_of(("unattributed",)),
    }


# -- the host plane ------------------------------------------------------------

def host_lines(profile) -> Dict[str, List[Tuple[float, float, str, object]]]:
    """``{line: [(start, end, name, span id or None)]}`` of ``/host:CPU``."""
    out: Dict[str, list] = {}
    for plane in profile.planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            rows = out.setdefault(line.name, [])
            for ev in line.events:
                s = ev.start_ns * 1e-9
                sid = next((v for k, v in ev.stats if k == "span"), None)
                rows.append((s, s + ev.duration_ns * 1e-9, ev.name, sid))
    return out


def program_spans(lines) -> List[Tuple[float, float, str, int]]:
    """The host events that carry a span id: the program's block spans."""
    return [r for rows in lines.values() for r in rows if r[3] is not None]


def annotation_split(lines, name: str) -> Optional[dict]:
    """The annotation ``name``: its durations, its self time on its own
    thread, and the host events nested in it, by name, each with its own
    self time (so that one thread's rows add up to the annotation)."""
    found = [(ln, r) for ln, rows in lines.items() for r in rows
             if r[2] == name]
    if not found:
        return None
    nested: Dict[Tuple[str, str], List[float]] = {}
    own_self = []
    for ln, rows in lines.items():
        selfs = tr.self_times([(s, e, n) for s, e, n, _ in rows])
        for (s, e, n, _), (_, self_t) in zip(rows, selfs):
            if n == name:
                own_self.append(self_t)
                continue
            if any(a[0] <= s and e <= a[1] for _, a in found):
                row = nested.setdefault((re.sub(r"/\d+$", "", ln), n),
                                        [0, 0.0, 0.0])
                row[0] += 1
                row[1] += self_t
                row[2] += e - s
    durs = [r[1] - r[0] for _, r in found]
    n = len(found)
    rows = sorted(nested.items(), key=lambda kv: -kv[1][1])[:TOP_NESTED]
    return {
        "n": n, "ms_p50": 1e3 * median(durs), "ms_max": 1e3 * max(durs),
        "self_ms_mean": 1e3 * sum(own_self) / n,
        "nested": [{"thread": k[0], "event": k[1], "calls": v[0] / n,
                    "self_ms": 1e3 * v[1] / n, "ms": 1e3 * v[2] / n}
                   for k, v in rows],
    }


def device_gaps(profile) -> List[List[Tuple[float, float]]]:
    """Per chip, its idle gaps over whole periods (``trace_reduce``'s
    window: first program's start to last program's start)."""
    out = []
    for plane in profile.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        ops = tr._line_events(plane, tr.OPS_LINE)
        modules = sorted(tr._line_events(plane, tr.MODULES_LINE))
        if not ops or len(modules) < 2:
            continue
        w0, w1 = modules[0][0], modules[-1][0]
        busy = tr.merge(tr.clip(((s, e) for s, e, _ in ops), w0, w1))
        out.append(tr.subtract([(w0, w1)], busy))
    return out


def idle_by_span(gaps: Sequence[List[Tuple[float, float]]], lines
                 ) -> Dict[str, float]:
    """Idle seconds (mean over the chips of :func:`device_gaps`) by the
    innermost program span or harness annotation over each gap's
    midpoint."""
    covers = [(s, e, n) for s, e, n, _ in program_spans(lines)]
    covers += [(s, e, n) for rows in lines.values() for s, e, n, sid in rows
               if sid is None and n in ANNOTATIONS]
    total: Dict[str, float] = {}
    for chip in gaps:
        for k, v in tr._attribute_gaps(chip, covers, MIN_GAP_S).items():
            total[k] = total.get(k, 0.0) + v / len(gaps)
    return dict(sorted(total.items(), key=lambda kv: -kv[1]))


# -- the recorder's side, and the join by span id -----------------------------

def closed_spans(events: Sequence[dict]) -> List[dict]:
    """Spans that opened and closed inside ``events`` (the recorder's
    window): ``{id, name, parent, t0, dur, **attributes}``."""
    starts = {e["value"]: e for e in events if e["kind"] == "span_start"}
    skip = ("kind", "name", "value", "t", "span", "step")
    out = []
    for e in events:
        st = starts.get(e.get("span")) if e["kind"] == "span_end" else None
        if st is not None:
            out.append({**{k: v for k, v in {**st, **e}.items()
                           if k not in skip},
                        "id": e["span"], "name": e["name"],
                        "t0": e["t"] - e["value"], "dur": e["value"]})
    return out


def clock_link(lines, spans: Sequence[dict]) -> dict:
    """Joins the recorder's closed spans to the plane's by id: how many of
    each name, and the largest disagreement between the two sinks'
    durations."""
    rec = {s["id"]: s for s in spans}
    joined: Dict[str, int] = {}
    diffs = []
    for s, e, name, sid in program_spans(lines):
        r = rec.get(sid)
        if r is not None and r["name"] == name:
            joined[name] = joined.get(name, 0) + 1
            diffs.append(abs((e - s) - r["dur"]))
    return {"joined": joined,
            "duration_diff_us_max": 1e6 * max(diffs) if diffs else None}


def round_host_s(spans: Sequence[dict]) -> List[float]:
    """Of each ``serve/round``, the host's seconds outside the dispatching
    spans: its duration minus its ``serve/prefill`` and ``serve/decode_step``
    children. Not the device's idle time: the device also waits INSIDE
    those spans, from the span's opening to the dispatch's arrival
    (``idle_by_span`` has that part, ``device_idle_share`` the whole)."""
    covered: Dict[int, float] = {}
    for s in spans:
        if s["name"] in ("serve/prefill", "serve/decode_step"):
            covered[s["parent"]] = covered.get(s["parent"], 0.0) + s["dur"]
    return [s["dur"] - covered.get(s["id"], 0.0) for s in spans
            if s["name"] == "serve/round"]


def span_table(spans: Sequence[dict]) -> Dict[str, dict]:
    """The closed spans by name: how many, their median and their summed
    milliseconds (a round's phases, and the cost of ``serve/gauges``)."""
    durs: Dict[str, List[float]] = {}
    for s in spans:
        durs.setdefault(s["name"], []).append(s["dur"])
    return {k: {"n": len(v), "ms_p50": 1e3 * median(v), "ms_sum": 1e3 * sum(v)}
            for k, v in sorted(durs.items())}


# -- one pass per run ----------------------------------------------------------

def trace_path(run: dict) -> Optional[str]:
    """The ``.xplane.pb`` of this run's profiler session, or None. A
    session that started wiped what an earlier run left there."""
    if not run.get("traced"):
        return None
    try:
        return tr.newest_xplane(
            os.path.join(ROOT, ".bench_trace", run["workload"]))
    except FileNotFoundError:
        return None


def reduce_run(run: dict) -> Optional[dict]:
    """Everything above for one traced run, computed once and kept on
    ``run``; the tables that are no metric go to ``run["notes"]``. None
    where the run has no trace of its own."""
    if "_span_reduce" in run:
        return run["_span_reduce"]
    path = trace_path(run)
    out = None
    if path is not None:
        profile = tr.load(path)
        lines = host_lines(profile)
        gaps = device_gaps(profile)
        wanted = {tr.module_name(n) for plane in profile.planes
                  if tr.DEVICE_PLANE.match(plane.name)
                  for _, _, n in tr._line_events(plane, tr.MODULES_LINE)}
        scopes = device_scopes(profile, live_hlo_texts(wanted))
        window = closed_spans(run.get("window_events") or [])
        link = clock_link(lines, window)
        out = {"scopes": scopes, "link": link, "window_spans": window}
        notes = run["notes"]
        notes["span_clock"] = link
        if window:
            notes["window_spans"] = span_table(window)
        if scopes:
            busy = scopes["busy_s"]
            notes["scope_shares"] = {
                "busy_s": busy,
                "attributed_share": scopes["attributed_share"],
                **{k: 100.0 * v / busy
                   for k, v in scopes["phase_s"].items()},
                "remat_clones": {k: 100.0 * v / busy for k, v in
                                 scopes["remat_clone_s"].items()},
                "top_ops": scopes["top_ops"],
                "top_unattributed": scopes["top_unattributed"]}
            notes["idle_by_span"] = idle_by_span(gaps, lines)
        split = annotation_split(lines, "dispatch")
        if split:
            notes["dispatch_split"] = split
    run["_span_reduce"] = out
    return out


def _scopes(run: dict, scope: str) -> Optional[dict]:
    """The run's table by scope, where its trace shows ``apx:<scope>``."""
    red = reduce_run(run)
    sc = red and red["scopes"]
    return sc if sc and sc["busy_s"] and scope in sc["scope_s"] else None


def phase_share(run: dict, *phases: str) -> Optional[float]:
    """Percent of the operations' busy time in ``phases``; None unless the
    run's trace shows the step's ``apx:amp_grad`` scope."""
    sc = _scopes(run, GRAD_SCOPE)
    return sc and 100.0 * sum(sc["phase_s"][p] for p in phases) / sc["busy_s"]


def scope_share(run: dict, scope: str) -> Optional[float]:
    """Percent of the operations' busy time under ``apx:<scope>``."""
    sc = _scopes(run, scope)
    return sc and 100.0 * sc["scope_s"][scope] / sc["busy_s"]


def window_spans(run: dict, name: str) -> Optional[List[dict]]:
    """The recorder's closed spans of the window, all names, where spans
    called ``name`` are on the trace's host plane with the recorder's ids;
    None otherwise."""
    red = reduce_run(run)
    if not red or not red["link"]["joined"].get(name):
        return None
    return red["window_spans"]


def flash_roofline(run: dict, direction: str) -> Optional[float]:
    """The flash attention kernels of one direction (``fwd`` | ``bwd``):
    the least time the chip could take for them over the device time of
    the instructions ``apx_flash_attention_<direction>``."""
    from . import bytes as bytes_mod
    from . import flops as flops_mod
    a = run["program"].attention
    if run.get("trace") is None or a.get("kind") != "flash":
        return None
    took = tr.kernel_seconds(run["trace"],
                             rf"^apx_flash_attention_{direction}")
    if not took:
        return None
    shape = (a["batch"], a["heads"], a["seq"], a["head_dim"])
    least, bound = bytes_mod.roofline_seconds(
        getattr(flops_mod, f"flash_{direction}_flops")(*shape, a["causal"]),
        getattr(bytes_mod, f"flash_{direction}_bytes")(*shape), run["peak"])
    run["notes"][f"flash_attention_{direction}_roofline_bound"] = bound
    return 100.0 * least * a["layers"] * run["traced"]["steps"] / took
