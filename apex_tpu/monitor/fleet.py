"""Multi-replica telemetry: scrape N replica exports, aggregate with
honest semantics, alert on SLO burn, emit autoscale decision events.

Every earlier telemetry surface observes ONE process. Production chat
traffic is many serve replicas behind a router; this module is the
fleet-shaped counterpart of what ``merge`` does for training ranks:

- :class:`ReplicaSet` — the registry of replica endpoints: live
  ``MetricsExporter`` HTTP URLs (``export.serve_engine(engine, ...)``
  registers the engine via the ``on_export`` hook) and/or file-backed
  exposition snapshots (``monitor export --once`` output).
- :class:`FleetPoller` — scrapes every endpoint through the existing
  ``parse_prometheus``, tolerating dead/slow replicas: a per-scrape
  timeout or refused connection marks the replica ``up=0`` with its
  last-seen age and the poll loop continues — a dying replica can
  NEVER kill fleet observability. Aggregation semantics are honest by
  construction:

  ===========  ========================================================
  counters     summed across live replicas (monotone totals add)
  gauges       kept per-replica + min/max/sum/mean views (a last-value
               gauge has no single honest scalar)
  histograms   ``LogHistogram.merge`` of the reconstructed per-replica
               bucket snapshots — fleet p50/p99 come from ONE merged
               histogram over the pooled population, never an average
               of per-replica percentiles (which is not a percentile
               of anything)
  ===========  ========================================================

  Each poll feeds the :mod:`~apex_tpu.monitor.slo` policy layer
  (multi-window burn-rate ``slo_alert``s + ``scale_decision`` events,
  both typed health events) and, with a recorder given, emits one
  ``kind="fleet"`` event per poll — the ``## fleet`` block of
  ``report.aggregate()``.

- :class:`ReplicaThreadRouter` + :class:`LocalFleet` — the CPU-testable
  multi-replica harness: K ``ServeEngine``s on threads, each under
  ``export.serve_engine(export_port=0)`` with its OWN concrete Recorder (the router
  is attached as the single global recorder and routes every write-path
  hook to the calling thread's recorder), registered into a
  ``ReplicaSet`` as their ports bind. Purity: all of this is host-side
  thread plumbing — compiled prefill/decode programs are byte-identical
  with a fleet poller scraping (asserted in ``tests/test_fleet.py``).

CLI::

    python -m apex_tpu.monitor fleet ENDPOINT [ENDPOINT...] \
        [--watch | --once] [--json] [--interval S] [--timeout S]

where ENDPOINT is an ``http(s)://...`` URL or an exposition file path;
``--once`` exits non-zero when any SLO alert fires (the CI gate).

No jax anywhere in this module (APX001) — imported lazily via
``apex_tpu.monitor.__getattr__`` like ``export``.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import threading
import time
from typing import Dict, Optional

from apex_tpu.monitor import export
from apex_tpu.monitor import slo as slo_mod
from apex_tpu.monitor.export import parse_prometheus, parse_prometheus_types
from apex_tpu.monitor.recorder import Recorder, json_safe
from apex_tpu.monitor.spans import LogHistogram, hist_summary

__all__ = ["ReplicaSet", "FleetPoller", "ReplicaThreadRouter",
           "LocalFleet", "classify_samples",
           "histogram_snapshot_from_buckets", "main"]

# exposition defaults assumed when reconstructing histograms from
# bucket edges (Recorder.observe's LogHistogram defaults)
DEFAULT_HIST = {"lo": 1e-3, "hi": 1e7, "buckets_per_decade": 10}


# ---------------------------------------------------------------------------
# scrape classification: one exposition document -> per-replica views
# ---------------------------------------------------------------------------

def classify_samples(parsed: dict, default_replica: str = "",
                     types: Optional[dict] = None) -> dict:
    """Split ``parse_prometheus`` output into per-replica typed views
    ``{replica: {counters, gauges, histograms, scrape_time}}``.

    Label-aware: a ``replica=`` label keys the sample (one document may
    carry many replicas — e.g. concatenated scrapes); unlabeled samples
    fall back to ``default_replica`` (the registered endpoint id).
    ``types`` (``parse_prometheus_types`` output) takes precedence when
    it names a sample — a gauge declared ``# TYPE ... gauge`` stays a
    gauge even when its name ends in ``_total``. Without a declared
    type, classification follows the exporter's naming convention:
    ``*_bucket{le=...}`` + ``*_sum``/``*_count`` siblings are
    histograms, other ``*_total``/``*_count`` samples are counters,
    everything else is a gauge."""
    types = types or {}
    staged: Dict[str, dict] = {}
    views: Dict[str, dict] = {}

    def view(rid):
        return views.setdefault(rid, {
            "counters": {}, "gauges": {}, "histograms": {},
            "scrape_time": None})

    for (name, labels), value in parsed.items():
        lab = dict(labels)
        rid = lab.get("replica", default_replica)
        v = view(rid)
        if name == "apex_replica_up":
            continue                       # the poller decides up-ness
        if name == "apex_scrape_timestamp_seconds":
            v["scrape_time"] = value
            continue
        if name.endswith("_bucket") and "le" in lab:
            base = name[:-len("_bucket")]
            h = v["histograms"].setdefault(
                base, {"buckets": {}, "sum": 0.0, "count": 0})
            h["buckets"][_le(lab["le"])] = value
            continue
        staged.setdefault(rid, {})[name] = value
    for rid, samples in staged.items():
        v = view(rid)
        hists = v["histograms"]
        for name, value in samples.items():
            if name.endswith("_sum") and name[:-len("_sum")] in hists:
                hists[name[:-len("_sum")]]["sum"] = value
            elif name.endswith("_count") and name[:-len("_count")] in hists:
                hists[name[:-len("_count")]]["count"] = int(value)
            elif types.get(name) == "gauge":
                v["gauges"][name] = value
            elif types.get(name) == "counter" \
                    or name.endswith("_total") or name.endswith("_count"):
                v["counters"][name] = value
            else:
                v["gauges"][name] = value
    return views


def _le(raw: str) -> float:
    return float("inf") if raw == "+Inf" else float(raw)


def histogram_snapshot_from_buckets(hist: dict, *, lo: float = None,
                                    hi: float = None,
                                    buckets_per_decade: int = None) -> dict:
    """Invert the exporter's cumulative-bucket rendering back into a
    :meth:`LogHistogram.snapshot` payload (so fleet merging can use
    ``LogHistogram.merge``). Bucket index recovery relies on the
    exporter emitting each populated bucket's exact upper edge
    ``lo * 10^((i+1)/bpd)``.

    Documented slack vs the original histogram: the exposition folds
    the underflow bin into the first populated bucket's cumulative
    count (indistinguishable after rendering), and exact min/max are
    not exported — they are replaced by the populated bucket range. In
    range, percentiles are unaffected (same buckets, same midpoints)."""
    lo = float(lo if lo is not None else DEFAULT_HIST["lo"])
    hi = float(hi if hi is not None else DEFAULT_HIST["hi"])
    bpd = int(buckets_per_decade if buckets_per_decade is not None
              else DEFAULT_HIST["buckets_per_decade"])
    proto = LogHistogram(lo=lo, hi=hi, buckets_per_decade=bpd)
    count = int(hist.get("count") or 0)
    counts: Dict[str, int] = {}
    prev = 0.0
    last_finite_cum = 0.0
    for le in sorted(hist.get("buckets") or {}):
        cum = hist["buckets"][le]
        if math.isinf(le):
            continue
        i = int(round(math.log10(le / lo) * bpd)) - 1
        i = min(max(i, 0), proto.n_buckets - 1)
        c = int(round(cum - prev))
        if c > 0:
            counts[str(i)] = counts.get(str(i), 0) + c
        prev = cum
        last_finite_cum = cum
    overflow = max(0, count - int(round(last_finite_cum)))
    mn = mx = None
    if counts:
        idxs = sorted(int(i) for i in counts)
        mn = proto.bucket_bounds(idxs[0])[0]
        mx = proto.bucket_bounds(idxs[-1])[1]
    if overflow:
        mx = hi
    return {"lo": lo, "hi": hi, "buckets_per_decade": bpd,
            "count": count, "sum": float(hist.get("sum") or 0.0),
            "min": mn, "max": mx, "underflow": 0, "overflow": overflow,
            "counts": counts}


# ---------------------------------------------------------------------------
# replica registry + poller
# ---------------------------------------------------------------------------

class _Replica:
    __slots__ = ("rid", "endpoint", "kind", "up", "last_seen_t", "error")

    def __init__(self, rid: str, endpoint: str):
        self.rid = rid
        self.endpoint = endpoint
        self.kind = "url" if "://" in endpoint else "file"
        self.up = None                 # unknown until first poll
        self.last_seen_t = None        # monotonic, poller clock
        self.error = None


class ReplicaSet:
    """Registry of replica endpoints the :class:`FleetPoller` scrapes.

    ``add(rid, endpoint)`` takes an HTTP(S) ``/metrics`` URL or an
    exposition file path; :meth:`register_engine` is the live-serve
    hook — pass it as ``export.serve_engine(engine,
    on_export=rs.register_engine)`` and the engine is registered the
    moment its port binds."""

    def __init__(self):
        self._lock = threading.Lock()
        self._replicas: Dict[str, _Replica] = {}

    def add(self, rid: str, endpoint: str) -> None:
        with self._lock:
            self._replicas[str(rid)] = _Replica(str(rid), str(endpoint))

    def register_engine(self, engine, port: int,
                        addr: str = "127.0.0.1") -> None:
        self.add(engine.replica_id, f"http://{addr}:{port}/metrics")

    def remove(self, rid: str) -> None:
        with self._lock:
            self._replicas.pop(str(rid), None)

    def ids(self) -> list:
        with self._lock:
            return sorted(self._replicas)

    def items(self) -> list:
        with self._lock:
            return [self._replicas[k] for k in sorted(self._replicas)]

    def __len__(self):
        with self._lock:
            return len(self._replicas)


class FleetPoller:
    """Scrape a :class:`ReplicaSet`, aggregate, evaluate SLOs, decide.

    One :meth:`poll_once` call never raises on a replica's account: a
    scrape failure (timeout, refused connection, unreadable file,
    garbage payload) marks that replica ``up=0`` with its last-seen
    age and the loop continues. Aggregates cover LIVE replicas only —
    a dead replica's stale counters age out of the fleet view (its row
    stays in the replica table) rather than being frozen in as if
    still current."""

    def __init__(self, replica_set: ReplicaSet, *, recorder=None,
                 timeout_s: float = 2.0, slos=None, windows=None,
                 evaluator=None, decider=None, now=time.monotonic):
        self.replica_set = replica_set
        self.recorder = recorder
        self.timeout_s = float(timeout_s)
        self.evaluator = evaluator if evaluator is not None else \
            slo_mod.SLOEvaluator(slos=slos, windows=windows)
        self.decider = decider if decider is not None else \
            slo_mod.AutoscaleDecider()
        self.now = now
        self.polls = 0
        self.alerts: list = []         # accumulated across polls
        self.decisions: list = []
        self.last_view: Optional[dict] = None

    # -- scraping ----------------------------------------------------------
    def _scrape(self, rep: _Replica) -> str:
        if rep.kind == "file":
            with open(rep.endpoint) as f:
                return f.read()
        import urllib.request
        with urllib.request.urlopen(rep.endpoint,
                                    timeout=self.timeout_s) as resp:
            return resp.read().decode("utf-8", "replace")

    def poll_once(self) -> dict:
        """Scrape every replica once and return the fleet view dict
        (also kept on ``self.last_view``); emits ``fleet`` +
        ``health_event`` records into the recorder when one is set."""
        t = self.now()
        self.polls += 1
        live_views: Dict[str, dict] = {}
        rows = []
        for rep in self.replica_set.items():
            try:
                text = self._scrape(rep)
                views = classify_samples(
                    parse_prometheus(text), default_replica=rep.rid,
                    types=parse_prometheus_types(text))
            except Exception as e:           # noqa: BLE001 — never fatal
                rep.up = False
                rep.error = f"{type(e).__name__}: {e}"
            else:
                rep.up = True
                rep.error = None
                rep.last_seen_t = t
                live_views.update(views)
            age = None if rep.last_seen_t is None \
                else round(t - rep.last_seen_t, 3)
            rows.append({"replica": rep.rid, "endpoint": rep.endpoint,
                         "up": 1 if rep.up else 0, "age_s": age,
                         "error": rep.error})
        fleet = self._aggregate(live_views)
        fleet.update({
            "t": round(t, 3), "poll": self.polls,
            "n_replicas": len(rows),
            "n_up": sum(r["up"] for r in rows),
            "replicas": rows,
        })
        alerts = self.evaluator.observe(fleet, t)
        decision = self.decider.decide(fleet, alerts)
        decisions = [decision] if decision else []
        fleet["alerts"] = alerts
        fleet["decisions"] = decisions
        self.alerts.extend(alerts)
        self.decisions.extend(decisions)
        self.last_view = fleet
        self._emit(fleet, alerts, decisions)
        return fleet

    # -- aggregation -------------------------------------------------------
    @staticmethod
    def _aggregate(views: Dict[str, dict]) -> dict:
        counters: Dict[str, float] = {}
        counters_by: Dict[str, dict] = {}
        gauges: Dict[str, dict] = {}
        hist_parts: Dict[str, list] = {}
        for rid in sorted(views):
            v = views[rid]
            for k, val in v["counters"].items():
                counters[k] = counters.get(k, 0.0) + val
                counters_by.setdefault(k, {})[rid] = val
            for k, val in v["gauges"].items():
                g = gauges.setdefault(
                    k, {"min": val, "max": val, "sum": 0.0,
                        "by_replica": {}})
                g["min"] = min(g["min"], val)
                g["max"] = max(g["max"], val)
                g["sum"] += val
                g["by_replica"][rid] = val
            for base, h in v["histograms"].items():
                hist_parts.setdefault(base, []).append(
                    histogram_snapshot_from_buckets(h))
        for g in gauges.values():
            g["mean"] = g["sum"] / len(g["by_replica"])
        merged: Dict[str, dict] = {}
        summaries: Dict[str, dict] = {}
        for base, parts in hist_parts.items():
            snap = LogHistogram.merge(*parts).snapshot()
            merged[base] = snap
            summaries[base] = hist_summary(snap)
        return {"counters": counters, "counters_by_replica": counters_by,
                "gauges": gauges, "histograms": merged,
                "hist_summary": summaries}

    # -- recorder emission -------------------------------------------------
    _DECISION_VALUE = {"scale_out": 1.0, "scale_in": -1.0,
                       "rebalance": 0.0}

    def _emit(self, fleet: dict, alerts, decisions) -> None:
        rec = self.recorder
        if rec is None:
            return
        for a in alerts:
            rec.emit("health_event", "slo_alert", a["burn_short"],
                     severity=a["severity"], diagnosis=a["diagnosis"],
                     slo=a["slo"], window=a["window"],
                     threshold=a["threshold"],
                     error_budget=a["error_budget"])
            rec.counter("health/slo_alert")
        for d in decisions:
            rec.emit("health_event", "scale_decision",
                     self._DECISION_VALUE.get(d["decision"]),
                     severity=d["severity"],
                     diagnosis=f"[{d['decision']}] {d['rationale']}",
                     decision=d["decision"], inputs=d["inputs"])
            rec.counter("health/scale_decision")
            rec.counter(f"fleet/decision_{d['decision']}")
        rec.emit("fleet", "fleet/poll", fleet["n_up"],
                 n_replicas=fleet["n_replicas"], poll=fleet["poll"],
                 replicas=fleet["replicas"], counters=fleet["counters"],
                 gauges={k: {kk: v[kk] for kk in
                             ("min", "max", "sum", "mean", "by_replica")}
                         for k, v in fleet["gauges"].items()},
                 histograms=fleet["histograms"],
                 hist_summary=fleet["hist_summary"],
                 alerts=alerts, decisions=decisions)

    def watch(self, interval_s: float = 10.0,
              iterations: Optional[int] = None, render=None):
        """Poll forever (or ``iterations`` times) at ``interval_s``,
        passing each view to ``render``. KeyboardInterrupt exits."""
        n = 0
        with contextlib.suppress(KeyboardInterrupt):
            while iterations is None or n < iterations:
                view = self.poll_once()
                if render is not None:
                    render(view)
                n += 1
                if iterations is not None and n >= iterations:
                    break
                time.sleep(interval_s)
        return self.last_view


# ---------------------------------------------------------------------------
# multi-replica harness: per-thread recorder routing + K engines
# ---------------------------------------------------------------------------

class ReplicaThreadRouter:
    """A write-path Recorder proxy that routes every hook to the
    CALLING THREAD's bound concrete Recorder.

    The monitor guard is one module global (``_state.recorder``); a
    multi-replica harness wants one recorder per engine thread without
    giving up that single-global purity contract. Attach the router as
    the one global recorder, then each engine thread calls
    :meth:`bind` once — every subsequent ``hooks.counter``/``gauge``/
    ``observe``/span/step write from that thread lands in its own
    recorder. Unbound threads' writes are dropped (a null recorder),
    never an error. ``traced_hooks`` is False: the router is a
    host-only observer by construction, so compiled programs stay
    byte-identical (the purity test scrapes a live fleet while
    re-tracing the engine programs)."""

    traced_hooks = False

    def __init__(self, name: str = "fleet-router"):
        self.name = name
        self.capacity = 0
        self.meta: dict = {}
        self._t0 = time.perf_counter()
        self._local = threading.local()
        self._lock = threading.Lock()
        self.recorders: Dict[str, Recorder] = {}

    def bind(self, rid: str, recorder: Recorder) -> Recorder:
        """Route this thread's telemetry to ``recorder`` (and remember
        it under ``rid`` for the harness/debugging)."""
        self._local.rec = recorder
        with self._lock:
            self.recorders[str(rid)] = recorder
        return recorder

    def unbind(self) -> None:
        self._local.rec = None

    def _rec(self) -> Optional[Recorder]:
        return getattr(self._local, "rec", None)

    # -- write path (the hook surface) ----------------------------------
    def counter(self, name, inc=1, **extra):
        rec = self._rec()
        return rec.counter(name, inc, **extra) if rec is not None else 0

    def gauge(self, name, value, **extra):
        rec = self._rec()
        if rec is not None:
            rec.gauge(name, value, **extra)

    def observe(self, name, value, **kw):
        rec = self._rec()
        if rec is not None:
            rec.observe(name, value, **kw)

    def timer_event(self, name, seconds, **extra):
        rec = self._rec()
        if rec is not None:
            rec.timer_event(name, seconds, **extra)

    def timer(self, name, **extra):
        rec = self._rec()
        return rec.timer(name, **extra) if rec is not None \
            else contextlib.nullcontext()

    def emit(self, kind, name, value, **extra):
        rec = self._rec()
        if rec is not None:
            return rec.emit(kind, name, value, **extra)
        return {"kind": kind, "name": name, "value": value}

    def step(self, **meta):
        rec = self._rec()
        return rec.step(**meta) if rec is not None \
            else contextlib.nullcontext(-1)

    @property
    def _open_step(self):
        rec = self._rec()
        return rec._open_step if rec is not None else None

    def emit_histograms(self):
        rec = self._rec()
        if rec is not None:
            rec.emit_histograms()

    # -- read path (flight dumps, reports on the bound thread) ----------
    @property
    def dropped(self):
        rec = self._rec()
        return rec.dropped if rec is not None else 0

    def records(self, kind=None):
        rec = self._rec()
        return rec.records(kind) if rec is not None else []

    def counters(self):
        rec = self._rec()
        return rec.counters() if rec is not None else {}

    def gauges(self):
        rec = self._rec()
        return rec.gauges() if rec is not None else {}

    def histograms(self):
        rec = self._rec()
        return rec.histograms() if rec is not None else {}

    def _histogram_events(self):
        rec = self._rec()
        return rec._histogram_events() if rec is not None else []

    def add_observer(self, fn):
        return fn                       # observers attach per-recorder

    def remove_observer(self, fn):
        pass


class LocalFleet:
    """CPU-testable multi-replica harness: K engines on threads.

    Each engine thread binds its own concrete Recorder into the shared
    :class:`ReplicaThreadRouter` (which the CALLER attaches globally:
    ``with monitor.attached(fleet.router): ...``), queues its requests,
    and runs under ``export.serve_engine(export_port=0)`` — registering into
    ``self.replica_set`` the moment its port binds, and holding its
    ``/metrics`` endpoint open after the drain until :meth:`release`
    (so a poller can take a final post-drain scrape: that is the
    counters-sum-exactly moment). Per-replica hold events let a test
    kill one replica early and watch the fleet degrade to ``up=0``.

    Usage::

        fleet = LocalFleet([eng_a, eng_b])
        with monitor.attached(fleet.router):
            fleet.start({eng_a.replica_id: reqs_a,
                         eng_b.replica_id: reqs_b})
            fleet.wait_ready()
            poller = FleetPoller(fleet.replica_set, recorder=my_rec)
            view = poller.poll_once()        # live scrape
            outputs = fleet.join()           # releases holds, joins
    """

    def __init__(self, engines, *, recorders=None,
                 watchdogs: Optional[dict] = None):
        self.engines = list(engines)
        self.router = ReplicaThreadRouter()
        self.replica_set = ReplicaSet()
        self.recorders: Dict[str, Recorder] = recorders or {
            e.replica_id: Recorder(traced_hooks=False, name=e.replica_id)
            for e in self.engines}
        # optional per-replica Watchdogs ({rid: kwargs}) observing each
        # concrete recorder's step stream — their firings become the
        # scrapeable apex_health_* counters the decision engine reads
        self.watchdogs: dict = {}
        if watchdogs:
            from apex_tpu.monitor.health import Watchdog
            for rid, kw in watchdogs.items():
                self.watchdogs[rid] = Watchdog(self.recorders[rid],
                                               **(kw or {}))
        self.holds = {e.replica_id: threading.Event()
                      for e in self.engines}
        self.ready = {e.replica_id: threading.Event()
                      for e in self.engines}
        self.outputs: Dict[str, dict] = {}
        self.errors: Dict[str, BaseException] = {}
        self._threads: list = []

    def start(self, requests: Dict[str, list]) -> None:
        """Spawn one serving thread per engine. ``requests`` maps
        replica_id -> list of ``(prompt, max_new_tokens)``."""
        for eng in self.engines:
            rid = eng.replica_id

            def body(eng=eng, rid=rid):
                self.router.bind(rid, self.recorders[rid])
                try:
                    for prompt, n_new in requests.get(rid, []):
                        eng.add_request(list(prompt), int(n_new))

                    def register(e, port, rid=rid):
                        self.replica_set.register_engine(e, port)
                        self.ready[rid].set()

                    self.outputs[rid] = export.serve_engine(
                        eng, export_port=0,
                        export_recorder=self.recorders[rid],
                        on_export=register,
                        export_hold=self.holds[rid])
                except BaseException as e:    # noqa: BLE001 — surfaced in join
                    self.errors[rid] = e
                finally:
                    self.ready[rid].set()

            th = threading.Thread(target=body, daemon=True,
                                  name=f"fleet-{rid}")
            self._threads.append(th)
            th.start()

    def wait_ready(self, timeout: float = 60.0) -> None:
        """Block until every replica's export port is bound (or a
        thread died trying — re-raised here)."""
        for rid, ev in self.ready.items():
            if not ev.wait(timeout):
                raise TimeoutError(f"replica {rid} never bound its "
                                   "export port")
        self._reraise()

    def release(self, rid: Optional[str] = None) -> None:
        """Let one replica (or all) stop its exporter and return from
        ``serve()`` — killing its endpoint."""
        for r, ev in self.holds.items():
            if rid is None or r == rid:
                ev.set()

    def join(self, timeout: float = 120.0) -> Dict[str, dict]:
        """Release every hold, join the threads, re-raise any engine
        error, return ``{replica_id: serve() outputs}``."""
        self.release()
        for th in self._threads:
            th.join(timeout)
        self._reraise()
        return self.outputs

    def _reraise(self):
        for rid, e in self.errors.items():
            raise RuntimeError(f"replica {rid} failed") from e

    def drained(self) -> bool:
        """True once no engine has schedulable work left."""
        return all(not e.sched.has_work for e in self.engines)


# ---------------------------------------------------------------------------
# CLI: python -m apex_tpu.monitor fleet ...
# ---------------------------------------------------------------------------

def _endpoint_id(endpoint: str, index: int) -> str:
    if "://" in endpoint:
        rest = endpoint.split("://", 1)[1]
        return rest.split("/", 1)[0] or f"r{index}"
    base = os.path.basename(endpoint)
    return os.path.splitext(base)[0] or f"r{index}"


def render_fleet_table(view: dict) -> str:
    """Human-readable per-replica + fleet table for one poll view."""
    out = [f"fleet: {view['n_up']}/{view['n_replicas']} replicas up "
           f"(poll {view['poll']})"]
    out.append(f"{'replica':<16} {'up':>2} {'age_s':>8}  endpoint")
    for r in view["replicas"]:
        age = "-" if r["age_s"] is None else f"{r['age_s']:.1f}"
        line = f"{r['replica']:<16} {r['up']:>2} {age:>8}  {r['endpoint']}"
        if r.get("error"):
            line += f"  [{r['error']}]"
        out.append(line)
    if view.get("counters"):
        out.append("counters (fleet sum):")
        for k in sorted(view["counters"]):
            out.append(f"  {k} = {view['counters'][k]:g}")
    if view.get("hist_summary"):
        out.append("histograms (merged across replicas):")
        for k in sorted(view["hist_summary"]):
            s = view["hist_summary"][k]
            out.append(
                f"  {k}: count={s['count']} p50={s['p50']} "
                f"p95={s['p95']} p99={s['p99']}")
    for a in view.get("alerts") or []:
        out.append(f"ALERT [{a['severity']}] {a['diagnosis']}")
    for d in view.get("decisions") or []:
        out.append(f"DECISION [{d['decision']}] {d['rationale']}")
    return "\n".join(out)


def main(args) -> int:
    """``python -m apex_tpu.monitor fleet`` body (args pre-parsed by
    ``monitor.__main__``). ``--once`` exits 1 when any SLO alert
    fires — the CI gate; ``--watch`` polls until interrupted."""
    rs = ReplicaSet()
    for i, ep in enumerate(args.endpoints):
        rs.add(_endpoint_id(ep, i), ep)
    poller = FleetPoller(rs, timeout_s=args.timeout)

    def render(view):
        if args.json:
            print(json.dumps(json_safe(view)))
        else:
            print(render_fleet_table(view))

    if args.watch:
        poller.watch(interval_s=args.interval, render=render)
        return 0
    view = poller.poll_once()
    render(view)
    return 1 if view["alerts"] else 0
