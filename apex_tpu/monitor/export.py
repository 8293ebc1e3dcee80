"""Pull-based plaintext metrics endpoint (Prometheus text exposition).

Snapshots an attached :class:`~apex_tpu.monitor.recorder.Recorder`'s
counters, gauges, timers and log-scale histograms into the Prometheus
text exposition format (version 0.0.4) and serves it from a stdlib
``http.server`` thread — ``GET /metrics`` while a server is running,
or ``--once`` to stdout for CI:

    python -m apex_tpu.monitor export run.jsonl --once [--check]
    python -m apex_tpu.monitor export run.jsonl --port 9464

Live mode rides the serve engine: :func:`serve_engine` drives
``ServeEngine.run()`` under an exporter bound to whichever recorder is
attached, so SLO histograms (p50/p95/p99 token latency, TTFT), pool-occupancy gauges and
scheduler counters are scrapeable while requests are in flight.

Disabled mode is free by construction: this module is imported lazily
(``apex_tpu.monitor.__getattr__``) so a process that never exports
never pays the ``http.server`` import, and no thread exists until
:meth:`MetricsExporter.start`.

Mapping (names sanitized to ``[a-zA-Z0-9_:]``, ``apex_`` prefixed):

- counter  ``serve/preemptions``    -> ``apex_serve_preemptions_total``
- gauge    ``serve/queue_depth``    -> ``apex_serve_queue_depth``
- timer    ``data/host_wait``       -> ``apex_data_host_wait_seconds_total``
                                       + ``..._seconds_count`` (counters)
- histogram ``serve/ttft_ms``       -> ``apex_serve_ttft_ms_bucket{le=..}``
                                       + ``_sum`` + ``_count`` (classic
                                       cumulative histogram; bucket
                                       bounds are the LogHistogram's
                                       populated upper edges)

:func:`parse_prometheus` is the self-check twin: it parses an
exposition document back into ``{(name, labels): value}`` so the CLI's
``--check`` (and ``tests/test_export.py``'s golden round trip) can
assert scrape == aggregate.
"""

from __future__ import annotations

import re
import threading

from apex_tpu.monitor import _state

PREFIX = "apex_"
CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def sanitize(name: str) -> str:
    """A recorder event name as a legal Prometheus metric name."""
    out = _NAME_RE.sub("_", str(name))
    if out and out[0].isdigit():
        out = "_" + out
    return PREFIX + out


def _fmt_value(v) -> str:
    if v is None:
        return "NaN"
    f = float(v)
    if f != f:
        return "NaN"
    if f in (float("inf"), float("-inf")):
        return "+Inf" if f > 0 else "-Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def snapshot(recorder=None, events=None, header=None) -> dict:
    """One point-in-time metrics snapshot, from a live recorder
    (default: the attached one) or from an event list (the JSONL file
    modes). Shape: ``{counters, gauges, timers, histograms}`` where
    histograms hold :meth:`LogHistogram.snapshot` payloads.

    Recorder blind spots are exported too, so a saturated ring is
    itself observable: ``monitor/dropped_events`` (ring evictions →
    ``apex_monitor_dropped_events_total``) and ``monitor/open_spans``
    (started-but-unfinished spans → ``apex_monitor_open_spans``) —
    live from ``Recorder.dropped``/``spans.open_spans()``, file-backed
    from the dump ``header`` when the caller passes it."""
    if events is not None:
        if header:
            return _with_blind_spots(
                snapshot(events=events),
                header.get("dropped"), header.get("open_spans"))
        from apex_tpu.monitor.report import aggregate as _aggregate
        counters: dict = {}
        gauges: dict = {}
        timers: dict = {}
        hists: dict = {}
        agg = _aggregate(events)
        counters.update(agg.get("counters") or {})
        gauges.update(agg.get("gauges") or {})
        timers.update(agg.get("timers") or {})
        # aggregate() summarizes histograms; re-collect the raw
        # snapshots here so bucket counts survive into exposition
        for ev in events:
            if ev.get("kind") == "histogram":
                hists[ev.get("name")] = {
                    **{k: ev.get(k) for k in
                       ("lo", "hi", "buckets_per_decade", "sum", "min",
                        "max", "underflow", "overflow", "counts")},
                    "count": ev.get("value")}
        return {"counters": counters, "gauges": gauges, "timers": timers,
                "histograms": hists}
    rec = recorder if recorder is not None else _state.recorder
    if rec is None:
        return {"counters": {}, "gauges": {}, "timers": {},
                "histograms": {}}
    from apex_tpu.monitor.spans import open_spans
    agg_timers: dict = {}
    for ev in rec.records("timer"):
        t = agg_timers.setdefault(ev.get("name"), {"n": 0, "total_s": 0.0})
        t["n"] += 1
        t["total_s"] += float(ev.get("value") or 0.0)
    # the recorder shadows each timer with a "<name>/total_s" counter
    # (host bookkeeping, not an event) — the timer series already
    # exposes that value, and the file-backed path never sees the
    # shadow, so drop it for live == file consistency
    counters = {k: v for k, v in rec.counters().items()
                if not k.endswith("/total_s")}
    return _with_blind_spots(
        {"counters": counters, "gauges": rec.gauges(),
         "timers": agg_timers,
         "histograms": {k: h.snapshot()
                        for k, h in rec.histograms().items()}},
        rec.dropped, open_spans())


def _with_blind_spots(snap: dict, dropped, open_spans) -> dict:
    snap["counters"]["monitor/dropped_events"] = float(dropped or 0)
    snap["gauges"]["monitor/open_spans"] = float(open_spans or 0)
    return snap


def render_prometheus(snap: dict) -> str:
    """Prometheus text exposition (0.0.4) for a :func:`snapshot`."""
    from apex_tpu.monitor.spans import LogHistogram

    lines: list[str] = []

    def emit(name: str, mtype: str, rows):
        lines.append(f"# TYPE {name} {mtype}")
        lines.extend(rows)

    for k in sorted(snap.get("counters") or {}):
        n = sanitize(k) + "_total"
        emit(n, "counter", [f"{n} {_fmt_value(snap['counters'][k])}"])
    for k in sorted(snap.get("gauges") or {}):
        n = sanitize(k)
        emit(n, "gauge", [f"{n} {_fmt_value(snap['gauges'][k])}"])
    for k in sorted(snap.get("timers") or {}):
        t = snap["timers"][k]
        n = sanitize(k) + "_seconds"
        emit(n + "_total", "counter",
             [f"{n}_total {_fmt_value(t.get('total_s'))}"])
        emit(n + "_count", "counter",
             [f"{n}_count {_fmt_value(t.get('n'))}"])
    for k in sorted(snap.get("histograms") or {}):
        h = LogHistogram.from_snapshot(snap["histograms"][k])
        n = sanitize(k)
        rows = []
        cum = h.underflow
        for i in range(h.n_buckets):
            c = h._counts[i]
            if not c:
                continue
            cum += c
            le = h.bucket_bounds(i)[1]
            rows.append(f'{n}_bucket{{le="{_fmt_value(le)}"}} {cum}')
        rows.append(f'{n}_bucket{{le="+Inf"}} {h.count}')
        rows.append(f"{n}_sum {_fmt_value(h.sum)}")
        rows.append(f"{n}_count {h.count}")
        emit(n, "histogram", rows)
    return "\n".join(lines) + ("\n" if lines else "")


_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)\s*$')


def parse_prometheus(text: str) -> dict:
    """Parse an exposition document into ``{(name, labels): value}``
    where ``labels`` is a sorted tuple of ``(key, value)`` pairs — the
    self-check half of the golden round trip."""
    out: dict = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            raise ValueError(f"unparseable exposition line: {line!r}")
        name, labels, value = m.groups()
        lab = ()
        if labels:
            pairs = []
            for part in labels[1:-1].split(","):
                if not part.strip():
                    continue
                lk, lv = part.split("=", 1)
                pairs.append((lk.strip(), lv.strip().strip('"')))
            lab = tuple(sorted(pairs))
        out[(name, lab)] = (float("inf") if value == "+Inf"
                            else float("-inf") if value == "-Inf"
                            else float(value))
    return out


def selfcheck_text(text: str, snap: dict) -> None:
    """Assert ``text`` (an exposition render of ``snap``) parses and
    its counter/gauge/histogram-count samples equal the snapshot —
    the ``--check`` CLI mode and the CI export stage."""
    parsed = parse_prometheus(text)
    for k, v in (snap.get("counters") or {}).items():
        got = parsed[(sanitize(k) + "_total", ())]
        assert got == float(v), (k, got, v)
    for k, v in (snap.get("gauges") or {}).items():
        got = parsed[(sanitize(k), ())]
        if v is None or (isinstance(v, float) and v != v):
            assert got != got, (k, got, v)
        else:
            assert got == float(v), (k, got, v)
    for k, h in (snap.get("histograms") or {}).items():
        n = sanitize(k)
        assert parsed[(n + "_count", ())] == float(h.get("count") or 0), k
        inf = parsed[(n + "_bucket", (("le", "+Inf"),))]
        assert inf == float(h.get("count") or 0), k


class MetricsExporter:
    """Serve ``GET /metrics`` from a daemon thread.

    ``recorder=None`` resolves the *attached* recorder at every scrape
    — attach/detach cycles are honored live, and a scrape while
    detached returns an empty (but valid) document. ``port=0`` binds an
    ephemeral port; the bound port is returned by :meth:`start` and
    kept on ``.port``.
    """

    def __init__(self, recorder=None, port: int = 9464,
                 addr: str = "127.0.0.1"):
        self.recorder = recorder
        self.addr = addr
        self.port = int(port)
        self._srv = None
        self._thread = None

    def _render(self) -> str:
        rec = (self.recorder if self.recorder is not None
               else _state.recorder)
        return render_prometheus(snapshot(recorder=rec)
                                 if rec is not None else
                                 {"counters": {}, "gauges": {},
                                  "timers": {}, "histograms": {}})

    def start(self) -> int:
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        exporter = self

        class _Handler(BaseHTTPRequestHandler):
            def do_GET(self):                       # noqa: N802
                if self.path.split("?")[0] not in ("/metrics", "/"):
                    self.send_error(404)
                    return
                try:
                    body = exporter._render().encode()
                except Exception as e:              # noqa: BLE001
                    self.send_error(500, str(e)[:200])
                    return
                self.send_response(200)
                self.send_header("Content-Type", CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):              # scrapes are not news
                pass

        self._srv = ThreadingHTTPServer((self.addr, self.port), _Handler)
        self.port = self._srv.server_address[1]
        self._thread = threading.Thread(
            target=self._srv.serve_forever, daemon=True,
            name="apex-tpu-metrics-exporter")
        self._thread.start()
        return self.port

    def stop(self):
        srv, self._srv = self._srv, None
        th, self._thread = self._thread, None
        if srv is not None:
            srv.shutdown()
            srv.server_close()
        if th is not None:
            th.join(timeout=5)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


def serve_engine(engine, *, export_port: int,
                 export_addr: str = "127.0.0.1",
                 max_steps: int = 100_000, on_export=None) -> dict:
    """``engine.run()`` with a live metrics surface: a
    :class:`MetricsExporter` serves ``GET /metrics`` (the attached
    recorder's counters/gauges/SLO histograms) for the duration of the
    drain and is stopped after it. ``export_port=0`` binds an ephemeral
    port; ``on_export(engine, port)`` fires once the port is bound."""
    with MetricsExporter(port=export_port, addr=export_addr) as exporter:
        if on_export is not None:
            on_export(engine, exporter.port)
        return engine.run(max_steps=max_steps)


def main(args) -> int:
    """``python -m apex_tpu.monitor export`` body (args pre-parsed by
    ``monitor.__main__``): render a recorder JSONL dump/stream once to
    stdout, optionally self-check the round trip, or serve it over
    HTTP (re-reading the file per scrape, so a live ``stream=`` file
    exports its current tail)."""
    from apex_tpu.monitor.report import load_jsonl

    def _snap():
        header, events = load_jsonl(args.path)
        return snapshot(events=events, header=header)

    if args.once:
        snap = _snap()
        text = render_prometheus(snap)
        if args.check:
            selfcheck_text(text, snap)
        print(text, end="")
        if args.check:
            import sys
            n = sum(len(snap[k]) for k in
                    ("counters", "gauges", "histograms"))
            print(f"export selfcheck ok: {n} metric(s) round-tripped",
                  file=sys.stderr)
        return 0

    exporter = MetricsExporter(port=args.port, addr=args.addr)
    exporter._render = lambda: render_prometheus(_snap())   # file-backed
    port = exporter.start()
    print(f"serving {args.path} at http://{args.addr}:{port}/metrics "
          f"(ctrl-c to stop)")
    try:
        exporter._thread.join()
    except KeyboardInterrupt:
        exporter.stop()
    return 0
