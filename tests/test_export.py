"""monitor.export: Prometheus text exposition + HTTP endpoint.

Contracts:

- golden format: a deterministic recorder renders to an exact
  exposition document (counters ``_total``, gauges, timers as
  ``_seconds_total``/``_count``, histograms as cumulative ``_bucket``
  + ``_sum`` + ``_count``);
- round trip: scrape -> parse -> values equal the recorder aggregate
  (``selfcheck_text``, the CLI ``--check`` body);
- the HTTP thread serves ``/metrics``, 404s elsewhere, resolves the
  ATTACHED recorder at scrape time, and stops cleanly;
- disabled purity: importing ``apex_tpu.monitor`` does NOT import the
  export module (or ``http.server``) — the no-import-cost half of the
  "disabled mode stays free" claim (the no-thread half is construction:
  no ``MetricsExporter.start``, no thread).
"""

import io
import json
import subprocess
import sys
import urllib.error
import urllib.request

import pytest

from apex_tpu import monitor
from apex_tpu.monitor import export


def _mini_recorder():
    rec = monitor.Recorder(name="golden")
    rec.counter("serve/preemptions", 3)
    rec.gauge("serve/queue_depth", 2)
    rec.gauge("serve/pages_free", 5)
    rec.timer_event("serve/step", 0.25)
    rec.timer_event("serve/step", 0.75)
    rec.observe("serve/ttft_ms", 2.0, lo=1.0, hi=100.0,
                buckets_per_decade=1)
    rec.observe("serve/ttft_ms", 20.0, lo=1.0, hi=100.0,
                buckets_per_decade=1)
    return rec


GOLDEN = """\
# TYPE apex_monitor_dropped_events_total counter
apex_monitor_dropped_events_total 0
# TYPE apex_serve_preemptions_total counter
apex_serve_preemptions_total 3
# TYPE apex_monitor_open_spans gauge
apex_monitor_open_spans 0
# TYPE apex_serve_pages_free gauge
apex_serve_pages_free 5
# TYPE apex_serve_queue_depth gauge
apex_serve_queue_depth 2
# TYPE apex_serve_step_seconds_total counter
apex_serve_step_seconds_total 1
# TYPE apex_serve_step_seconds_count counter
apex_serve_step_seconds_count 2
# TYPE apex_serve_ttft_ms histogram
apex_serve_ttft_ms_bucket{le="10"} 1
apex_serve_ttft_ms_bucket{le="100"} 2
apex_serve_ttft_ms_bucket{le="+Inf"} 2
apex_serve_ttft_ms_sum 22
apex_serve_ttft_ms_count 2
"""


def test_render_prometheus_golden_format():
    rec = _mini_recorder()
    text = export.render_prometheus(export.snapshot(recorder=rec))
    assert text == GOLDEN, f"exposition drifted:\n{text}"


def test_scrape_parse_roundtrip_matches_aggregate():
    rec = _mini_recorder()
    snap = export.snapshot(recorder=rec)
    text = export.render_prometheus(snap)
    export.selfcheck_text(text, snap)            # raises on any drift
    parsed = export.parse_prometheus(text)
    agg = rec.aggregate()
    assert parsed[("apex_serve_preemptions_total", ())] == \
        agg["counters"]["serve/preemptions"]
    assert parsed[("apex_serve_queue_depth", ())] == \
        agg["gauges"]["serve/queue_depth"]
    assert parsed[("apex_serve_ttft_ms_count", ())] == \
        agg["histograms"]["serve/ttft_ms"]["count"]
    assert parsed[("apex_serve_step_seconds_total", ())] == \
        pytest.approx(agg["timers"]["serve/step"]["total_s"])


def test_snapshot_from_events_matches_live():
    """The file-backed CLI path: dump -> load -> snapshot(events=...,
    header=...) must carry the same values as the live recorder
    snapshot — including the monitor blind-spot metrics, which the
    file path reads from the dump header."""
    rec = _mini_recorder()
    buf = io.StringIO()
    rec.dump_jsonl(buf)
    buf.seek(0)
    header, events = monitor.load_jsonl(buf)
    live = export.snapshot(recorder=rec)
    from_file = export.snapshot(events=events, header=header)
    assert from_file["counters"] == live["counters"]
    assert from_file["gauges"] == live["gauges"]
    assert from_file["histograms"]["serve/ttft_ms"]["counts"] == \
        live["histograms"]["serve/ttft_ms"]["counts"]
    export.selfcheck_text(export.render_prometheus(from_file), from_file)


def test_nan_gauge_renders_and_checks():
    """The watchdog's reason to exist — a NaN loss gauge — must not
    break the exposition or the self-check."""
    rec = monitor.Recorder()
    rec.gauge("train/loss", float("nan"))
    snap = export.snapshot(recorder=rec)
    text = export.render_prometheus(snap)
    assert "apex_train_loss NaN" in text
    export.selfcheck_text(text, snap)


def test_http_exporter_scrape_and_stop():
    rec = _mini_recorder()
    exporter = export.MetricsExporter(recorder=rec, port=0)
    port = exporter.start()
    try:
        url = f"http://127.0.0.1:{port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == export.CONTENT_TYPE
            body = resp.read().decode()
        assert body == GOLDEN
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope",
                                   timeout=10)
        assert ei.value.code == 404
    finally:
        exporter.stop()
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                               timeout=2)


def test_http_exporter_resolves_attached_recorder_per_scrape():
    """recorder=None follows attach/detach live: the same server
    serves the currently-attached recorder's values, and an empty (but
    valid) document while detached."""
    exporter = export.MetricsExporter(port=0)
    port = exporter.start()
    url = f"http://127.0.0.1:{port}/metrics"
    try:
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.read().decode() == ""           # detached
        rec = monitor.Recorder()
        rec.counter("live/hits", 7)
        with monitor.attached(rec):
            with urllib.request.urlopen(url, timeout=10) as resp:
                assert "apex_live_hits_total 7" in resp.read().decode()
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.read().decode() == ""           # detached again
    finally:
        exporter.stop()


def _memory_recorder():
    """The ``memory/hbm_*`` gauges a ``MemorySampler`` tick records."""
    from apex_tpu.monitor import memory
    rec = monitor.Recorder(name="memory")
    with monitor.attached(rec):
        memory.device_memory_snapshot()
    return rec


@pytest.mark.parametrize("make_recorder,expected", [
    (_mini_recorder, "apex_serve_preemptions_total 3"),
    # the sampler's gauges are scrapeable (was a stage of scripts/ci.sh)
    (_memory_recorder, "\napex_memory_hbm_bytes_in_use "),
])
def test_cli_export_once_check(tmp_path, make_recorder, expected):
    from apex_tpu.monitor.__main__ import main as cli_main
    rec = make_recorder()
    path = tmp_path / "run.jsonl"
    rec.dump_jsonl(str(path))
    import contextlib
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = cli_main(["export", str(path), "--once", "--check"])
    assert rc == 0
    assert expected in out.getvalue()


def test_monitor_import_does_not_import_export():
    """The lazy-import contract: importing apex_tpu.monitor must NOT
    load the export module (jax's own profiler pulls http.server, so
    the assertable boundary is our module, not the stdlib one);
    attribute access loads it on demand. Subprocess for a clean module
    table."""
    code = (
        "import sys\n"
        "import apex_tpu.monitor\n"
        "assert 'apex_tpu.monitor.export' not in sys.modules, 'eager'\n"
        "apex_tpu.monitor.export  # attribute access loads it lazily\n"
        "assert 'apex_tpu.monitor.export' in sys.modules\n"
        "print('lazy ok')\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "lazy ok" in proc.stdout


def test_sanitize_names():
    assert export.sanitize("serve/ttft_ms") == "apex_serve_ttft_ms"
    assert export.sanitize("psum@data") == "apex_psum_data"
    assert export.sanitize("0weird") == "apex__0weird"


def test_parse_rejects_garbage():
    with pytest.raises(ValueError):
        export.parse_prometheus("not a metric line at all!!!")


def test_concurrent_scrape_while_writer_emits():
    """A writer thread hammering counters/gauges/histograms while the
    render path snapshots repeatedly: every scrape parses clean and the
    scraped counter is monotone (no torn reads, no exceptions) — the
    lock-protected snapshot contract a live scrape leans on."""
    import threading
    rec = monitor.Recorder(traced_hooks=False)
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        try:
            while not stop.is_set():
                rec.counter("serve/tokens_generated")
                rec.gauge("serve/queue_depth", i % 7)
                rec.observe("serve/token_latency_ms", 1.0 + (i % 50))
                i += 1
        except BaseException as e:     # noqa: BLE001 — surfaced below
            errors.append(e)

    th = threading.Thread(target=writer, daemon=True)
    th.start()
    try:
        last = -1.0
        for _ in range(25):
            snap = export.snapshot(recorder=rec)
            text = export.render_prometheus(snap)
            export.selfcheck_text(text, snap)
            parsed = export.parse_prometheus(text)
            cur = parsed[("apex_serve_tokens_generated_total", ())]
            assert cur >= last, "scraped counter went backwards"
            last = cur
    finally:
        stop.set()
        th.join(10)
    assert not errors, errors
    assert last > 0
