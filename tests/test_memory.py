"""apex_tpu.monitor.memory: the unified memory surface (ISSUE 15).

Acceptance: the analytic high-water walk is EXACT on a hand-computable
3-op program; memory instrumentation is free when detached (scoped/
sampled step jaxprs byte-identical to plain, recorder attached or not);
the ``memory_stats()=None`` backend degrades to the nominal row; the
watchdog's ``hbm_high_water`` and ``memory_leak`` fire under forced
pressure and render under ``## health`` while a healthy constant-
footprint run stays silent.
"""

import io
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import monitor
from apex_tpu.monitor import memory


@pytest.fixture(autouse=True)
def _detached():
    while monitor.get_recorder() is not None:
        monitor.detach()
    yield
    while monitor.get_recorder() is not None:
        monitor.detach()


def _report(rec):
    buf = io.StringIO()
    rec.dump_jsonl(buf)
    buf.seek(0)
    header, events = monitor.load_jsonl(buf)
    return monitor.render_report(events, header=header), events


# ---------------------------------------------------------------------------
# analytic high water: exactness on a hand-computable program
# ---------------------------------------------------------------------------

def test_analytic_high_water_exact_three_op_program():
    """f(x) = (2x + 1)^2 over f32[256] (1024 B):

    - eqn0 ``a = x * 2``:  x resident + a          = 2048 B
    - eqn1 ``b = a + 1``:  x + a (last use) + b    = 3072 B  <- peak
    - eqn2 ``c = b * b``:  x + b (last use) + c    = 3072 B

    Inputs are resident for the whole program (the undonated-call
    convention); intermediates free at their last use."""
    def f(x):
        a = x * jnp.float32(2.0)
        b = a + jnp.float32(1.0)
        return b * b

    x = jnp.ones((256,), jnp.float32)
    closed = jax.make_jaxpr(f)(x)
    assert len(closed.jaxpr.eqns) == 3     # the program IS 3 ops
    hw = memory.attribute_high_water(closed)
    assert hw["peak_live_bytes"] == 3072, hw
    assert hw["argument_bytes"] == 1024
    assert hw["output_bytes"] == 1024
    assert hw["estimated"] is False


def test_analytic_high_water_scope_attribution():
    """The peak is charged to the innermost apx: scope that owns it —
    'which module owns the peak' has a named answer."""
    from apex_tpu.monitor import profile

    def g(x, w1, w2):
        with profile.scope("small"):
            h = jnp.tanh(x @ w1)           # [8, 512]
        with profile.scope("big"):
            p = h @ w2                     # [8, 2048]: the peak lives here
            return jnp.sum(p * p)

    args = (jnp.ones((8, 64)), jnp.ones((64, 512)),
            jnp.ones((512, 2048)))
    hw = memory.analytic_high_water(g, *args)
    assert hw["peak_scope"] == "big", hw["peak_scope"]
    assert hw["scopes"]["big"]["peak_live_bytes"] == hw["peak_live_bytes"]
    assert "small" in hw["scopes"]
    assert hw["scopes"]["small"]["peak_live_bytes"] < \
        hw["scopes"]["big"]["peak_live_bytes"]


def test_analytic_high_water_scan_and_while():
    """scan: body intermediates ride ON TOP of the call site's live set
    but the peak does NOT multiply by trip count (iterations reuse the
    body's buffers); while flags the result as estimated."""
    def scanned(x):
        def body(c, _):
            return jnp.tanh(c @ c), c.sum()
        c, ys = jax.lax.scan(body, x, None, length=4)
        return c, ys

    x = jnp.ones((32, 32))                 # 4096 B per [32,32] f32
    hw4 = memory.analytic_high_water(scanned, x)

    def scanned16(x):
        def body(c, _):
            return jnp.tanh(c @ c), c.sum()
        c, ys = jax.lax.scan(body, x, None, length=16)
        return c, ys

    hw16 = memory.analytic_high_water(scanned16, x)
    # longer trip only grows the stacked-ys output (16 vs 4 scalars),
    # never multiplies the body peak
    assert hw16["peak_live_bytes"] - hw4["peak_live_bytes"] == 12 * 4
    assert hw4["estimated"] is False

    def looped(x):
        return jax.lax.while_loop(lambda c: c.sum() < 100.0,
                                  lambda c: c * 1.1, x)

    assert memory.analytic_high_water(looped, jnp.ones((16,)))[
        "estimated"] is True


def test_analytic_cond_branches_max_not_summed():
    """Mutually-exclusive cond branches contribute their MAX to the
    call site's peak, never their sum — each sibling sub-jaxpr stacks
    on the call-site live set, not on the previous sibling's peak."""
    def branch(v):
        a = v * 2.0                        # 1 KiB intermediate
        b = a + 1.0                        # +1 KiB (a still live)
        return b.sum()

    def f(x):
        return jax.lax.cond(x[0] > 0, branch, branch, x)

    x = jnp.ones((256,), jnp.float32)      # 1 KiB input
    hw = memory.analytic_high_water(f, x)
    # one branch's 2 KiB of intermediates on top of the ~1 KiB call
    # site; the pre-fix sum-of-siblings walk reported ~5 KiB
    assert hw["peak_live_bytes"] >= 3 * 1024
    assert hw["peak_live_bytes"] < 4 * 1024


# ---------------------------------------------------------------------------
# purity: memory instrumentation is free when detached (and attached)
# ---------------------------------------------------------------------------

def test_sampled_step_jaxpr_byte_identity():
    """A step traced while a recorder is attached AND a MemorySampler
    is running is byte-identical to the same step traced detached —
    the sampler is a host thread, the walk is abstract, nothing
    inserts ops or retraces."""
    from apex_tpu.monitor import profile

    def step(x, w1, w2):
        with profile.scope("l1"):
            h = jnp.tanh(x @ w1)
        with profile.scope("l2"):
            return jnp.sum(h @ w2)

    args = (jnp.ones((4, 16)), jnp.ones((16, 32)), jnp.ones((32, 8)))
    grad = jax.value_and_grad(step, argnums=(1, 2))
    plain = str(jax.make_jaxpr(grad)(*args))
    rec = monitor.Recorder(name="t")
    with monitor.attached(rec), memory.MemorySampler(0.01):
        memory.analytic_high_water(grad, *args, record=True)
        attached = str(jax.make_jaxpr(grad)(*args))
    assert attached == plain
    assert "callback" not in attached


# ---------------------------------------------------------------------------
# snapshots + sampler: the memory_stats()=None degradation path
# ---------------------------------------------------------------------------

def test_snapshot_degrades_to_nominal_row_on_cpu():
    """The CPU backend reports no memory_stats: the snapshot degrades
    to the nominal row — real live-array resident bytes against the
    DEVICE_PEAKS table limit, stamped nominal — and still records the
    headline gauges."""
    keep = jnp.ones((1024,), jnp.float32)   # noqa: F841  (resident)
    rec = monitor.Recorder(name="t")
    with monitor.attached(rec):
        rows = memory.device_memory_snapshot()
    assert rows and rows[0]["platform"] == "cpu"
    row = rows[0]
    assert row.get("nominal") is True
    assert row["bytes_in_use"] >= keep.nbytes
    assert row["limit_bytes"] == memory.hbm_limit_for("cpu")
    assert 0.0 <= row["utilization"] < 1.0
    g = rec.gauges()
    assert g["memory/hbm_bytes_in_use"] >= keep.nbytes
    assert g["memory/hbm_limit_bytes"] == memory.hbm_limit_for("cpu")
    assert "memory/hbm_utilization" in g


def test_hbm_limit_table_lookup():
    assert memory.hbm_limit_for("TPU v5e") == 16 << 30
    assert memory.hbm_limit_for("TPU v5p chip") == 95 << 30
    assert memory.hbm_limit_for("warp-drive-9000") is None


def test_program_and_benchmark_agree_on_bf16_peak():
    """The program may not import ``benchmarks/``, so the peaks live
    twice: ``attribution.DEVICE_PEAKS`` for the program's own MFU gauge and
    ``benchmarks/harness/peaks.py`` for the ledger's rooflines. Every
    ``device_kind`` both list has ONE bf16 peak (run from the root of a
    checkout, as ``tests/test_deepseek.py`` is)."""
    from apex_tpu.monitor import attribution
    from benchmarks.harness import peaks
    shared = [kind for kind in peaks.PEAKS
              if attribution.peak_flops_for(kind) is not None]
    assert "TPU v5 lite" in shared
    for kind in shared:
        assert attribution.peak_flops_for(kind) == peaks.PEAKS[kind].bf16_flops


def test_memory_sampler_thread_and_detach():
    """The sampler polls on its interval into gauges + the streaming
    histogram; it resolves the recorder AT SAMPLE TIME, so a detached
    window records nothing (the fire-time-resolution contract)."""
    rec = monitor.Recorder(name="t")
    smp = memory.MemorySampler(0.02)
    with monitor.attached(rec):
        with smp:
            time.sleep(0.1)
    n_attached = len(rec.records("gauge"))
    assert smp.samples >= 2
    assert n_attached > 0
    # the histogram is a DISTINCT metric family from the gauge (one
    # Prometheus TYPE line per name), MiB-denominated as named
    assert "memory/hbm_mib_in_use" in rec.histograms()
    agg = rec.aggregate()
    assert agg["memory"]["timeline"]["samples"] >= 2
    assert agg["memory"]["timeline"]["max"] > 0
    # detached: the same sampler object records nothing new
    smp2 = memory.MemorySampler(0.02)
    with smp2:
        time.sleep(0.06)
    assert smp2.samples >= 1
    assert len(rec.records("gauge")) == n_attached


# ---------------------------------------------------------------------------
# compiled footprints + the aggregate/report round trip
# ---------------------------------------------------------------------------

def test_compiled_memory_profile_and_report_block():
    def f(x, w):
        return jnp.sum(jnp.tanh(x @ w))

    args = (jnp.ones((16, 64)), jnp.ones((64, 32)))
    rec = monitor.Recorder(name="t")
    with monitor.attached(rec):
        prof = memory.memory_profile(f, *args, label="tiny",
                                     record=True)
    cm = prof["compiled"]
    assert cm["argument_size_in_bytes"] == (16 * 64 + 64 * 32) * 4
    assert cm["output_size_in_bytes"] == 4
    assert cm["total_bytes"] >= cm["argument_size_in_bytes"]
    rendered, events = _report(rec)
    agg = monitor.aggregate(events)
    progs = agg["memory"]["programs"]
    assert "tiny" in progs
    assert progs["tiny"]["analytic_peak_bytes"] == \
        prof["analytic"]["peak_live_bytes"]
    assert agg["memory"]["analytic"]["peak_live_bytes"] > 0
    assert "## memory" in rendered and "tiny" in rendered


def test_trace_shims_delegate():
    """trace.memory_analysis / trace.device_memory_snapshot are thin
    re-export shims over monitor.memory (the pyprof precedent): same
    numbers, deprecation pointer in the docstring."""
    def f(x):
        return x * 2.0

    x = jnp.ones((64,), jnp.float32)
    via_shim = monitor.trace.memory_analysis(f, x)
    direct = memory.compiled_memory_profile(f, x)
    assert via_shim == direct
    assert via_shim["argument_size_in_bytes"] == 256
    assert "memory.compiled_memory_profile" in \
        monitor.trace.memory_analysis.__doc__
    assert "memory.device_memory_snapshot" in \
        monitor.trace.device_memory_snapshot.__doc__
    shim_rows = monitor.trace.device_memory_snapshot()
    assert shim_rows and shim_rows[0]["platform"] == "cpu"


# ---------------------------------------------------------------------------
# watchdog: hbm_high_water / memory_leak / recompile_storm
# ---------------------------------------------------------------------------

def _synthetic_run(byte_series, limit=1000.0, extra=None):
    rec = monitor.Recorder(name="t")
    dog = monitor.Watchdog(rec, leak_window=len(byte_series))
    with monitor.attached(rec):
        for b in byte_series:
            with rec.step():
                rec.gauge("memory/hbm_bytes_in_use", b)
                rec.gauge("memory/hbm_limit_bytes", limit)
                if extra:
                    extra(rec)
    return rec, dog


def test_hbm_high_water_fires_and_rearms():
    series = [100, 400, 950, 960, 500, 300, 980]   # limit 1000
    rec, dog = _synthetic_run(series)
    names = [e["name"] for e in dog.events]
    # fired at 950 (>=0.9), stayed one-shot at 960, re-armed below
    # 0.81x limit, fired again at 980
    assert names.count("hbm_high_water") == 2, dog.events
    rendered, _ = _report(rec)
    assert "## health" in rendered and "hbm_high_water" in rendered


def test_memory_leak_fires_on_growth_silent_on_constant():
    """The false-positive guard: a healthy CONSTANT footprint (with a
    little noise) never fires; steady growth does."""
    leak = [1000 + 40 * i for i in range(20)]      # +4%/step growth
    rec, dog = _synthetic_run(leak, limit=1e9)
    assert [e["name"] for e in dog.events] == ["memory_leak"]
    ev = dog.events[0]
    assert ev["growth_bytes"] > 0
    rendered, _ = _report(rec)
    assert "memory_leak" in rendered

    rng = np.random.RandomState(0)
    flat = [1000 + float(rng.randint(-5, 6)) for _ in range(20)]
    _, dog2 = _synthetic_run(flat, limit=1e9)
    assert dog2.events == [], dog2.events


def test_recompile_storm_fires_after_grace():
    """Compile counters landing step after step (after the warmup
    grace) name the storm; warmup-only compiles stay silent."""
    def stormy(i):
        def extra(rec):
            rec.counter("jax/compile/cache_miss")
        return extra

    rec = monitor.Recorder(name="t")
    dog = monitor.Watchdog(rec)
    with monitor.attached(rec):
        for i in range(10):
            with rec.step():
                rec.gauge("loss", 1.0)
                if i < 2 or i > 5:            # warmup + the storm
                    rec.counter("jax/compile/cache_miss")
    names = [e["name"] for e in dog.events]
    assert names == ["recompile_storm"], dog.events

    rec2 = monitor.Recorder(name="t")
    dog2 = monitor.Watchdog(rec2)
    with monitor.attached(rec2):
        for i in range(10):
            with rec2.step():
                rec2.gauge("loss", 1.0)
                if i < 2:                      # warmup compiles only
                    rec2.counter("jax/compile/cache_miss")
    assert dog2.events == [], dog2.events


def test_recompile_storm_silent_on_sparse_compiles():
    """The quiet-step regression: a step with no memory gauges and no
    compile still pushes a 0 into the storm window — three one-off
    compiles spread over a long run must NOT read as consecutive."""
    rec = monitor.Recorder(name="t")
    dog = monitor.Watchdog(rec)
    with monitor.attached(rec):
        for i in range(80):
            with rec.step():
                rec.gauge("misc/x", 1.0)     # no memory/ gauges at all
                if i in (3, 30, 60):          # sparse legitimate compiles
                    rec.counter("jax/compile/cache_miss")
    assert dog.events == [], dog.events


def test_snapshot_survives_stats_without_bytes_in_use():
    """A backend whose memory_stats() returns a dict WITHOUT
    bytes_in_use must degrade (live-array residency), not KeyError —
    and the sampler's opening sample must never kill the run."""
    class FakeDevice:
        id = 99
        platform = "weird"
        device_kind = "warp-drive-9000"

        def memory_stats(self):
            return {"num_allocs": 5}

    rec = monitor.Recorder(name="t")
    with monitor.attached(rec):
        rows = memory.device_memory_snapshot(devices=[FakeDevice()])
        smp = memory.MemorySampler(0.02, devices=[FakeDevice()])
        with smp:
            time.sleep(0.05)
    assert rows[0]["num_allocs"] == 5
    assert rows[0]["bytes_in_use"] == 0      # no live arrays there
    assert smp.samples >= 1


def test_healthy_memory_run_stays_silent():
    """The full healthy picture: constant bytes well under the limit,
    no compiles past warmup — zero health events, no ## health block
    mentioning memory."""
    rec, dog = _synthetic_run([500.0] * 25, limit=10000.0)
    assert dog.events == []
    rendered, _ = _report(rec)
    assert "hbm_high_water" not in rendered
    assert "memory_leak" not in rendered


# ---------------------------------------------------------------------------
# capacity reports + calibration + CLI
# ---------------------------------------------------------------------------

def test_serve_pool_report_matches_cache_config():
    from apex_tpu.serve.cache import CacheConfig

    rec = monitor.Recorder(name="t")
    with monitor.attached(rec):
        sp = memory.serve_pool_report(num_layers=2, kv_heads=4,
                                      head_dim=16, num_pages=9,
                                      page_size=8, seq_len=32,
                                      pages_in_use=6, record=True)
    cfg = CacheConfig(num_layers=2, kv_heads=4, head_dim=16,
                      num_pages=9, page_size=8, dtype=jnp.bfloat16)
    assert sp["bytes_per_page"] == cfg.bytes_per_page()
    assert sp["bytes_in_use"] == cfg.occupancy_bytes(6)
    assert sp["occupancy"] == round(6 / 8, 4)
    assert sp["fp8_capacity_ratio"] >= 2.0
    g = rec.gauges()
    assert g["memory/serve_pool_occupancy"] == sp["occupancy"]


def test_vmem_calibration_rows_and_mispredict_event(monkeypatch):
    """The tuner feedback loop: each kernel's resolved config gets a
    predicted-envelope vs compiled-temp row; an under-predicting
    envelope (forced tiny here) bumps tune/vmem_mispredict."""
    rec = monitor.Recorder(name="t")
    with monitor.attached(rec):
        cal = memory.vmem_calibration(kernels=("fused_layer_norm",),
                                      record=True)
    assert cal["checked"] == 1
    row = cal["rows"][0]
    assert row["kernel"] == "fused_layer_norm"
    assert row["predicted_vmem_bytes"] > 0
    assert row["measured_temp_bytes"] is not None
    assert row["source"] in ("tuned", "heuristic")

    # force an under-prediction: the envelope claims 1 byte
    from apex_tpu.tune import vmem
    monkeypatch.setattr(vmem, "vmem_estimate",
                        lambda kernel, **kw: 1)
    rec2 = monitor.Recorder(name="t")
    with monitor.attached(rec2):
        cal2 = memory.vmem_calibration(kernels=("fused_layer_norm",),
                                       record=True)
    assert cal2["mispredicts"] == 1
    assert rec2.counters().get("tune/vmem_mispredict") == 1
    evs = rec2.records("vmem_calibration")
    assert evs and evs[0]["mispredict"] is True


def test_memory_cli_json_round_trip(capsys):
    """python -m apex_tpu.monitor memory --model mlp --json emits one
    parseable document with the compiled + analytic + calibration
    blocks; --model serve emits the pool accounting."""
    import json as _json

    from apex_tpu.monitor.__main__ import main

    assert main(["memory", "--model", "mlp", "--json"]) == 0
    out = _json.loads(capsys.readouterr().out)
    assert out["profile"]["compiled"]["total_bytes"] > 0
    assert out["profile"]["analytic"]["peak_live_bytes"] > 0
    assert out["vmem_calibration"]["checked"] >= 1

    assert main(["memory", "--model", "serve", "--json"]) == 0
    out = _json.loads(capsys.readouterr().out)
    assert out["serve_pool"]["fp8_capacity_ratio"] >= 2.0
