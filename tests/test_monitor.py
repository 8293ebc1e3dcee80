"""apex_tpu.monitor tests: recorder semantics, the instrumented amp hot
loop, the disabled-mode purity guarantee, collective accounting,
pipeline-schedule telemetry, loader wait timing, and the CLI.

The acceptance contract (ISSUE 2): with a recorder attached to the
simple AMP example step, one training run yields per-step records
containing loss-scale, grad-norm, collective-count, and step-time
fields; with monitoring disabled the step function's jaxpr is
byte-identical to the uninstrumented one.
"""

import io
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import monitor
from apex_tpu.monitor import hooks as mhooks


@pytest.fixture(autouse=True)
def _detached():
    """Every test starts and ends with monitoring disabled."""
    while monitor.get_recorder() is not None:
        monitor.detach()
    yield
    while monitor.get_recorder() is not None:
        monitor.detach()


# ---------------------------------------------------------------------------
# recorder core
# ---------------------------------------------------------------------------

def test_recorder_counters_gauges_timers():
    rec = monitor.Recorder(name="t")
    assert rec.counter("a") == 1
    assert rec.counter("a", 2) == 3
    rec.gauge("g", 1.5)
    rec.gauge("g", 2.5)
    with rec.timer("tm"):
        pass
    assert rec.counters()["a"] == 3
    assert rec.gauges()["g"] == 2.5
    assert rec.counters()["tm/total_s"] >= 0
    kinds = [e["kind"] for e in rec.records()]
    assert kinds.count("counter") >= 2 and "gauge" in kinds \
        and "timer" in kinds


def test_recorder_ring_capacity_drops_oldest():
    rec = monitor.Recorder(capacity=10)
    for i in range(25):
        rec.counter("c")
    assert len(rec.records()) == 10
    assert rec.dropped == 15
    # totals survive eviction (counters are cumulative, not replayed)
    assert rec.counters()["c"] == 25


def test_recorder_step_records_and_deltas():
    rec = monitor.Recorder()
    rec.counter("pre", 5)               # before any step: not attributed
    with rec.step() as i0:
        rec.counter("inside")
        rec.gauge("lv", 7.0)
    with rec.step() as i1:
        rec.counter("inside", 2)
    assert (i0, i1) == (0, 1)
    s0, s1 = rec.steps()
    assert s0["counters"] == {"inside": 1}
    assert s1["counters"] == {"inside": 2}
    assert s0["gauges"] == {"lv": 7.0}
    assert s0["step_time_s"] > 0
    # events emitted inside a step carry its index
    inside = [e for e in rec.records("counter") if e["name"] == "inside"]
    assert [e["step"] for e in inside] == [0, 1]


def test_jsonl_roundtrip_and_aggregate():
    rec = monitor.Recorder(name="rt", meta={"k": "v"})
    with rec.step():
        rec.gauge("x", 1.0)
    with rec.step():
        rec.gauge("x", 3.0)
    buf = io.StringIO()
    n = rec.dump_jsonl(buf)
    buf.seek(0)
    header, events = monitor.load_jsonl(buf)
    assert header["name"] == "rt" and header["meta"] == {"k": "v"}
    assert len(events) == n
    agg = monitor.aggregate(events, header=header)
    assert agg["steps"]["count"] == 2
    assert agg["steps"]["gauges"]["x"] == {"first": 1.0, "last": 3.0, "n": 2}
    # every event line is valid JSON (dump is line-oriented)
    buf.seek(0)
    for ln in buf.read().splitlines():
        json.loads(ln)


def test_attach_detach_epoch_and_context():
    e0 = mhooks.epoch()
    rec = monitor.Recorder()
    assert not mhooks.enabled()
    with monitor.attached(rec):
        assert mhooks.enabled() and monitor.get_recorder() is rec
        assert mhooks.epoch() == e0 + 1
    assert not mhooks.enabled()
    assert mhooks.epoch() == e0 + 2
    # nesting restores the outer recorder
    outer, inner = monitor.Recorder(), monitor.Recorder()
    with monitor.attached(outer):
        with monitor.attached(inner):
            assert monitor.get_recorder() is inner
        assert monitor.get_recorder() is outer


# ---------------------------------------------------------------------------
# the acceptance contract: instrumented simple AMP step
# ---------------------------------------------------------------------------

def _simple_amp_step(dp_axis=False):
    """The examples/simple/main_amp.py hot loop, sized down: amp-armed
    fused optimizer + dynamic scaler (+ optional dp all-reduce under
    shard_map, for real collective counts)."""
    from apex_tpu import amp
    from apex_tpu.amp import scaler as scaler_mod
    from apex_tpu.optimizers import FusedSGD
    from apex_tpu.parallel import allreduce_gradients

    params = {"w1": jnp.ones((4, 8), jnp.float32) * 0.1,
              "w2": jnp.ones((8, 2), jnp.float32) * 0.1}
    opt = FusedSGD(lr=0.05, momentum=0.9)
    opt_state = opt.init(params)
    sstate = scaler_mod.init_state(2.0 ** 8)
    x = jnp.ones((8, 4), jnp.float32)
    y = jnp.zeros((8, 2), jnp.float32)

    def loss_fn(p, x, y):
        h = jnp.tanh(x @ p["w1"])
        return jnp.mean((h @ p["w2"] - y) ** 2)

    if not dp_axis:
        step = amp.make_train_step(loss_fn, opt, donate=False)
        return step, (params, opt_state, sstate, x, y)

    from jax.sharding import Mesh, PartitionSpec as P
    from apex_tpu._compat import shard_map
    mesh = Mesh(np.array(jax.devices()), ("data",))

    def step(params, opt_state, sstate, x, y):
        grads, loss = jax.grad(
            lambda p: (lambda l: (scaler_mod.scale_value(l, sstate), l))(
                loss_fn(p, x, y)), has_aux=True)(params)
        grads = allreduce_gradients(grads, "data")
        grads, found_inf = scaler_mod.unscale(grads, sstate)
        params, opt_state = opt.apply(opt_state, params, grads,
                                      skip=found_inf)
        sstate = scaler_mod.update(sstate, found_inf, dynamic=True)
        return params, opt_state, sstate, jax.lax.pmean(loss, "data")

    fn = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P(), P()), check_vma=False))
    return fn, (params, opt_state, sstate, x, y)


def test_amp_step_per_step_records():
    """One training run with a recorder attached → per-step records with
    loss-scale, grad-norm, collective-count and step-time fields."""
    rec = monitor.Recorder(name="amp-run")
    with monitor.attached(rec):
        step, (params, opt_state, sstate, x, y) = _simple_amp_step(
            dp_axis=True)
        for _ in range(4):
            with rec.step():
                params, opt_state, sstate, loss = step(
                    params, opt_state, sstate, x, y)
    steps = rec.steps()
    assert len(steps) == 4
    for s in steps:
        assert s["step_time_s"] > 0
        assert s["gauges"]["amp/loss_scale"] == 256.0
        assert s["gauges"]["optim/grad_norm"] > 0
        assert "optim/update_norm" in s["gauges"]
        # the dp gradient all-reduce was accounted (trace-time): the
        # cumulative collective table rides on every step record
        psum = s["collectives"].get("psum@data")
        assert psum is not None and psum["count"] >= 1 \
            and psum["bytes"] > 0
    # loss fell: the run was a real training trajectory
    assert float(loss) < 0.05


def test_amp_step_attach_retraces_once_and_detach_restores():
    """make_train_step picks up a recorder attached AFTER compilation
    (the monitoring-epoch static arg), and detaching stops telemetry."""
    step, (params, opt_state, sstate, x, y) = _simple_amp_step()
    # compile while detached
    out = step(params, opt_state, sstate, x, y)
    rec = monitor.Recorder()
    with monitor.attached(rec):
        with rec.step():
            step(params, opt_state, sstate, x, y)
    assert "amp/loss_scale" in rec.steps()[0]["gauges"]
    n_events = len(rec.records())
    # detached again: no further telemetry lands
    step(params, opt_state, sstate, x, y)
    jax.effects_barrier()
    assert len(rec.records()) == n_events


def test_detach_stops_user_owned_jit_telemetry():
    """A user-owned jit traced WHILE attached bakes in callbacks; the
    callback target resolves the recorder at fire time, so detaching
    stops emission (no stale-recorder capture) and a newly attached
    recorder receives subsequent events."""
    from apex_tpu.amp import scaler as scaler_mod

    rec1 = monitor.Recorder()
    sstate = scaler_mod.init_state(128.0)
    with monitor.attached(rec1):
        upd = jax.jit(lambda s: scaler_mod.update(
            s, jnp.asarray(False), dynamic=True))
        sstate = upd(sstate)            # traced + run attached
    jax.effects_barrier()
    n1 = len(rec1.records())
    assert rec1.gauges()["amp/loss_scale"] == 128.0
    # detached: same compiled program, no emission anywhere
    sstate = upd(sstate)
    jax.effects_barrier()
    assert len(rec1.records()) == n1
    # a different recorder attached later receives the events
    rec2 = monitor.Recorder()
    with monitor.attached(rec2):
        upd(sstate)
        jax.effects_barrier()
    assert rec2.gauges().get("amp/loss_scale") == 128.0
    assert len(rec1.records()) == n1
    # a host-only observer opted out of traced telemetry: baked-in
    # callbacks must not deliver into it either
    rec3 = monitor.Recorder(traced_hooks=False)
    with monitor.attached(rec3):
        upd(sstate)
        jax.effects_barrier()
    assert "amp/loss_scale" not in rec3.gauges()


def test_attach_cycles_bound_the_jit_cache():
    """Repeated attach/detach sampling must not grow make_train_step's
    jit cache: the static key is the bool guard, so at most two
    programs (instrumented / uninstrumented) ever exist."""
    step, (params, opt_state, sstate, x, y) = _simple_amp_step()
    step(params, opt_state, sstate, x, y)
    for _ in range(4):
        rec = monitor.Recorder()
        with monitor.attached(rec):
            step(params, opt_state, sstate, x, y)
        step(params, opt_state, sstate, x, y)
    cache_size = getattr(step._jitted, "_cache_size", None)
    if cache_size is not None:
        assert cache_size() <= 2, cache_size()


def test_disabled_mode_jaxpr_byte_identical():
    """With monitoring disabled the traced step is byte-identical to
    the uninstrumented program: stubbing every hook out entirely must
    produce the same jaxpr, and no callback/effect ops may appear
    (while the enabled trace does carry them)."""
    step, (params, opt_state, sstate, x, y) = _simple_amp_step()
    inner = step._jitted.__wrapped__   # the pre-jit python step fn

    def traced():
        return str(jax.make_jaxpr(
            lambda *a: inner(0, *a))(params, opt_state, sstate, x, y))

    disabled = traced()
    assert "callback" not in disabled

    # stub out the hook layer completely — the uninstrumented reference
    import unittest.mock as mock
    with mock.patch.object(mhooks, "traced_scalar", lambda *a, **k: None), \
            mock.patch.object(mhooks, "traced_enabled", lambda: False), \
            mock.patch.object(mhooks, "collective", lambda *a, **k: None):
        uninstrumented = traced()
    assert disabled == uninstrumented

    rec = monitor.Recorder()
    with monitor.attached(rec):
        enabled = traced()
    assert "callback" in enabled and enabled != disabled

    # detaching restores the original bytes exactly
    assert traced() == disabled


def test_host_only_recorder_keeps_program_clean():
    """Recorder(traced_hooks=False): host telemetry flows, traced
    programs stay byte-identical (the bench observer mode)."""
    step, (params, opt_state, sstate, x, y) = _simple_amp_step()
    inner = step._jitted.__wrapped__

    def traced():
        return str(jax.make_jaxpr(
            lambda *a: inner(0, *a))(params, opt_state, sstate, x, y))

    baseline = traced()
    rec = monitor.Recorder(traced_hooks=False)
    with monitor.attached(rec):
        assert traced() == baseline
        with rec.timer("host"):
            pass
    assert rec.counters()["host/total_s"] >= 0


# ---------------------------------------------------------------------------
# collective accounting in the TP mappings
# ---------------------------------------------------------------------------

def test_tp_mapping_collectives_accounted():
    from apex_tpu.transformer import parallel_state as ps
    from apex_tpu.transformer.tensor_parallel import mappings as mp
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map

    ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(tensor_model_parallel_size_=4,
                                        devices=jax.devices()[:4])
    rec = monitor.Recorder()
    x = jnp.ones((4, 16), jnp.float32)

    def fwd(x):
        h = mp.copy_to_tensor_model_parallel_region(x)
        h = mp.reduce_from_tensor_model_parallel_region(h * 2)
        return jnp.sum(mp.gather_from_tensor_model_parallel_region(
            h[:, :4]))

    with monitor.attached(rec):
        fn = jax.jit(shard_map(
            lambda x: jax.grad(fwd)(x), mesh=mesh,
            in_specs=(P(),), out_specs=P(), check_vma=False))
        fn(x)
    colls = rec.collectives()
    # reduce_from fwd psum + copy_to bwd psum on the tensor axis
    assert colls["psum@tensor"]["count"] >= 2
    assert colls["psum@tensor"]["bytes"] >= x.size * 4
    assert colls["all_gather@tensor"]["count"] >= 1
    ps.destroy_model_parallel()


# ---------------------------------------------------------------------------
# pipeline schedule telemetry
# ---------------------------------------------------------------------------

def test_pipeline_schedule_bubble_fraction():
    from apex_tpu.transformer import parallel_state as ps
    from apex_tpu.transformer.pipeline_parallel import pipeline_apply
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map

    ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(pipeline_model_parallel_size_=4)
    rec = monitor.Recorder()
    nmb, P_ = 8, 4

    def stage_fn(w, h):
        return jnp.tanh(h * w)

    def run(x, w):
        return pipeline_apply(stage_fn, w, x, n_microbatches=nmb,
                              remat=False)

    with monitor.attached(rec):
        fn = jax.jit(shard_map(
            run, mesh=mesh, in_specs=(P(), P("pipeline")),
            out_specs=P("pipeline"), check_vma=False))
        x = jnp.ones((nmb, 2, 4), jnp.float32)
        w = jnp.ones((P_,), jnp.float32)
        out = fn(x, w)
        out.block_until_ready()
    jax.effects_barrier()
    expect = 1.0 - nmb / (nmb + P_ - 1)
    got = rec.gauges()["pipeline/fill_drain/bubble_fraction"]
    assert abs(got - expect) < 1e-6, (got, expect)
    agg = rec.aggregate()
    sched = agg["schedules"]["pipeline/fill_drain"]
    assert sched["n_stages"] == P_ and sched["n_microbatches"] == nmb
    # the differentiable fill-drain schedule carries NO per-tick marks
    # (autodiff would drop them inconsistently); only the 1F1B
    # schedules emit ticks — see test_pipeline_1f1b_telemetry
    assert rec.records("tick") == []
    ps.destroy_model_parallel()


def test_pipeline_1f1b_telemetry():
    from apex_tpu.transformer import parallel_state as ps
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        forward_backward_pipelining_1f1b)
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map

    ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(pipeline_model_parallel_size_=2)
    rec = monitor.Recorder()
    nmb = 4

    def stage_fn(w, h):
        return jnp.tanh(h * w)

    def run(x, w):
        loss, g = forward_backward_pipelining_1f1b(
            stage_fn, lambda h: jnp.sum(h.astype(jnp.float32)), w, x, nmb)
        return jax.lax.psum(loss, ps.PIPELINE_AXIS)

    with monitor.attached(rec):
        fn = jax.jit(shard_map(
            run, mesh=mesh, in_specs=(P(), P("pipeline")),
            out_specs=P(), check_vma=False))
        fn(jnp.ones((nmb, 2, 4), jnp.float32),
           jnp.ones((2,), jnp.float32)).block_until_ready()
    jax.effects_barrier()
    assert "pipeline/1f1b/bubble_fraction" in rec.gauges()
    # the 1f1b scan is not differentiated-through: tick marks survive
    ticks = [e for e in rec.records("tick")
             if e["name"] == "pipeline/1f1b/tick"]
    assert len(ticks) >= nmb + 2  # nmb + 2(P-1) ticks, 2 ranks each
    ps.destroy_model_parallel()


# ---------------------------------------------------------------------------
# data loader wait instrumentation
# ---------------------------------------------------------------------------

def test_loader_host_wait_recorded():
    from apex_tpu.data import DataLoader

    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 255, (32, 8, 8, 3), dtype=np.uint8)
    labels = np.arange(32, dtype=np.int32)
    dl = DataLoader(imgs, labels, batch_size=8, augment=False,
                    shuffle=False, workers=1, prefetch=2)
    rec = monitor.Recorder()
    with monitor.attached(rec):
        batches = list(dl)
    assert len(batches) == 4
    assert rec.counters()["data/batches"] == 4
    waits = [e for e in rec.records("timer") if e["name"] == "data/host_wait"]
    assert len(waits) >= 4
    assert all(w["value"] >= 0 for w in waits)


# ---------------------------------------------------------------------------
# scaler / handle host telemetry
# ---------------------------------------------------------------------------

def test_eager_scaler_counters():
    from apex_tpu.amp.scaler import LossScaler

    rec = monitor.Recorder()
    sc = LossScaler("dynamic", init_scale=256.0, scale_window=2)
    with monitor.attached(rec):
        assert sc.update_scale(found_inf=True)       # skip
        assert not sc.update_scale(found_inf=False)
        assert not sc.update_scale(found_inf=False)  # window expiry
    assert rec.counters()["amp/skipped_steps"] == 1
    assert rec.counters()["amp/growth_interval_resets"] == 1
    summ = sc.state_summary()
    assert summ["skipped_steps"] == 1
    assert summ["growth_interval_resets"] == 1


# ---------------------------------------------------------------------------
# trace layer
# ---------------------------------------------------------------------------

def test_compile_event_logging():
    monitor.trace.install_compile_logging()
    rec = monitor.Recorder()
    with monitor.attached(rec):
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((16,)))
    c = rec.counters()
    assert c.get("jax/compile/trace/total_s", 0) > 0
    assert c.get("jax/compile/backend/total_s", 0) > 0
    # detached: events are discarded, not queued
    n = len(rec.records())
    jax.jit(lambda x: x * 5 - 2)(jnp.ones((16,)))
    assert len(rec.records()) == n


def test_wrap_and_annotate_record_timers():
    rec = monitor.Recorder()

    @monitor.trace.wrap
    def f(x):
        return x + 1

    with monitor.attached(rec):
        assert float(f(jnp.ones(()))) == 2.0
    assert rec.counters()["trace/f/total_s"] >= 0
    # detached: wrap still annotates, records nothing
    assert float(f(jnp.ones(()))) == 2.0
    assert rec.aggregate()["timers"]["trace/f"]["n"] == 1


def test_memory_analysis_and_snapshot():
    ma = monitor.trace.memory_analysis(
        lambda x: x @ x.T, jnp.ones((32, 16), jnp.float32))
    assert ma.get("argument_size_in_bytes", 0) >= 32 * 16 * 4
    assert ma.get("output_size_in_bytes", 0) >= 32 * 32 * 4
    rows = monitor.trace.device_memory_snapshot()
    assert len(rows) == len(jax.local_devices())
    assert all("device" in r for r in rows)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_report_and_json(tmp_path):
    rec = monitor.Recorder(name="cli")
    with monitor.attached(rec):
        step, (params, opt_state, sstate, x, y) = _simple_amp_step()
        for _ in range(2):
            with rec.step():
                params, opt_state, sstate, _ = step(
                    params, opt_state, sstate, x, y)
    p = tmp_path / "run.jsonl"
    rec.dump_jsonl(str(p))

    from apex_tpu.monitor.__main__ import main as cli_main
    import contextlib as _ctx
    buf = io.StringIO()
    with _ctx.redirect_stdout(buf):
        assert cli_main(["report", str(p)]) == 0
    out = buf.getvalue()
    assert "monitor report: cli" in out and "amp/loss_scale" in out

    buf = io.StringIO()
    with _ctx.redirect_stdout(buf):
        assert cli_main(["report", str(p), "--json"]) == 0
    agg = json.loads(buf.getvalue())
    assert agg["steps"]["count"] == 2


@pytest.mark.slow
def test_cli_selfcheck_subprocess():
    out = subprocess.run(
        [sys.executable, "-m", "apex_tpu.monitor", "selfcheck", "--quiet"],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]


def test_selfcheck_inline():
    agg = monitor.selfcheck(n_steps=3, verbose=False)
    assert agg["steps"]["count"] == 3


# ---------------------------------------------------------------------------
# pyprof parity shim still serves the old surface
# ---------------------------------------------------------------------------

def test_pyprof_shim_reexports_monitor():
    from apex_tpu import pyprof
    assert pyprof.annotate is monitor.trace.annotate
    assert pyprof.parse.op_stats_from_raw is monitor.xprof.op_stats_from_raw
    assert pyprof.prof.cost_analysis is monitor.trace.cost_analysis
    assert pyprof.nvtx.wrap is monitor.trace.wrap


# ---------------------------------------------------------------------------
# the tool side loads on first use: every listed name has a module behind it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", monitor._LAZY_MODULES)
def test_lazy_tool_module_resolves(name):
    mod = getattr(monitor, name)
    assert mod.__name__ == f"apex_tpu.monitor.{name}"


@pytest.mark.parametrize("name", sorted(monitor._LAZY_NAMES))
def test_lazy_tool_name_resolves_to_its_home(name):
    home = getattr(monitor, monitor._LAZY_NAMES[name])
    assert getattr(monitor, name) is getattr(home, name)


def test_cli_has_no_verb_without_a_module(capsys):
    """``fleet`` went with ``monitor/fleet.py``: the parser refuses it as
    it refuses any unknown verb, before anything is imported."""
    from apex_tpu.monitor.__main__ import main
    with pytest.raises(SystemExit) as ei:
        main(["fleet", "http://127.0.0.1:1/metrics", "--once"])
    assert ei.value.code == 2
    assert "invalid choice: 'fleet'" in capsys.readouterr().err
