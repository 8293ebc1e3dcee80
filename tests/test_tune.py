"""Pallas kernel autotuner (``apex_tpu.tune``, ISSUE 8).

Everything runs on CPU: sweeps go through interpret mode with an
injectable deterministic fake clock, so cache resolution, ranking and
persistence are tested without a TPU. The acceptance contracts:

- ``python -m apex_tpu.ops tune`` produces a cache file that a
  subsequent ``flash_attention(block_q=None)`` / ``lm_head_ce`` call
  resolves blocks from (asserted via monitor ``tune/cache_hit`` AND the
  traced kernel grid);
- ``autotune="off"`` reproduces today's defaults bit-for-bit
  (jaxpr-identical, modulo object addresses — the test_overlap idiom);
- same grid + same fake timings => same chosen config;
- corrupt JSON / unknown schema / cross-device_kind entries fall back
  to heuristics silently-but-gauged, and a partial atomic-write tmp
  file never shadows a good cache.
"""

import json
import os
import re
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import monitor
from apex_tpu.ops.flash_attention import flash_attention
from apex_tpu.ops.lm_head_ce import fused_lm_head_cross_entropy
from apex_tpu.tune import cache as tune_cache
from apex_tpu.tune import harness, space, vmem
from apex_tpu.tune import runtime as tune_rt
from apex_tpu.utils import parity

FWD_FLAGS = {"causal": True, "bias": False, "dropout": False,
             "segments": False}


def _normalized(jaxpr_str):
    return re.sub(r"0x[0-9a-f]+", "0xADDR", jaxpr_str)


def _pallas_grids(fn, *args):
    """Grids of every pallas_call in the traced program (outermost
    first) — how the tests see which block config actually ran."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for pv in eqn.params.values():
                if hasattr(pv, "jaxpr"):
                    walk(pv.jaxpr)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    d = str(tmp_path / "tune")
    monkeypatch.setenv(tune_cache.ENV_CACHE_DIR, d)
    tune_rt.invalidate()
    yield d
    tune_rt.invalidate()


def _qkv(b=1, h=2, s=256, d=32, dtype=jnp.float32):
    rng = np.random.RandomState(0)
    mk = lambda *sh: jnp.asarray(rng.randn(*sh) * 0.1, dtype)  # noqa: E731
    return mk(b, h, s, d), mk(b, h, s, d), mk(b, h, s, d)


def _flash_shape(q, k):
    return {"b": q.shape[0], "h": q.shape[1], "sq": q.shape[2],
            "sk": k.shape[2], "d": q.shape[3],
            "itemsize": q.dtype.itemsize}


def _seed_flash_cache(tune_dir, q, k, *, fwd=None, bwd=None,
                      dtype="float32", flags=FWD_FLAGS):
    c = tune_cache.TuneCache(tune_dir)
    shape = _flash_shape(q, k)
    if fwd is not None:
        c.put(tune_cache.cache_key("flash_attention_fwd", shape, dtype,
                                   flags), fwd)
    if bwd is not None:
        c.put(tune_cache.cache_key("flash_attention_bwd", shape, dtype,
                                   flags), bwd)
    tune_rt.invalidate()
    return c


# ---------------------------------------------------------------------------
# vmem envelope + config space
# ---------------------------------------------------------------------------

def test_vmem_calibration_points():
    """The envelope reproduces every hardware-verified pass/fail from
    the flash module docstring and the lm_head_ce budget math."""
    ok = dict(block_q=1024, block_k=1024, d=64, itemsize=2)
    assert vmem.fits("flash_attention_fwd", **ok)                 # default
    assert vmem.fits("flash_attention_fwd", bias=True, **ok)      # bias ok
    assert vmem.fits("flash_attention_fwd", dropout=True, **ok)   # drop ok
    assert not vmem.fits("flash_attention_fwd", bias=True,
                         dropout=True, **ok)   # both exceed VMEM (docstring)
    assert not vmem.fits("flash_attention_fwd", block_q=2048,
                         block_k=2048, d=64, itemsize=2)
    assert vmem.fits("flash_attention_fwd", block_q=512, block_k=512,
                     d=64, itemsize=2, bias=True, dropout=True)
    # backward: fused-at-1024 ran on hardware; 512 is the tuned default
    assert vmem.fits("flash_attention_bwd", **ok)
    assert vmem.fits("flash_attention_bwd", block_q=512, block_k=512,
                     d=64, itemsize=2)
    # lm_head_ce defaults are ~24 MB — inside the raised 64 MB limit
    est = vmem.vmem_estimate("lm_head_ce", block_t=512, block_v=2048,
                             h=1024, itemsize=2)
    assert 20 * 2**20 < est < 30 * 2**20
    assert est <= vmem.budget_for("lm_head_ce")


def test_config_space_pruned_and_clipped():
    configs = space.config_space(
        "flash_attention_fwd",
        {"sq": 1024, "sk": 1024, "d": 64, "itemsize": 2},
        {"bias": True, "dropout": True})
    assert configs, "space must not be empty"
    for cfg in configs:
        assert vmem.fits("flash_attention_fwd", block_q=cfg["block_q"],
                         block_k=cfg["block_k"], d=64, itemsize=2,
                         bias=True, dropout=True)
    # bias+dropout kill the (1024, 1024) tile (module docstring)
    assert {"block_q": 1024, "block_k": 1024} not in configs
    # blocks clip to the (pow2-rounded) sequence extent
    small = space.config_space(
        "flash_attention_fwd", {"sq": 128, "sk": 128, "d": 64}, {})
    assert small == [{"block_q": 128, "block_k": 128}]
    ce = space.config_space("lm_head_ce",
                            {"n": 8192, "v": 32768, "h": 1024}, {})
    for cfg in ce:
        assert vmem.fits("lm_head_ce", block_t=cfg["block_t"],
                         block_v=cfg["block_v"], h=1024, itemsize=2)
    assert {"block_t": 512, "block_v": 2048} in ce   # the shipped default


# ---------------------------------------------------------------------------
# sweep harness
# ---------------------------------------------------------------------------

def test_sweep_deterministic_under_fake_clock():
    """Same grid + same fake timings => same chosen config, including
    the tie-break (candidate order), and the monitor timer path records
    every measurement."""
    candidates = [{"block_q": bq, "block_k": bk}
                  for bq in (128, 256) for bk in (128, 256)]
    costs = {(128, 128): 3.0, (128, 256): 1.0, (256, 128): 1.0,
             (256, 256): 2.0}

    def fake(fn, cfg):
        return costs[(cfg["block_q"], cfg["block_k"])]

    build = lambda cfg: (lambda: None)  # noqa: E731
    rec = monitor.Recorder()
    with monitor.attached(rec):
        r1 = harness.sweep(candidates, build, timer=fake, median_of=3,
                           warmup=0, label="t")
    r2 = harness.sweep(candidates, build, timer=fake, median_of=3,
                       warmup=0, label="t")
    assert r1["best"] == r2["best"]
    # two configs tie at 1.0: candidate order must break the tie
    assert r1["best"] == {"block_q": 128, "block_k": 256}
    assert r1["best_s"] == 1.0
    assert [r["config"] for r in r1["results"]] == \
        [r["config"] for r in r2["results"]]
    timers = [e for e in rec.records("timer")
              if e["name"] == "tune/sweep/t"]
    assert len(timers) == len(candidates) * 3


def test_sweep_failed_config_skipped():
    candidates = [{"block_q": 128, "block_k": 128},
                  {"block_q": 256, "block_k": 256}]

    def build(cfg):
        if cfg["block_q"] == 128:
            raise RuntimeError("mosaic says no")
        return lambda: None

    r = harness.sweep(candidates, build, timer=lambda f, c: 1.0,
                      median_of=1, warmup=1)
    assert r["best"] == {"block_q": 256, "block_k": 256}
    assert len(r["failed"]) == 1
    assert "mosaic says no" in r["failed"][0]["error"]


def test_sweep_per_config_timeout():
    """A pathological config cannot eat the sweep: its build is cut off
    by the per-config budget and recorded as failed."""
    import time as _time
    candidates = [{"block_q": 128, "block_k": 128},
                  {"block_q": 256, "block_k": 256}]

    def build(cfg):
        if cfg["block_q"] == 128:
            _time.sleep(30)        # "pathological compile"
        return lambda: None

    t0 = __import__("time").perf_counter()
    r = harness.sweep(candidates, build, timer=lambda f, c: 1.0,
                      median_of=1, warmup=0, config_timeout_s=0.3)
    assert __import__("time").perf_counter() - t0 < 10
    assert r["best"] == {"block_q": 256, "block_k": 256}
    assert len(r["failed"]) == 1
    assert "budget" in r["failed"][0]["error"]


def test_sweep_preserves_enclosing_alarm_budget():
    """ITIMER_REAL is process-global: a sweep running inside an outer
    SIGALRM budget (a caller's own alarm) must leave that
    budget armed with its remaining time, not cancel it."""
    import signal

    fired = []
    prev_handler = signal.signal(signal.SIGALRM,
                                 lambda s, f: fired.append(s))
    signal.setitimer(signal.ITIMER_REAL, 30.0)    # the "section budget"
    try:
        harness.sweep([{"block_q": 128, "block_k": 128}],
                      lambda cfg: (lambda: None),
                      timer=lambda f, c: 1.0, median_of=1, warmup=0,
                      config_timeout_s=5.0)
        remaining, _ = signal.getitimer(signal.ITIMER_REAL)
        assert 0 < remaining <= 30.0, \
            f"outer alarm budget cancelled (remaining={remaining})"
        assert signal.getsignal(signal.SIGALRM) is not None
        assert not fired
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, prev_handler)


def test_sweep_propagates_base_exceptions():
    """BaseException control flow (a caller's timeout raised from an
    outer SIGALRM handler, which broad excepts must not eat) escapes the
    sweep instead of being recorded as a failed config."""
    class _SectionTimeout(BaseException):
        pass

    def build(cfg):
        raise _SectionTimeout()

    with pytest.raises(_SectionTimeout):
        harness.sweep([{"block_q": 128, "block_k": 128}], build,
                      timer=lambda f, c: 1.0, median_of=1, warmup=0)


# ---------------------------------------------------------------------------
# cache: persistence + robustness
# ---------------------------------------------------------------------------

def test_cache_round_trip(tmp_path):
    c = tune_cache.TuneCache(str(tmp_path), device_kind="cpu")
    key = tune_cache.cache_key(
        "flash_attention_fwd",
        {"b": 8, "h": 16, "sq": 1024, "sk": 1024, "d": 64},
        "bfloat16", {"causal": True})
    c.put(key, {"block_q": 512, "block_k": 512}, ms=1.17, swept=9)
    # a fresh handle reads the same entry from disk
    c2 = tune_cache.TuneCache(str(tmp_path), device_kind="cpu")
    assert c2.lookup(key) == {"block_q": 512, "block_k": 512}
    data = json.load(open(c2.path))
    assert data["schema"] == tune_cache.SCHEMA
    assert data["entries"][key]["ms"] == 1.17
    assert c2.lookup("no|such|key|here") is None


def test_cache_shape_bucketing():
    """b*h and sequence extents bucket to powers of two — one entry
    serves the whole bucket; d/h stay exact (they set tile geometry)."""
    k1 = tune_cache.cache_key(
        "flash_attention_fwd",
        {"b": 7, "h": 9, "sq": 1000, "sk": 1000, "d": 64}, "bfloat16", {})
    k2 = tune_cache.cache_key(
        "flash_attention_fwd",
        {"b": 8, "h": 8, "sq": 1024, "sk": 1024, "d": 64}, "bfloat16", {})
    assert k1 == k2
    k3 = tune_cache.cache_key(
        "flash_attention_fwd",
        {"b": 8, "h": 8, "sq": 1024, "sk": 1024, "d": 128}, "bfloat16", {})
    assert k3 != k2


def _miss_returns_defaults(tune_dir, expect_miss=1):
    """Call flash_attention under a recorder; assert heuristic grid +
    gauged misses."""
    q, k, v = _qkv()
    rec = monitor.Recorder()
    with monitor.attached(rec):
        grids = _pallas_grids(
            lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
    # heuristic default: 1024 clamps to s=256 -> one (1, 2, 1, 1) grid
    assert grids == [(1, 2, 1, 1)]
    assert rec.counters().get("tune/cache_miss", 0) >= expect_miss
    assert rec.counters().get("tune/cache_hit", 0) == 0
    assert rec.gauges().get("tune/cache_hit") == 0.0
    tunes = rec.records("tune")
    assert tunes and all(not e["hit"] for e in tunes)


def test_cache_corrupt_json_degrades_to_heuristics(tune_dir):
    os.makedirs(tune_dir, exist_ok=True)
    with open(os.path.join(tune_dir, "cpu.json"), "w") as f:
        f.write('{"schema": 1, "entries": {TRUNCATED')
    _miss_returns_defaults(tune_dir)


def test_cache_unknown_schema_degrades_to_heuristics(tune_dir):
    q, k, _ = _qkv()
    c = _seed_flash_cache(tune_dir, q, k, fwd={"block_q": 128,
                                               "block_k": 128})
    data = json.load(open(c.path))
    data["schema"] = 999
    with open(c.path, "w") as f:
        json.dump(data, f)
    tune_rt.invalidate()
    _miss_returns_defaults(tune_dir)


def test_cache_cross_device_kind_degrades_to_heuristics(tune_dir):
    """Entries tuned for another device kind are never served, even
    when they sit in the file the current kind would read."""
    q, k, _ = _qkv()
    c = _seed_flash_cache(tune_dir, q, k, fwd={"block_q": 128,
                                               "block_k": 128})
    data = json.load(open(c.path))
    data["device_kind"] = "TPU v5e"
    with open(c.path, "w") as f:
        json.dump(data, f)
    tune_rt.invalidate()
    _miss_returns_defaults(tune_dir)


def test_cache_atomic_write_partial_tmp_never_shadows(tune_dir):
    """Crash mid-write: the .tmp.<pid> sibling a killed process leaves
    behind is never read — the canonical file keeps serving."""
    q, k, _ = _qkv()
    c = _seed_flash_cache(tune_dir, q, k, fwd={"block_q": 128,
                                               "block_k": 128})
    # simulate the crash: a partial serialization next to the good file
    with open(c.path + ".tmp.99999", "w") as f:
        f.write('{"schema": 1, "device_kind": "cpu", "entries": {CRASH')
    tune_rt.invalidate()
    key = tune_cache.cache_key("flash_attention_fwd", _flash_shape(q, k),
                               "float32", FWD_FLAGS)
    c2 = tune_cache.TuneCache(tune_dir)
    assert c2.lookup(key) == {"block_q": 128, "block_k": 128}
    # and an interrupted _write (exception before os.replace) leaves
    # the old entry intact
    import unittest.mock as mock
    with mock.patch("os.replace", side_effect=OSError("disk full")):
        with pytest.raises(OSError):
            c2.put(key, {"block_q": 64, "block_k": 64})
    c3 = tune_cache.TuneCache(tune_dir)
    assert c3.lookup(key) == {"block_q": 128, "block_k": 128}


def test_cache_malformed_entry_values(tune_dir):
    q, k, _ = _qkv()
    c = _seed_flash_cache(tune_dir, q, k, fwd={"block_q": 128,
                                               "block_k": 128})
    data = json.load(open(c.path))
    key = next(iter(data["entries"]))
    data["entries"][key] = {"config": {"block_q": "huge", "block_k": -1}}
    with open(c.path, "w") as f:
        json.dump(data, f)
    tune_rt.invalidate()
    _miss_returns_defaults(tune_dir)


def test_cache_drifted_config_key_names(tune_dir):
    """An entry whose config NAMES drifted (hand-edit, schema
    evolution) is a miss, not a KeyError inside the kernel call."""
    q, k, v = _qkv()
    c = _seed_flash_cache(tune_dir, q, k, fwd={"block_q": 128,
                                               "block_k": 128})
    data = json.load(open(c.path))
    key = next(iter(data["entries"]))
    data["entries"][key] = {"config": {"block_t": 128, "block_v": 128}}
    with open(c.path, "w") as f:
        json.dump(data, f)
    tune_rt.invalidate()
    _miss_returns_defaults(tune_dir)


def test_cache_drifted_config_values(tune_dir):
    """Value-level drift — misaligned tiles or envelope-busting sizes —
    degrades to the heuristic instead of failing at Mosaic compile."""
    q, k, _ = _qkv()
    _seed_flash_cache(tune_dir, q, k, fwd={"block_q": 7, "block_k": 136})
    _miss_returns_defaults(tune_dir)          # not (8, 128)-aligned
    _seed_flash_cache(tune_dir, q, k, fwd={"block_q": 65536,
                                           "block_k": 65536})
    _miss_returns_defaults(tune_dir)          # over the VMEM envelope


# ---------------------------------------------------------------------------
# runtime resolution in flash_attention
# ---------------------------------------------------------------------------

def test_flash_fwd_and_bwd_resolve_from_cache(tune_dir):
    """Tuned entries govern the traced kernel grids — forward and
    backward independently — and resolutions land as monitor hits."""
    q, k, v = _qkv()          # s=256: heuristic default is one block
    _seed_flash_cache(tune_dir, q, k,
                      fwd={"block_q": 128, "block_k": 128},
                      bwd={"block_q": 64, "block_k": 64})
    rec = monitor.Recorder()
    with monitor.attached(rec):
        fwd_grids = _pallas_grids(
            lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
        bwd_grids = _pallas_grids(
            lambda q, k, v: jax.grad(lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True) ** 2),
                argnums=0)(q, k, v), q, k, v)
    assert fwd_grids == [(1, 2, 2, 2)]            # 256/128 q- and k-blocks
    # grad trace: fwd at 128-blocks + fused bwd at 64-blocks
    assert (1, 2, 4, 4) in bwd_grids
    assert rec.counters()["tune/cache_hit"] >= 2
    assert rec.gauges()["tune/cache_hit"] == 1.0
    hits = [e for e in rec.records("tune") if e["hit"]]
    assert {e["name"] for e in hits} == {"flash_attention_fwd",
                                         "flash_attention_bwd"}
    # numerics unchanged vs the heuristic tiling (same math, new tiles)
    tuned = flash_attention(q, k, v, causal=True)
    ref = flash_attention(q, k, v, causal=True, autotune="off")
    np.testing.assert_allclose(np.asarray(tuned), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_explicit_blocks_always_win(tune_dir):
    q, k, v = _qkv()
    _seed_flash_cache(tune_dir, q, k,
                      fwd={"block_q": 128, "block_k": 128},
                      bwd={"block_q": 64, "block_k": 64})
    grids = _pallas_grids(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        block_q=256, block_k=256,
                                        block_q_bwd=256, block_k_bwd=256),
        q, k, v)
    assert grids == [(1, 2, 1, 1)]


def test_flash_autotune_off_is_jaxpr_identical(tune_dir):
    """``autotune="off"`` (and the env-var form) reproduces today's
    heuristic defaults bit-for-bit even when a cache entry exists."""
    q, k, v = _qkv()
    _seed_flash_cache(tune_dir, q, k,
                      fwd={"block_q": 128, "block_k": 128})

    def traced(**kw):
        return _normalized(str(jax.make_jaxpr(
            lambda q, k, v: jax.value_and_grad(lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True, **kw) ** 2),
                argnums=(0, 1, 2))(q, k, v))(q, k, v)))

    j_off = traced(autotune="off")
    j_explicit = traced(block_q=256, block_k=256, block_q_bwd=256,
                        block_k_bwd=256)
    assert j_off == j_explicit      # the s=256-clamped heuristic default
    j_cache = traced()
    assert j_cache != j_off         # sanity: the cache really retunes
    os.environ[tune_rt.ENV_POLICY] = "off"
    try:
        assert traced() == j_off
    finally:
        del os.environ[tune_rt.ENV_POLICY]


def test_flash_invalid_policy_raises(tune_dir):
    q, k, v = _qkv(s=32)
    with pytest.raises(ValueError, match="autotune policy"):
        flash_attention(q, k, v, autotune="aggressive")
    with pytest.raises(ValueError, match="autotune policy"):
        flash_attention(q, k, v, block_q=16, block_k=16, block_q_bwd=16,
                        block_k_bwd=16, autotune="aggressive")


def test_cache_resolved_bwd_retires_inheritance_warning(tune_dir):
    """Satellite: when the cache supplies backward blocks, explicit
    forward blocks no longer warn about governing the backward — and
    the once-key is NOT consumed, so a later uncached call still gets
    its warning."""
    q, k, v = _qkv()
    _seed_flash_cache(tune_dir, q, k, bwd={"block_q": 64, "block_k": 64})
    key = "flash_attention.inherited_bwd_blocks"
    parity._seen.discard(key)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    assert key not in parity._seen, "once-key consumed by the cached path"
    # the cached bwd blocks actually governed the backward
    bwd_grids = _pallas_grids(
        lambda q, k, v: jax.grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=128,
                            block_k=128) ** 2), argnums=0)(q, k, v),
        q, k, v)
    assert (1, 2, 4, 4) in bwd_grids
    # a shape OUTSIDE the cached bucket still warns (both paths tested)
    q2, k2, v2 = _qkv(s=64)
    with pytest.warns(UserWarning, match="govern the BACKWARD"):
        flash_attention(q2, k2, v2, causal=True, block_q=32, block_k=32)
    assert key in parity._seen
    parity._seen.discard(key)


def test_flash_online_tunes_on_first_miss(tune_dir):
    """autotune="online": first call sweeps (real interpret timings on
    a single-candidate space), stores, and serves; the second call is a
    pure cache hit."""
    q, k, v = _qkv(s=128, d=8)   # 128-extent: one legal candidate/phase
    rec = monitor.Recorder()
    with monitor.attached(rec):
        out = flash_attention(q, k, v, causal=True, autotune="online")
    c = rec.counters()
    assert c.get("tune/cache_miss", 0) == 2          # fwd + bwd sweeps
    tunes = rec.records("tune")
    assert all(e["source"] == "online" and e["config"] for e in tunes)
    # the sweep persisted: second call hits without sweeping
    rec2 = monitor.Recorder()
    with monitor.attached(rec2):
        out2 = flash_attention(q, k, v, causal=True, autotune="online")
    assert rec2.counters().get("tune/cache_hit", 0) == 2
    assert "tune/cache_miss" not in rec2.counters()
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                               rtol=1e-6)
    ref = flash_attention(q, k, v, causal=True, autotune="off")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# runtime resolution in fused_lm_head_cross_entropy
# ---------------------------------------------------------------------------

def _xet(n=64, v=300, h=32):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(n, h) * 0.05, jnp.float32)
    e = jnp.asarray(rng.randn(v, h) * 0.05, jnp.float32)
    t = jnp.asarray(rng.randint(0, v, (n,)), jnp.int32)
    return x, e, t


def test_lm_head_resolves_from_cache(tune_dir):
    x, e, t = _xet()
    c = tune_cache.TuneCache(tune_dir)
    key = tune_cache.cache_key(
        "lm_head_ce", {"n": 64, "v": 300, "h": 32, "itemsize": 4},
        "float32", {"smoothing": False})
    c.put(key, {"block_t": 32, "block_v": 128})
    tune_rt.invalidate()
    rec = monitor.Recorder()
    with monitor.attached(rec):
        grids = _pallas_grids(
            lambda x, e, t: fused_lm_head_cross_entropy(x, e, t), x, e, t)
    # n=64 pads to 64/32=2 token blocks, v=300 pads to 3 vocab blocks
    assert grids == [(3, 2)]
    assert rec.counters()["tune/cache_hit"] == 1
    off_grids = _pallas_grids(
        lambda x, e, t: fused_lm_head_cross_entropy(x, e, t,
                                                    autotune="off"),
        x, e, t)
    assert off_grids == [(1, 1)]      # heuristic: one big tile pair
    tuned = fused_lm_head_cross_entropy(x, e, t)
    ref = fused_lm_head_cross_entropy(x, e, t, autotune="off")
    np.testing.assert_allclose(np.asarray(tuned), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_lm_head_half_explicit_over_budget_warns_nearest_legal():
    """Satellite: one explicit knob + the other's default exceeding the
    VMEM limit used to compile silently; now it warns once and runs the
    nearest legal pair."""
    n, v, h = 64, 9000, 2048
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(n, h) * 0.05, jnp.float32)
    e = jnp.asarray(rng.randn(v, h) * 0.05, jnp.float32)
    t = jnp.asarray(rng.randint(0, v, (n,)), jnp.int32)
    key = "lm_head_ce.half_explicit_over_budget"
    parity._seen.discard(key)
    with pytest.warns(UserWarning, match="nearest legal pair"):
        loss = fused_lm_head_cross_entropy(x, e, t, block_v=8192,
                                           autotune="off")
    ref = fused_lm_head_cross_entropy(x, e, t, autotune="off")
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    # fully-explicit pairs stay the user's responsibility: no warning
    parity._seen.discard(key)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        fused_lm_head_cross_entropy(_xet()[0], _xet()[1], _xet()[2],
                                    block_t=32, block_v=128,
                                    autotune="off")
    # and the defaulted-pair heuristic path never warns
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        fused_lm_head_cross_entropy(_xet()[0], _xet()[1], _xet()[2],
                                    autotune="off")


def test_lm_head_legal_half_explicit_unchanged():
    """A half-explicit pair that FITS keeps today's behavior exactly
    (no warning, explicit knob + heuristic default)."""
    x, e, t = _xet()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        a = fused_lm_head_cross_entropy(x, e, t, block_t=32,
                                        autotune="off")
    b = fused_lm_head_cross_entropy(x, e, t, autotune="off")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-6)


# ---------------------------------------------------------------------------
# the offline CLI
# ---------------------------------------------------------------------------

def test_cli_tune_produces_cache_both_kernels_resolve(tune_dir, capsys):
    """Acceptance: ``python -m apex_tpu.ops tune`` produces a cache
    file; subsequent ``flash_attention(block_q=None)`` and
    ``lm_head_ce`` calls resolve blocks from it (monitor cache_hit +
    traced grid)."""
    from apex_tpu.ops.__main__ import main
    rc = main(["tune", "--kernel", "flash_attention",
               "--shapes", "b=1,h=2,s=128,d=32,dtype=fp32,causal=1",
               "--cache", tune_dir, "--median-of", "1", "--warmup", "0",
               "--timeout", "120"])
    assert rc == 0
    rc = main(["tune", "--kernel", "lm_head_ce",
               "--shapes", "n=64,v=300,h=32,dtype=fp32",
               "--cache", tune_dir, "--median-of", "1", "--warmup", "0"])
    assert rc == 0
    capsys.readouterr()
    cache_file = os.path.join(tune_dir, "cpu.json")
    assert os.path.exists(cache_file)
    data = json.load(open(cache_file))
    assert data["schema"] == tune_cache.SCHEMA
    kinds = {k.split("|")[0] for k in data["entries"]}
    assert kinds == {"flash_attention_fwd", "flash_attention_bwd",
                     "lm_head_ce"}
    tune_rt.invalidate()
    q, k, v = _qkv(s=128)
    x, e, t = _xet()
    rec = monitor.Recorder()
    with monitor.attached(rec):
        fa_grids = _pallas_grids(
            lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
        ce_grids = _pallas_grids(
            lambda x, e, t: fused_lm_head_cross_entropy(x, e, t), x, e, t)
    assert rec.counters()["tune/cache_hit"] >= 3   # fa fwd + fa bwd + ce
    fa_cfg = data["entries"][tune_cache.cache_key(
        "flash_attention_fwd", _flash_shape(q, k), "float32",
        FWD_FLAGS)]["config"]
    assert fa_grids == [(1, 2, 128 // fa_cfg["block_q"],
                         128 // fa_cfg["block_k"])]
    ce_key = tune_cache.cache_key(
        "lm_head_ce", {"n": 64, "v": 300, "h": 32}, "float32",
        {"smoothing": False})
    ce_cfg = data["entries"][ce_key]["config"]
    n_vb = -(-300 // ce_cfg["block_v"])
    n_tb = -(-64 // ce_cfg["block_t"])
    assert ce_grids == [(n_vb, n_tb)]


def test_cli_list_and_json(tune_dir, capsys):
    from apex_tpu.ops.__main__ import main
    rc = main(["tune", "--kernel", "lm_head_ce",
               "--shapes", "n=64,v=300,h=32,dtype=fp32",
               "--cache", tune_dir, "--median-of", "1", "--json"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    payload = json.loads(out)
    assert payload["tuned"] and payload["tuned"][0]["best"]
    rc = main(["tune", "--list", "--cache", tune_dir])
    assert rc == 0
    assert "lm_head_ce|" in capsys.readouterr().out


def test_cli_shape_spec_validation():
    from apex_tpu.tune import kernels as tk
    spec = tk.parse_shape_spec("flash_attention",
                               "b=8,h=16,s=1024,d=64,dtype=bf16,causal=1")
    assert spec == {"b": 8, "h": 16, "sq": 1024, "sk": 1024, "d": 64,
                    "dtype": "bfloat16", "causal": True}
    with pytest.raises(ValueError, match="unknown shape field"):
        tk.parse_shape_spec("flash_attention", "b=8,z=3")
    with pytest.raises(ValueError, match="needs"):
        tk.parse_shape_spec("lm_head_ce", "n=64,v=300")
    with pytest.raises(ValueError, match="unknown dtype"):
        tk.split_shape("lm_head_ce",
                       {"n": 64, "v": 300, "h": 32, "dtype": "bf_16"})
