"""The ONE memory surface: compiled footprints, analytic high water,
live HBM timeline, and the byte accounting every capacity claim reports
through.

Memory is the capacity axis behind the repo's headline claims (ZeRO-3's
~world# per-chip resident-byte shrink, fp8-KV's >=2x concurrent
sequences, the zero-bubble wgrad-stash envelopes, the tuner's VMEM
budget model) — this module is where all of those become observable
through one vocabulary, in the mold of ``profile.py``/``spans.py``:

- :func:`compiled_memory_profile` — XLA's own static accounting for a
  compiled program (``Compiled.memory_analysis()``: argument/output/
  temp/alias/generated-code bytes — the numbers the allocator will
  honor, known before the first run). Subsumes
  ``monitor.trace.memory_analysis`` (now a thin re-export shim, the
  pyprof precedent).
- :func:`analytic_high_water` — a deviceless liveness walk over the
  jaxpr (``make_jaxpr`` — nothing executes) charging **peak live
  bytes** to the innermost ``apx:`` profile scope, so "which module
  owns the peak" is answerable on a CPU CI host. Semantics are
  hand-computable (asserted by ``tests/test_memory.py``):

  * the top jaxpr's inputs and consts are resident for the whole
    program (the undonated-call convention — the caller owns the
    buffers until the call returns);
  * an intermediate is live from the equation that defines it through
    its last use; program outputs are live through the end;
  * at each equation the charge is ``resident + live intermediates +
    this equation's outputs``;
  * sub-jaxprs (pjit/scan/cond/while/custom-vjp — duck-typed, the
    ``attribution.analytic_profile`` recursion pattern) add their internal
    intermediates ON TOP of the live set at the call site. Unlike
    FLOPs, a scan's peak does NOT multiply by trip count — iterations
    reuse the body's buffers, and the stacked outputs are already
    counted at full size on the outer equation (XLA allocates ``ys``
    up front). ``while`` flags the result ``estimated`` (dynamic trip
    counts; the per-iteration envelope is still the right bound).

- :class:`MemorySampler` — the live HBM timeline: a host thread
  polling ``device.memory_stats()`` on an interval into
  ``memory/hbm_bytes_in_use`` gauges and a streaming
  :class:`~apex_tpu.monitor.spans.LogHistogram`. Platforms whose
  backend returns ``None`` (CPU hosts) degrade to a nominal row — real
  ``jax.live_arrays()`` resident bytes against the
  ``attribution.DEVICE_PEAKS`` table limit (its nominal cpu row: the whole
  pipeline is exercisable on CI, and the row is stamped nominal). The
  sampler
  installs the ``jax.monitoring`` compile listeners, so retrace storms
  land on the same recorder timeline as the byte samples.
- :func:`resident_bytes` — device-local resident buffer bytes of a
  pytree (or of every live array): the measurement behind the ZeRO
  residency ratios.
- :func:`zero_memory_report` / :func:`serve_pool_report` — the ZeRO
  dense/zero2/zero3 residency split and the serve KV-pool occupancy,
  derived THROUGH this layer (``python -m apex_tpu.monitor memory``
  prints them).
- :func:`vmem_calibration` — closes the tuner loop: compares
  ``tune.vmem.vmem_estimate`` envelope predictions against compiled
  temp bytes for resolved kernel configs, emitting
  ``tune/vmem_mispredict`` events when the envelope under-predicts.

Purity contract (the monitor rule): nothing here inserts operations or
forces a retrace. The analytic walk traces abstractly; the sampler is a
host thread reading ``memory_stats()``; gauges ride
``jax.debug``-free host paths. A step traced with a recorder attached
and a sampler running is byte-identical to one traced detached
(asserted by ``tests/test_memory.py``). Recorders resolve at fire
time: detaching stops the telemetry even while a sampler thread runs.

Health: :class:`~apex_tpu.monitor.health.Watchdog` watches the gauges
this module records — ``hbm_high_water`` (usage at a fraction of the
limit, hysteresis re-arm), ``memory_leak`` (positive slope over a
sliding window of step-record byte gauges) and ``recompile_storm``
(compile events landing in step after step) all fire BEFORE the OOM,
riding the ordinary step-record path.

Rendered by ``python -m apex_tpu.monitor memory`` and embedded in
``report.aggregate()["memory"]`` when rows are recorded
(``record=True``).
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

from apex_tpu.monitor import _state
from apex_tpu.monitor.attribution import (_aval_bytes, _scope_of,
                                          _sub_jaxprs, device_peaks)
from apex_tpu.monitor.profile import UNSCOPED

#: The compiled-breakdown fields read off ``Compiled.memory_analysis()``
#: (one place, shared with the trace shim).
_MA_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")


def hbm_limit_for(device_kind: Optional[str] = None) -> Optional[int]:
    """Per-chip HBM bytes (``attribution.device_peaks``), ``None`` when the
    kind is unknown: utilization is then not computable."""
    row = device_peaks(device_kind)
    return row[1] if row else None


# ---------------------------------------------------------------------------
# resident bytes: the device-local measurement behind every residency claim
# ---------------------------------------------------------------------------

def _shard_bytes_by_device(leaves) -> dict:
    """One pass over ``leaves`` → ``{device: resident bytes}`` (the
    ONE shard-accumulation loop behind :func:`resident_bytes` and the
    snapshot's nominal rows)."""
    out: dict = {}
    for leaf in leaves:
        for sh in getattr(leaf, "addressable_shards", []):
            out[sh.device] = out.get(sh.device, 0) + sh.data.nbytes
    return out


def resident_bytes(tree=None, device=None) -> int:
    """Device-local resident buffer bytes.

    ``tree``: a pytree of jax arrays (default: every live array in the
    process, ``jax.live_arrays()``). ``device``: count only the shards
    resident on that device (default: the first local device —
    replicated trees count one full copy, sharded trees ``1/world``,
    exactly the per-chip residency the ZeRO ratios are about)."""
    import jax
    leaves = (jax.live_arrays() if tree is None
              else jax.tree_util.tree_leaves(tree))
    if device is None:
        try:
            device = jax.local_devices()[0]
        except Exception:
            return 0
    return _shard_bytes_by_device(leaves).get(device, 0)


# ---------------------------------------------------------------------------
# compiled-footprint attribution (Compiled.memory_analysis)
# ---------------------------------------------------------------------------

def compiled_memory_of(compiled, *, label: str = "program",
                       record: bool = False) -> dict:
    """Memory breakdown of an already-compiled executable. Returns the
    :data:`_MA_FIELDS` present plus ``total_bytes`` (argument + output
    + temp + generated code, minus aliased bytes — the allocator-
    footprint envelope); ``{}`` when the backend reports nothing."""
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    out = {}
    for k in _MA_FIELDS:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        total = (out.get("argument_size_in_bytes", 0)
                 + out.get("output_size_in_bytes", 0)
                 + out.get("temp_size_in_bytes", 0)
                 + out.get("generated_code_size_in_bytes", 0)
                 - out.get("alias_size_in_bytes", 0))
        out["total_bytes"] = max(total, 0)
    if record and out:
        rec = _state.recorder
        if rec is not None:
            rec.emit("memory", label, out["total_bytes"],
                     **{k: v for k, v in out.items() if k != "total_bytes"})
    return out


def compiled_memory_profile(fn: Callable, *args, label: str = "program",
                            record: bool = False, **kwargs) -> dict:
    """Compile ``fn(*args, **kwargs)`` and return XLA's static memory
    breakdown — the numbers the allocator will honor, known before the
    first run. ``record=True`` lands one typed ``memory`` event on the
    attached recorder (→ ``report.aggregate()["memory"]["programs"]``).
    """
    import jax
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    return compiled_memory_of(compiled, label=label, record=record)


# ---------------------------------------------------------------------------
# analytic high water: liveness walk, charged to the innermost scope
# ---------------------------------------------------------------------------

def _is_literal(v) -> bool:
    return hasattr(v, "val")          # jax.core.Literal; Vars have no .val


def _new_row() -> dict:
    return {"peak_live_bytes": 0, "eqns": 0}


def _live_walk(jaxpr, prefix: str, base: int, rows: dict, meta: dict,
               count_io: bool) -> int:
    """Linear-scan liveness over one jaxpr. ``base`` is the absolute
    live total outside this jaxpr (the call site's live set, operands
    and outputs included — recursive calls therefore count only their
    INTERNAL intermediates, ``count_io=False``). Returns the absolute
    peak observed inside."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)    # unwrap ClosedJaxpr
    n = len(jaxpr.eqns)
    last: dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last[v] = i
    for v in jaxpr.outvars:
        if not _is_literal(v):
            last[v] = n                 # program outputs live to the end
    arg_vars = set(jaxpr.invars) | set(jaxpr.constvars)
    resident = 0
    if count_io:
        resident = sum(_aval_bytes(v) for v in arg_vars)
    live: dict = {}
    peak = base + resident
    for i, eqn in enumerate(jaxpr.eqns):
        stack = str(getattr(eqn.source_info, "name_stack", ""))
        full = f"{prefix}/{stack}" if prefix else stack
        for v in eqn.outvars:
            if v not in arg_vars:
                live[v] = _aval_bytes(v)
        here = base + resident + sum(live.values())
        cur = here
        subs = _sub_jaxprs(eqn)
        if subs:
            if eqn.primitive.name == "while":
                meta["estimated"] = True
            for sub in subs:
                # every sibling stacks on the CALL SITE's live set, not
                # on the previous sibling's peak: cond branches (and
                # while's cond/body) are mutually exclusive, so the
                # equation's contribution is their max, never their sum
                inner = _live_walk(sub, full, here, rows, meta,
                                   count_io=False)
                cur = max(cur, inner)
        scope = _scope_of(full)
        row = rows.setdefault(scope, _new_row())
        row["eqns"] += 1
        if cur > row["peak_live_bytes"]:
            row["peak_live_bytes"] = cur
        if cur > meta["peak"]:
            meta["peak"] = cur
            meta["peak_scope"] = scope
        if cur > peak:
            peak = cur
        # free intermediates at their last use (outputs have last == n)
        for v in eqn.invars:
            if not _is_literal(v) and v not in arg_vars \
                    and last.get(v, -1) <= i:
                live.pop(v, None)
        for v in eqn.outvars:
            if v not in arg_vars and last.get(v, -1) <= i:
                live.pop(v, None)       # never read again (DropVar/dead)
    return peak


def attribute_high_water(closed_jaxpr) -> dict:
    """Analytic peak-live-bytes walk over a ``ClosedJaxpr`` (or
    anything with ``.jaxpr.eqns``/``.eqns``): per-scope peaks, the
    global peak and which ``apx:`` scope owns it."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    rows: dict = {}
    meta = {"estimated": False, "peak": 0, "peak_scope": UNSCOPED}
    peak = _live_walk(jaxpr, "", 0, rows, meta, count_io=True)
    args_bytes = sum(_aval_bytes(v) for v in
                     tuple(jaxpr.invars) + tuple(jaxpr.constvars))
    out_bytes = sum(_aval_bytes(v) for v in jaxpr.outvars
                    if not _is_literal(v))
    return {"peak_live_bytes": int(peak),
            "peak_scope": meta["peak_scope"],
            "scopes": rows,
            "argument_bytes": int(args_bytes),
            "output_bytes": int(out_bytes),
            "estimated": meta["estimated"]}


def _emit_scope_rows(rec, scopes: dict):
    """The ONE per-scope ``memory_scope`` emission (shared by
    :func:`analytic_high_water` and :func:`memory_profile`)."""
    for name, row in sorted(scopes.items()):
        rec.emit("memory_scope", name, row["peak_live_bytes"],
                 eqns=row["eqns"])


def analytic_high_water(fn: Callable, *args, record: bool = False,
                        label: str = "program", **kwargs) -> dict:
    """Trace ``fn(*args, **kwargs)`` abstractly (``jax.make_jaxpr`` —
    nothing executes, deviceless) and attribute its peak live bytes per
    profile scope. ``record=True`` emits one ``memory_scope`` event per
    scope plus the program's ``memory`` row with the analytic fields."""
    import functools
    import jax
    closed = jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)
    hw = attribute_high_water(closed)
    if record:
        rec = _state.recorder
        if rec is not None:
            _emit_scope_rows(rec, hw["scopes"])
            rec.emit("memory", label, hw["peak_live_bytes"],
                     analytic_peak_bytes=hw["peak_live_bytes"],
                     peak_scope=hw["peak_scope"],
                     argument_bytes=hw["argument_bytes"],
                     output_bytes=hw["output_bytes"],
                     estimated=hw["estimated"])
    return hw


def memory_profile(fn: Callable, *args, label: str = "program",
                   record: bool = False, **kwargs) -> dict:
    """The combined per-program view: compiled breakdown + analytic
    high-water walk. ``record=True`` emits ONE ``memory`` event
    carrying both (plus the per-scope ``memory_scope`` rows), so the
    table rides JSONL dumps and ``report.aggregate()["memory"]``."""
    hw = analytic_high_water(fn, *args, **kwargs)
    compiled = compiled_memory_profile(fn, *args, **kwargs)
    if record:
        rec = _state.recorder
        if rec is not None:
            _emit_scope_rows(rec, hw["scopes"])
            rec.emit(
                "memory", label,
                compiled.get("total_bytes", hw["peak_live_bytes"]),
                analytic_peak_bytes=hw["peak_live_bytes"],
                peak_scope=hw["peak_scope"],
                estimated=hw["estimated"],
                **{k: v for k, v in compiled.items()
                   if k != "total_bytes"})
    return {"label": label, "compiled": compiled, "analytic": hw}


# ---------------------------------------------------------------------------
# live HBM timeline
# ---------------------------------------------------------------------------

def device_memory_snapshot(devices=None, recorder=None) -> list[dict]:
    """Per-device live memory stats. Platforms that report
    ``memory_stats()`` get the real row (``bytes_in_use``,
    ``peak_bytes_in_use``, ``bytes_limit`` when present); platforms
    that return ``None`` (CPU hosts) degrade to a NOMINAL row —
    ``jax.live_arrays()`` resident bytes against the
    ``attribution.DEVICE_PEAKS`` table limit, stamped ``"nominal": True``.
    Recorded as ``memory/...`` gauges on the
    attached (or passed) recorder; the headline
    ``memory/hbm_bytes_in_use`` gauge is the max across devices."""
    import jax
    devices = devices if devices is not None else jax.local_devices()
    out = []
    rec = recorder if recorder is not None else _state.recorder
    worst = None
    live_by_dev = None       # one live-array pass shared by all rows
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        row = {"device": str(d), "platform": d.platform}
        if stats:
            row.update(stats)
            limit = stats.get("bytes_limit") or \
                hbm_limit_for(getattr(d, "device_kind", None))
        else:
            row["nominal"] = True
            limit = hbm_limit_for(getattr(d, "device_kind", None))
        if "bytes_in_use" not in row:
            # stats-less backend (or stats without the headline key):
            # the nominal bytes_in_use is the REAL live-array residency
            if live_by_dev is None:
                live_by_dev = _shard_bytes_by_device(jax.live_arrays())
            row["bytes_in_use"] = live_by_dev.get(d, 0)
        if limit:
            row["limit_bytes"] = int(limit)
            row["utilization"] = row["bytes_in_use"] / float(limit)
        out.append(row)
        if worst is None or row["bytes_in_use"] > worst["bytes_in_use"]:
            worst = row
        if rec is not None:
            for k in ("bytes_in_use", "peak_bytes_in_use"):
                if k in row:
                    rec.gauge(f"memory/{d.id}/{k}", row[k])
    if rec is not None and worst is not None:
        rec.gauge("memory/hbm_bytes_in_use", worst["bytes_in_use"])
        if "limit_bytes" in worst:
            rec.gauge("memory/hbm_limit_bytes", worst["limit_bytes"])
            rec.gauge("memory/hbm_utilization",
                      round(worst["utilization"], 6))
    return out


class MemorySampler:
    """Host-side HBM timeline: polls :func:`device_memory_snapshot` on
    an interval thread, landing ``memory/hbm_bytes_in_use`` (+ limit/
    utilization and per-device) gauges and one streaming
    :class:`~apex_tpu.monitor.spans.LogHistogram` observation per
    sample on whichever recorder is attached AT SAMPLE TIME (detach
    stops the telemetry mid-flight; the thread itself is inert).

    Also installs the ``jax.monitoring`` compile listeners
    (:func:`~apex_tpu.monitor.trace.install_compile_logging`) so
    backend-compile events and the byte samples share one timeline —
    a retrace storm shows up as compile timers interleaved with the
    HBM gauges it inflates.

    Usage::

        with monitor.attached(rec), monitor.MemorySampler(0.2):
            train()
        rec.aggregate()["memory"]["timeline"]   # downsampled trajectory

    Purity: the sampler is a plain thread doing host reads — it
    inserts no ops and forces no retrace; traced programs are
    byte-identical with or without it (asserted by tests).
    """

    def __init__(self, interval_s: float = 0.5, *, devices=None,
                 recorder=None,
                 histogram: Optional[str] = "memory/hbm_mib_in_use"):
        self.interval_s = float(interval_s)
        self.devices = devices
        self.recorder = recorder          # None: resolve at sample time
        self.histogram = histogram
        self.samples = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def sample_once(self) -> list[dict]:
        """One sample (also usable without the thread)."""
        rec = self.recorder if self.recorder is not None \
            else _state.recorder
        rows = device_memory_snapshot(self.devices, recorder=rec)
        if rec is not None and rows and self.histogram:
            worst = max(r.get("bytes_in_use", 0) for r in rows)
            # histogram in MiB (the unit is in the NAME: the gauge and
            # the histogram must be distinct Prometheus families — one
            # TYPE line per name — and the LogHistogram default range
            # suits MiB magnitudes, not raw bytes)
            rec.observe(self.histogram, worst / float(1 << 20))
        self.samples += 1
        return rows

    def _run(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.sample_once()
            except Exception:
                pass                  # telemetry must never kill the run

    def start(self) -> "MemorySampler":
        if self._thread is not None:
            return self
        try:
            from apex_tpu.monitor import trace as _trace
            _trace.install_compile_logging()
        except Exception:
            pass
        try:
            self.sample_once()        # one sample lands immediately
        except Exception:
            pass                      # telemetry must never kill the run
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="apex-memory-sampler", daemon=True)
        self._thread.start()
        return self

    def stop(self):
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout=5.0)
        self._thread = None
        try:
            self.sample_once()        # closing sample
        except Exception:
            pass

    def __enter__(self) -> "MemorySampler":
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False


# ---------------------------------------------------------------------------
# the capacity claims, derived through this layer
# ---------------------------------------------------------------------------

def zero_memory_report(world: Optional[int] = None, *, hidden: int = 128,
                       batch: int = 16, record: bool = False) -> dict:
    """The ZeRO residency split, measured through this layer: dense DDP
    vs ZeRO-2 (``DistributedFusedAdam``) vs ZeRO-3
    (``ZeroOptimizer(shard_params=True)``) at a matched tiny config on
    the host data mesh — per-chip resident param+optimizer bytes
    (:func:`resident_bytes` on device 0) and the compiled step
    footprint (:func:`compiled_memory_of`) per tier, plus the
    dense/ZeRO-3 shrink ratio (~``world``x within padding +
    replicated-bias slack, the PR 6 claim). Runs on host CPU devices by
    design: the residency split is backend-independent."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu._compat import shard_map
    from apex_tpu import zero
    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import allreduce_gradients

    try:
        devs = jax.devices("cpu")
    except RuntimeError:
        devs = jax.devices()
    if world is None:
        world = max(w for w in (8, 4, 2, 1) if w <= len(devs))
    devs = devs[:world]
    mesh = Mesh(np.array(devs), ("data",))
    h, b = int(hidden), int(batch)
    rng = np.random.RandomState(7)
    params = {"w1": jnp.asarray(rng.randn(h, h) * 0.2, jnp.float32),
              "b1": jnp.asarray(rng.randn(h) * 0.1, jnp.float32),
              "w2": jnp.asarray(rng.randn(h, h) * 0.2, jnp.float32)}
    x = jnp.asarray(rng.randn(b * world, h), jnp.float32)
    y = jnp.asarray(rng.randn(b * world, h), jnp.float32)
    hyper = dict(lr=1e-2, weight_decay=0.01)

    def loss_fn(p, xs, ys):
        return jnp.mean(((jnp.tanh(xs @ p["w1"] + p["b1"])) @ p["w2"]
                         - ys) ** 2)

    decisions = jax.tree.map(
        lambda d: P("data") if (d and world > 1) else P(),
        zero.match_zero_rules(None, params))
    rep = jax.tree.map(lambda _: P(), params)
    zm3 = zero.ZeroShardedModel(None)

    def build(which):
        if which == "dense":
            opt = FusedAdam(params, master_weights=True, **hyper)

            def init(p):
                return p, opt.init(p)

            def step(p, st, xs, ys):
                g = jax.grad(loss_fn)(p, xs, ys)
                g = allreduce_gradients(g, "data")
                return opt.apply(st, p, g)

            return init, step, (rep, P())
        if which == "zero2":
            opt = DistributedFusedAdam(**hyper)

            def init(p):
                return p, opt.init(p)

            def step(p, st, xs, ys):
                g = jax.grad(loss_fn)(p, xs, ys)
                return opt.apply(st, p, g)

            sspec = zero.ShardedAdamState(
                P(), *((P("data") if world > 1 else P(),) * 3))
            return init, step, (rep, sspec)
        opt = zero.ZeroOptimizer(shard_params=True, **hyper)

        def init(p):
            shards = zm3.shard(p)
            return shards, opt.init(shards, zm3.spec)

        def step(s, st, xs, ys):
            g = jax.grad(lambda s: loss_fn(zm3.materialize(s), xs, ys))(s)
            return opt.apply(st, s, g, spec=zm3.spec)

        sspec = zero.Zero3State(P(), decisions, decisions, decisions)
        return init, step, (decisions, sspec)

    out: dict = {
        "world_size": world,
        "model_param_bytes": sum(int(v.size) * 4
                                 for v in jax.tree.leaves(params)),
        "per_chip_bytes": {}, "compiled": {},
    }
    for which in ("dense", "zero2", "zero3"):
        init, step, state_specs = build(which)
        jinit = jax.jit(shard_map(init, mesh=mesh, in_specs=(P(),),
                                  out_specs=state_specs, check_vma=False))
        p_or_s, st = jinit(params)
        out["per_chip_bytes"][which] = resident_bytes((p_or_s, st),
                                                      device=devs[0])
        compiled = jax.jit(shard_map(
            step, mesh=mesh,
            in_specs=(*state_specs, P("data"), P("data")),
            out_specs=state_specs,
            check_vma=False)).lower(p_or_s, st, x, y).compile()
        cm = compiled_memory_of(compiled, label=f"zero/{which}",
                                record=record)
        if cm:
            out["compiled"][which] = cm
    dense_b = out["per_chip_bytes"]["dense"]
    z3_b = out["per_chip_bytes"]["zero3"]
    out["dense_over_zero3_ratio"] = round(dense_b / max(z3_b, 1), 3)
    if record:
        rec = _state.recorder
        if rec is not None:
            for which, nbytes in out["per_chip_bytes"].items():
                rec.gauge(f"memory/zero/{which}_bytes_per_chip", nbytes)
            rec.gauge("memory/zero/dense_over_zero3_ratio",
                      out["dense_over_zero3_ratio"])
    return out


def serve_pool_report(*, num_layers: int = 12, kv_heads: int = 16,
                      head_dim: int = 64, num_pages: int = 256,
                      page_size: int = 128, seq_len: int = 1024,
                      pages_in_use: Optional[int] = None,
                      record: bool = False) -> dict:
    """Serve KV-pool occupancy through the cache's own byte accounting
    (``CacheConfig`` — the accounting PR 11's capacity claims come
    from): pool bytes, occupancy at ``pages_in_use`` (default: 3/4 of
    the usable pool), and the fp8-vs-bf16 concurrent-sequence capacity
    at the same pool budget."""
    import jax.numpy as jnp
    from apex_tpu.serve.cache import CacheConfig

    common = dict(num_layers=num_layers, kv_heads=kv_heads,
                  head_dim=head_dim, num_pages=num_pages,
                  page_size=page_size)
    bf16 = CacheConfig(dtype=jnp.bfloat16, **common)
    fp8 = CacheConfig(fp8=True, **common)
    usable = bf16.usable_pages
    if pages_in_use is None:
        pages_in_use = (3 * usable) // 4
    budget = bf16.pool_bytes()
    occupancy = pages_in_use / float(usable)
    out = {
        "pool_bytes": budget,
        "bytes_per_page": bf16.bytes_per_page(),
        "fp8_bytes_per_page": fp8.bytes_per_page(),
        "pages_in_use": int(pages_in_use),
        "usable_pages": usable,
        "occupancy": round(occupancy, 4),
        "bytes_in_use": bf16.occupancy_bytes(pages_in_use),
        "bf16_seqs_at_budget": bf16.max_concurrent_seqs(budget, seq_len),
        "fp8_seqs_at_budget": fp8.max_concurrent_seqs(budget, seq_len),
    }
    out["fp8_capacity_ratio"] = round(
        out["fp8_seqs_at_budget"] / max(out["bf16_seqs_at_budget"], 1), 3)
    if record:
        rec = _state.recorder
        if rec is not None:
            rec.gauge("memory/serve_pool_bytes", out["pool_bytes"])
            rec.gauge("memory/serve_pool_bytes_in_use",
                      out["bytes_in_use"])
            rec.gauge("memory/serve_pool_occupancy", out["occupancy"])
    return out


def serve_weight_report(cfg, params, *, record: bool = False) -> dict:
    """Serve weight-streaming accounting: the HBM bytes of block linear
    weights (kernels + any fp8 scales) ONE decode step streams, against
    the bf16 baseline of the same leaves — the byte accounting the
    bench's fp8-weight streamed-bytes assertion reads
    (``serve.model.weight_stream_bytes``; same rule the engine serves
    with, so telemetry and capacity claims cannot drift apart). A bf16
    tree reports ratio 1.0; an e4m3-quantized tree
    (``serve.quantize_gpt_weights``) ~0.5."""
    from apex_tpu.serve import model as serve_model

    streamed = serve_model.weight_stream_bytes(cfg, params)
    elems = 0
    for i in range(cfg.num_layers):
        blk = params[f"block_{i}"]
        for group, name in serve_model._FP8_WEIGHT_LINEARS:
            elems += int(blk[group][name]["kernel"].size)
    bf16 = 2 * elems
    out = {
        "weight_bytes_per_step": streamed,
        "bf16_weight_bytes_per_step": bf16,
        "weight_stream_ratio": round(streamed / max(bf16, 1), 4),
    }
    if record:
        rec = _state.recorder
        if rec is not None:
            rec.gauge("memory/serve_weight_bytes",
                      out["weight_bytes_per_step"])
            rec.gauge("memory/serve_weight_bytes_bf16",
                      out["bf16_weight_bytes_per_step"])
            rec.gauge("memory/serve_weight_ratio",
                      out["weight_stream_ratio"])
    return out


# ---------------------------------------------------------------------------
# tuner-loop calibration: envelope predictions vs compiled temp bytes
# ---------------------------------------------------------------------------

def _calibration_call(kernel: str, shape: dict, dtype: str, flags: dict,
                      config: dict, interpret):
    """(fn, args, vmem_kwargs) for one kernel at one block config —
    the compile target whose ``temp_size_in_bytes`` grounds the
    envelope. The three r13 kernels: cheap to compile at tiny shapes
    on any backend (interpret mode off-TPU)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(0)
    if kernel == "fused_layer_norm":
        from apex_tpu.ops.layer_norm import fused_layer_norm_affine
        n, h = shape["n"], shape["h"]
        dt = jnp.dtype(dtype)
        x = jnp.asarray(rng.randn(n, h) * 0.5, dt)
        w = jnp.ones((h,), jnp.float32)
        b = jnp.zeros((h,), jnp.float32)

        def fn(x, w, b):
            return fused_layer_norm_affine(
                x, w, b, (h,), block_r=config["block_r"],
                interpret=interpret, out_dtype=dt)

        return fn, (x, w, b), dict(block_r=config["block_r"], h=h,
                                   itemsize=dt.itemsize)
    if kernel == "xentropy":
        from apex_tpu.ops.fused_ce import \
            softmax_cross_entropy_with_smoothing
        n, v = shape["n"], shape["v"]
        dt = jnp.dtype(dtype)
        logits = jnp.asarray(rng.randn(n, v) * 0.1, dt)
        labels = jnp.asarray(rng.randint(0, v, (n,)), jnp.int32)

        def fn(logits):
            return softmax_cross_entropy_with_smoothing(
                logits, labels, 0.0, block_t=config["block_t"],
                block_v=config["block_v"], interpret=interpret)

        return fn, (logits,), dict(block_t=config["block_t"],
                                   block_v=config["block_v"],
                                   itemsize=dt.itemsize)
    if kernel == "multi_tensor_update":
        from apex_tpu.zero.fused_update import fused_shard_update
        n = shape["n"]
        p = jnp.asarray(rng.randn(n) * 0.05, jnp.float32)
        g = jnp.asarray(rng.randn(n) * 0.01, jnp.float32)
        m = jnp.zeros((n,), jnp.float32)
        v = jnp.zeros((n,), jnp.float32)
        step = jnp.asarray(7, jnp.int32)

        def fn(p, g, m, v):
            return fused_shard_update(
                p, g, m, v, step, kind="adam", lr=1e-3,
                betas=(0.9, 0.999), eps=1e-8, weight_decay=0.0,
                adam_w_mode=True, bias_correction=True,
                block_n=config["block_n"], interpret=interpret)

        return fn, (p, g, m, v), dict(block_n=config["block_n"])
    raise ValueError(f"vmem_calibration supports "
                     f"fused_layer_norm/xentropy/multi_tensor_update, "
                     f"got {kernel!r}")


#: tiny default calibration shapes (compile in well under a second on a
#: CPU host in interpret mode — the CI-sized twin of
#: ``tune.kernels.DEFAULT_SHAPES``)
CALIBRATION_SHAPES = {
    "fused_layer_norm": dict(n=256, h=128, dtype="bfloat16"),
    "xentropy": dict(n=64, v=256, dtype="bfloat16"),
    "multi_tensor_update": dict(n=16384, dtype="float32"),
}


def vmem_calibration(kernels=None, *, shapes: Optional[dict] = None,
                     interpret: Optional[bool] = None,
                     record: bool = False) -> dict:
    """Close the tuner loop: for each kernel, resolve its block config
    (tuned cache entry when one exists — ``tune.runtime.resolve`` —
    else the first legal candidate of the pruned config space), compile
    the kernel call, and compare the ``tune.vmem.vmem_estimate``
    envelope prediction against the compiled ``temp_size_in_bytes``.

    A **mispredict** is the dangerous direction: measured temp bytes
    exceeding the envelope that the sweep pruner trusted as an upper
    bound. Each mispredict bumps the ``tune/vmem_mispredict`` counter
    and (``record=True``) lands one typed ``vmem_calibration`` event
    per kernel — the envelope model's first measured feedback.

    Off-TPU the kernels compile in interpret mode, where XLA's temp
    accounting covers the interpreted program rather than Mosaic's
    VMEM allocator — those rounds exercise the pipeline; the verdicts
    that matter come from hardware rounds (units are platform-stamped
    by the bench accordingly)."""
    from apex_tpu.tune import runtime, space, vmem
    from apex_tpu.tune.cache import cache_key

    kernels = tuple(kernels or CALIBRATION_SHAPES)
    rows = []
    mispredicts = 0
    rec = _state.recorder
    for kernel in kernels:
        shape = dict((shapes or {}).get(kernel)
                     or CALIBRATION_SHAPES[kernel])
        dtype = shape.pop("dtype")
        flags: dict = {}
        cfg = runtime.resolve(kernel, shape, dtype, flags,
                              policy="cache")
        source = "tuned" if cfg is not None else "heuristic"
        if cfg is None:
            cands = space.config_space(kernel, shape, flags)
            if not cands:
                continue
            cfg = cands[0]
        fn, args, vkw = _calibration_call(kernel, shape, dtype, flags,
                                          cfg, interpret)
        import jax
        compiled = jax.jit(fn).lower(*args).compile()
        cm = compiled_memory_of(compiled)
        predicted = vmem.vmem_estimate(kernel, **vkw)
        measured = cm.get("temp_size_in_bytes")
        row = {"kernel": kernel, "config": dict(cfg), "source": source,
               "key": cache_key(kernel, shape, dtype, flags),
               "predicted_vmem_bytes": int(predicted),
               "budget_bytes": vmem.budget_for(kernel),
               "measured_temp_bytes": measured}
        row["mispredict"] = bool(measured is not None
                                 and measured > predicted)
        if row["mispredict"]:
            mispredicts += 1
            if rec is not None:
                rec.counter("tune/vmem_mispredict")
        if record and rec is not None:
            rec.emit("vmem_calibration", kernel,
                     row["predicted_vmem_bytes"], **{
                         k: v for k, v in row.items()
                         if k not in ("kernel", "predicted_vmem_bytes")})
        rows.append(row)
    return {"rows": rows, "checked": len(rows),
            "mispredicts": mispredicts}


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

def render_memory_profile(prof: dict, max_rows: int = 30) -> str:
    """Human render of a :func:`memory_profile` result: the compiled
    breakdown line + the per-scope analytic peak table."""
    from apex_tpu.monitor.report import _fmt_bytes
    lines = [f"# memory profile: {prof.get('label', 'program')}"]
    cm = prof.get("compiled") or {}
    if cm:
        lines.append(
            f"compiled: total {_fmt_bytes(cm.get('total_bytes'))} "
            f"(argument {_fmt_bytes(cm.get('argument_size_in_bytes'))}, "
            f"output {_fmt_bytes(cm.get('output_size_in_bytes'))}, "
            f"temp {_fmt_bytes(cm.get('temp_size_in_bytes'))}, "
            f"generated "
            f"{_fmt_bytes(cm.get('generated_code_size_in_bytes'))})")
    hw = prof.get("analytic") or {}
    if hw:
        est = " (estimated: dynamic while-loop trip counts)" \
            if hw.get("estimated") else ""
        lines.append(
            f"analytic high water: {_fmt_bytes(hw['peak_live_bytes'])} "
            f"at scope `{hw['peak_scope']}`{est}  "
            f"(args {_fmt_bytes(hw['argument_bytes'])}, "
            f"outputs {_fmt_bytes(hw['output_bytes'])})")
        scopes = hw.get("scopes") or {}
        if scopes:
            lines.append("")
            lines.append("| scope | peak live | eqns |\n|---|---|---|")
            order = sorted(scopes.items(),
                           key=lambda kv: -kv[1]["peak_live_bytes"])
            for name, row in order[:max_rows]:
                lines.append(f"| {name} "
                             f"| {_fmt_bytes(row['peak_live_bytes'])} "
                             f"| {row['eqns']} |")
            if len(order) > max_rows:
                lines.append(f"... ({len(order) - max_rows} more scopes)")
    return "\n".join(lines)
