"""Per-module cost attribution: the tools that read the profile scopes.

``monitor.profile`` (the emit side) tags regions with ``apx:`` scopes; this
module charges costs to them. It sits above the program: ``demo_train_step``
and ``measured_profile`` import ``amp``, ``models``, ``optimizers`` and the
recorder inside their functions, and ``apex_tpu.monitor`` loads it on first
use, so importing the program loads none of it.

- :func:`analytic_profile` — trace a function, walk the jaxpr (recursing
  through pjit/scan/cond/while/custom-vjp sub-jaxprs, multiplying scan
  trip counts) and charge each equation's FLOPs, HBM-proxy bytes and
  collective bytes to its innermost scope. The byte conventions match
  the trace-time collective table (``hooks.collective``: operand bytes),
  and Pallas kernel calls are counted per scope with their operand
  traffic (XLA's own ``cost_analysis`` counts custom calls as 0 FLOPs —
  same caveat as the bench MFU accounting).
- :func:`measured_profile` — sample per-scope WALL time: run the
  function eagerly (``jax.disable_jit``) with scope timing armed, so
  each scope's body executes op-by-op and its recorder timer measures
  real host time. A sampling mode for small shapes; device-accurate
  per-op numbers stay the job of XProf (``monitor.trace.trace`` +
  ``monitor.xprof``).

Rendered as a per-module table by ``python -m apex_tpu.monitor profile``
and embedded in ``report.aggregate()["profile"]`` when rows are
recorded into an attached recorder (``record=True``).
"""

from __future__ import annotations

import math
import re
import sys
from typing import Callable, Optional

from apex_tpu.monitor import _state
from apex_tpu.monitor.profile import UNSCOPED, measuring

# matches one profile-scope component anywhere in a name-stack string,
# including inside the jvp(...)/transpose(...) wrappers autodiff adds
# around forward and backward equations
_SCOPE_RE = re.compile(r"apx:([^/()]+)")


# ---------------------------------------------------------------------------
# analytic attribution: walk the jaxpr, charge the innermost scope
# ---------------------------------------------------------------------------

# primitives charged 1 FLOP per output element (the coarse unit-flop
# model: enough to rank matmuls vs elementwise chains, not a cycle
# count; transcendentals deliberately count 1 — their true cost is a
# VPU-implementation detail this model does not pretend to know)
_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "rem", "pow", "max", "min", "neg", "abs",
    "exp", "log", "log1p", "expm1", "tanh", "logistic", "erf", "erf_inv",
    "erfc", "rsqrt", "sqrt", "sin", "cos", "tan", "sign", "floor", "ceil",
    "round", "integer_pow", "select_n", "clamp", "nextafter", "add_any",
    "and", "or", "xor", "not", "atan2", "square", "cbrt",
})

# reductions: charged 1 FLOP per INPUT element
_REDUCTIONS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "cumsum", "cummax", "cummin",
    "cumprod", "cumlogsumexp", "reduce_precision",
})

# collectives: operand bytes charged to collective_bytes — the SAME
# convention as the trace-time table (hooks.collective is called with
# the input operand by the mappings/DDP/zero comm layers)
_COLLECTIVES = frozenset({
    "psum", "pmax", "pmin", "ppermute", "all_gather", "all_to_all",
    "psum_scatter", "reduce_scatter", "pbroadcast",
})


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        return int(math.prod(shape)) * dtype.itemsize
    except (TypeError, AttributeError):
        return 0


def _aval_elems(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    try:
        return int(math.prod(shape))
    except TypeError:
        return 0


def _dot_flops(eqn) -> int:
    """2*batch*M*N*K from the dot_general dimension numbers."""
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = math.prod(lhs[i] for i in lb) if lb else 1
    contract = math.prod(lhs[i] for i in lc) if lc else 1
    m = math.prod(d for i, d in enumerate(lhs) if i not in lc and i not in lb)
    n = math.prod(d for i, d in enumerate(rhs) if i not in rc and i not in rb)
    return 2 * batch * m * n * contract


def _conv_flops(eqn) -> int:
    """2 * out_elems * (kernel elems / out_features): the standard
    im2col count, feature-group-aware enough for the models here."""
    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    dn = eqn.params.get("dimension_numbers")
    out_features = rhs[dn.rhs_spec[0]] if dn is not None else rhs[-1]
    per_out = math.prod(rhs) // max(int(out_features), 1)
    return 2 * int(math.prod(out)) * per_out


def _eqn_flops(eqn) -> int:
    name = eqn.primitive.name
    if name == "dot_general":
        return _dot_flops(eqn)
    if name == "conv_general_dilated":
        return _conv_flops(eqn)
    if name in _ELEMENTWISE:
        return sum(_aval_elems(o) for o in eqn.outvars)
    if name in _REDUCTIONS:
        return sum(_aval_elems(i) for i in eqn.invars)
    return 0


def _sub_jaxprs(eqn):
    """Every jaxpr nested in an equation's params (pjit/call/scan/cond/
    while/custom-vjp/remat — duck-typed so new primitives keep working)."""
    out = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if hasattr(x, "eqns"):                      # raw Jaxpr
                out.append(x)
            elif hasattr(x, "jaxpr") and hasattr(
                    getattr(x, "jaxpr"), "eqns"):       # ClosedJaxpr
                out.append(x.jaxpr)
    return out


def _scope_of(stack_str: str) -> str:
    parts = _SCOPE_RE.findall(stack_str)
    if not parts:
        return UNSCOPED
    # collapse consecutive repeats: a sub-jaxpr's inner name stacks
    # repeat the enclosing scope the walker already carries in the
    # prefix (and autodiff re-wraps the same scope in jvp/transpose
    # layers), so "amp_grad/amp_grad/fc1" is the fc1 backward, not a
    # nested amp_grad — fwd and bwd merge into one per-module row
    out = [parts[0]]
    for p in parts[1:]:
        if p != out[-1]:
            out.append(p)
    return "/".join(out)


def _new_row() -> dict:
    return {"flops": 0, "hbm_bytes": 0, "collective_bytes": 0,
            "eqns": 0, "pallas_calls": 0}


def _walk(jaxpr, prefix: str, mult: int, rows: dict, meta: dict):
    for eqn in jaxpr.eqns:
        stack = getattr(eqn.source_info, "name_stack", "")
        full = f"{prefix}/{stack}" if prefix else str(stack)
        name = eqn.primitive.name
        subs = _sub_jaxprs(eqn)
        if subs:
            sub_mult = mult
            if name == "scan":
                sub_mult = mult * int(eqn.params.get("length", 1))
            elif name == "while":
                # trip count is dynamic: charge one iteration and flag
                # the result as a lower-bound estimate
                meta["estimated"] = True
            for sub in subs:
                _walk(sub, full, sub_mult, rows, meta)
            continue
        row = rows.setdefault(_scope_of(full), _new_row())
        row["eqns"] += 1
        row["flops"] += mult * _eqn_flops(eqn)
        nbytes = (sum(_aval_bytes(v) for v in eqn.invars)
                  + sum(_aval_bytes(v) for v in eqn.outvars))
        row["hbm_bytes"] += mult * nbytes
        if name in _COLLECTIVES:
            row["collective_bytes"] += mult * sum(
                _aval_bytes(v) for v in eqn.invars)
        if name == "pallas_call":
            row["pallas_calls"] += mult


def attribute_jaxpr(closed_jaxpr) -> dict:
    """Charge every equation of ``closed_jaxpr`` (a ``ClosedJaxpr`` or
    anything with ``.jaxpr.eqns``/``.eqns``) to its innermost enclosing
    profile scope. Returns the raw per-scope rows plus totals, the
    unscoped row, and the scoped-FLOPs coverage fraction."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    rows: dict[str, dict] = {}
    meta = {"estimated": False}
    _walk(jaxpr, "", 1, rows, meta)
    total = _new_row()
    for row in rows.values():
        for k in total:
            total[k] += row[k]
    unscoped = rows.get(UNSCOPED, _new_row())
    coverage = ((total["flops"] - unscoped["flops"]) / total["flops"]
                if total["flops"] else 1.0)
    return {"scopes": rows, "total": total, "unscoped": unscoped,
            "flops_scope_coverage": round(coverage, 6),
            "estimated": meta["estimated"]}


def analytic_profile(fn: Callable, *args, record: bool = False,
                     **kwargs) -> dict:
    """Trace ``fn(*args, **kwargs)`` and attribute its cost per scope.

    Traces with ``jax.make_jaxpr`` (abstract — nothing executes) and
    walks the result with :func:`attribute_jaxpr`. ``record=True`` also
    emits one typed ``profile`` event per scope into the attached
    recorder, so the table rides JSONL dumps and
    ``report.aggregate()["profile"]``.
    """
    import functools
    import jax
    closed = jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)
    prof = attribute_jaxpr(closed)
    if record:
        rec = _state.recorder
        if rec is not None:
            for name, row in sorted(prof["scopes"].items()):
                rec.emit("profile", name, row["flops"],
                         hbm_bytes=row["hbm_bytes"],
                         collective_bytes=row["collective_bytes"],
                         eqns=row["eqns"], pallas_calls=row["pallas_calls"])
            rec.emit("profile", "(total)", prof["total"]["flops"],
                     hbm_bytes=prof["total"]["hbm_bytes"],
                     collective_bytes=prof["total"]["collective_bytes"],
                     eqns=prof["total"]["eqns"],
                     pallas_calls=prof["total"]["pallas_calls"],
                     flops_scope_coverage=prof["flops_scope_coverage"])
    return prof


def measured_profile(fn: Callable, *args, repeats: int = 3,
                     recorder=None, **kwargs) -> dict:
    """Sample per-scope WALL time by running ``fn`` eagerly.

    Runs ``fn(*args)`` ``repeats`` times under ``jax.disable_jit()``
    with scope timing armed: every :func:`scope` body executes op-by-op
    and its host timer measures real elapsed time, landing as
    ``profile/<path>`` timer events in ``recorder`` (default: the
    attached one, else a private recorder). Returns
    ``{"scopes": {path: {n, total_s, mean_s}}, "repeats": ...}``.

    This is a *sampling* mode for small shapes (eager dispatch overhead
    rides along); use XProf for device-accurate per-op attribution.
    """
    import jax
    from apex_tpu import monitor
    from apex_tpu.monitor.recorder import Recorder

    rec = recorder or _state.recorder or Recorder(name="measured_profile")
    with monitor.attached(rec), measuring(), jax.disable_jit():
        for _ in range(max(1, int(repeats))):
            out = fn(*args, **kwargs)
            jax.block_until_ready(out)
    agg = rec.aggregate().get("timers", {})
    rows = {}
    for k, v in agg.items():
        if k.startswith("profile/"):
            rows[k[len("profile/"):]] = {
                "n": v["n"], "total_s": v["total_s"], "mean_s": v["mean_s"]}
    return {"scopes": rows, "repeats": int(repeats)}


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

def _fmt_count(v: float) -> str:
    for unit, div in (("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(v) >= div:
            return f"{v / div:.2f}{unit}"
    return f"{v:.0f}"


def render_profile(prof: dict, measured: Optional[dict] = None,
                   max_rows: int = 40) -> str:
    """Markdown per-module table from :func:`analytic_profile` output
    (optionally merged with a :func:`measured_profile` result)."""
    total = prof["total"]
    tf = total["flops"] or 1
    mrows = (measured or {}).get("scopes", {})
    hdr = ["scope", "flops", "%flops", "hbm bytes", "coll bytes", "eqns"]
    if mrows:
        hdr.append("wall ms (measured)")
    lines = ["| " + " | ".join(hdr) + " |", "|" + "---|" * len(hdr)]
    order = sorted(prof["scopes"].items(),
                   key=lambda kv: (-kv[1]["flops"], kv[0]))
    for name, row in order[:max_rows]:
        cells = [name, _fmt_count(row["flops"]),
                 f"{100.0 * row['flops'] / tf:.1f}%",
                 _fmt_count(row["hbm_bytes"]),
                 _fmt_count(row["collective_bytes"]), str(row["eqns"])]
        if mrows:
            m = mrows.get(name)
            cells.append(f"{1e3 * m['mean_s']:.3f}" if m else "")
        lines.append("| " + " | ".join(cells) + " |")
    if len(order) > max_rows:
        lines.append(f"... ({len(order) - max_rows} more scopes)")
    lines.append("")
    est = " (lower bound: dynamic while-loop trip counts)" \
        if prof.get("estimated") else ""
    lines.append(
        f"total: {_fmt_count(total['flops'])} flops, "
        f"{_fmt_count(total['hbm_bytes'])} hbm bytes, "
        f"{_fmt_count(total['collective_bytes'])} collective bytes; "
        f"scoped-flops coverage "
        f"{100.0 * prof['flops_scope_coverage']:.1f}%{est}")
    return "\n".join(lines)


def demo_train_step(model: str = "gpt", *, batch: int = 2, seq: int = 64,
                    hidden: int = 64, layers: int = 2, heads: int = 2,
                    vocab: int = 256, dtype: str = "float32",
                    attention: str = "fused_softmax",
                    fused_lm_head: bool = False):
    """The canonical amp train step the profile and memory CLIs
    attribute — ONE recipe, so both always measure the same program.
    Returns ``(step, args)`` with ``step(*args)``
    runnable and traceable. ``model`` is ``"gpt"`` (tiny Megatron-style
    GPT; ``fused_softmax``/unfused LM head by default so every matmul
    is visible to the analytic FLOP model) or ``"mlp"``. All heavy
    imports are deferred to the call."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from apex_tpu import amp
    from apex_tpu.amp import scaler as scaler_mod
    from apex_tpu.optimizers import FusedSGD

    jdtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    if model == "gpt":
        from apex_tpu.models import GPT, GPTConfig
        from apex_tpu.transformer import parallel_state as ps
        ps.destroy_model_parallel()
        cfg = GPTConfig(vocab_size=vocab, max_seq_len=seq,
                        hidden_size=hidden, num_layers=layers,
                        num_heads=heads, dtype=jdtype,
                        attention_impl=attention,
                        fused_lm_head=fused_lm_head)
        gpt = GPT(cfg)
        rng = np.random.RandomState(0)
        ids = jnp.asarray(rng.randint(0, vocab, (batch, seq)), jnp.int32)
        labels = jnp.asarray(np.roll(np.asarray(ids), -1, 1))
        params = gpt.init(jax.random.PRNGKey(0), ids)
        loss_fn = gpt.loss
        data = (ids, labels)
    elif model == "mlp":
        def loss_fn(p, x, y):
            h = jnp.tanh(x @ p["w1"])
            return jnp.mean((h @ p["w2"] - y) ** 2)

        params = {"w1": jnp.ones((hidden, 4 * hidden), jdtype) * 0.1,
                  "w2": jnp.ones((4 * hidden, hidden), jdtype) * 0.1}
        x = jnp.ones((batch, hidden), jdtype)
        data = (x, x)
    else:
        raise ValueError(f"model must be 'gpt' or 'mlp', got {model!r}")
    opt = FusedSGD(lr=0.01)
    opt_state = opt.init(params)
    sstate = scaler_mod.init_state(2.0 ** 8)
    step = amp.make_train_step(loss_fn, opt, donate=False)
    return step, (params, opt_state, sstate) + data


# ---------------------------------------------------------------------------
# MFU / goodput accounting
# ---------------------------------------------------------------------------

#: Per-chip peaks by ``device_kind`` substring: dense bf16 matmul
#: FLOP/s (the MFU convention) and HBM capacity in bytes. Sources:
#: published TPU specs (v2-v6e). The ``cpu`` row is NOMINAL, not a
#: hardware spec: it exists so the MFU pipeline (analytic FLOPs ÷ wall
#: ÷ peak) and the HBM-utilization pipeline (``memory.MemorySampler``
#: -> gauges -> watchdog ``hbm_high_water``) are exercisable on CI
#: hosts; whatever reads it is stamped nominal.
DEVICE_PEAKS = {
    "tpu v2": (45e12, 8 << 30),
    "tpu v3": (123e12, 16 << 30),
    "tpu v4": (275e12, 32 << 30),
    "tpu v5 lite": (197e12, 16 << 30),
    "tpu v5e": (197e12, 16 << 30),
    "tpu v5p": (459e12, 95 << 30),
    "tpu v6 lite": (918e12, 32 << 30),
    "tpu v6e": (918e12, 32 << 30),
    "tpu7": (2307e12, 192 << 30),
    "cpu": (5e10, 4 << 30),
}


def device_peaks(device_kind: Optional[str] = None) -> Optional[tuple]:
    """The :data:`DEVICE_PEAKS` row ``(FLOP/s, HBM bytes)`` for a
    ``device_kind`` string (default: the first jax device's), by
    normalized longest-substring match. ``None`` for unknown kinds —
    callers must treat that as "not computable", never substitute a
    guess."""
    if device_kind is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        try:
            device_kind = jax.devices()[0].device_kind
        except Exception:
            return None
    kind = str(device_kind).strip().lower()
    keys = [key for key in DEVICE_PEAKS if key in kind]
    return DEVICE_PEAKS[max(keys, key=len)] if keys else None


def peak_flops_for(device_kind: Optional[str] = None) -> Optional[float]:
    """Peak FLOP/s per chip (:func:`device_peaks`), ``None`` when the
    kind is unknown: MFU is then not computable."""
    row = device_peaks(device_kind)
    return row[0] if row else None


def mfu(flops_per_step: float, step_time_s: float, *,
        peak: Optional[float] = None,
        device_kind: Optional[str] = None,
        n_devices: int = 1) -> Optional[dict]:
    """Model FLOPs utilization: ``flops_per_step / step_time_s`` over
    ``n_devices * peak``. ``peak`` (FLOP/s per device) wins over the
    ``device_kind`` table lookup. Returns ``None`` when the peak is
    unknown or the wall time is degenerate, else a dict with
    ``mfu_pct``, ``achieved_flops_per_sec``, ``peak_flops_per_sec``
    and the resolved ``device_kind``."""
    if step_time_s is None or step_time_s <= 0 or not flops_per_step:
        return None
    if peak is None:
        peak = peak_flops_for(device_kind)
    if peak is None or peak <= 0:
        return None
    achieved = float(flops_per_step) / float(step_time_s)
    total_peak = float(peak) * max(1, int(n_devices))
    return {"mfu_pct": round(100.0 * achieved / total_peak, 4),
            "achieved_flops_per_sec": achieved,
            "peak_flops_per_sec": total_peak,
            "device_kind": device_kind}


def measured_mfu(fn: Callable, args: tuple, *, flops: Optional[float] = None,
                 peak: Optional[float] = None, repeats: int = 3,
                 record: bool = False) -> Optional[dict]:
    """MFU of one executed step: times ``fn(*args)`` (median of
    ``repeats`` after one warmup/compile call, ``block_until_ready``
    both sides) and divides the analytic FLOPs walk (computed here when
    ``flops`` is not passed) by wall x peak. ``record=True`` lands
    ``profile/mfu_pct`` + ``profile/step_time_ms`` gauges on the
    attached recorder — the training-side twin of the serve engine's
    ``serve/goodput_tokens_per_sec_chip`` gauge."""
    import statistics
    import time as _time

    import jax

    if flops is None:
        flops = analytic_profile(fn, *args)["total"]["flops"]
    jax.block_until_ready(fn(*args))            # compile + warm
    times = []
    for _ in range(max(1, int(repeats))):
        t0 = _time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(_time.perf_counter() - t0)
    wall = statistics.median(times)
    try:
        kind = jax.devices()[0].device_kind
    except Exception:
        kind = None
    row = mfu(flops, wall, peak=peak, device_kind=kind,
              n_devices=1)
    out = {"step_time_s": round(wall, 6), "flops": int(flops),
           "repeats": int(repeats), "device_kind": kind}
    if row is not None:
        out.update(row)
        out["device_kind"] = kind
    if record:
        rec = _state.recorder
        if rec is not None:
            rec.gauge("profile/step_time_ms", 1e3 * wall)
            if row is not None:
                rec.gauge("profile/mfu_pct", row["mfu_pct"])
                rec.gauge("profile/achieved_flops_per_sec",
                          row["achieved_flops_per_sec"])
    return out


def render_mfu(row: Optional[dict]) -> str:
    """One human line for a :func:`measured_mfu` result."""
    if not row:
        return "MFU: n/a (no timed execution)"
    base = (f"step {1e3 * row['step_time_s']:.3f} ms over "
            f"{row['repeats']} reps, "
            f"{_fmt_count(row['flops'])} analytic flops")
    if row.get("mfu_pct") is None:
        return (f"MFU: n/a — no peak-FLOPs entry for device_kind "
                f"{row.get('device_kind')!r} (pass --peak-tflops); "
                f"{base}")
    return (f"MFU: {row['mfu_pct']:.4g}% of "
            f"{row['peak_flops_per_sec'] / 1e12:.4g} TFLOP/s peak "
            f"({row.get('device_kind')}) — "
            f"{_fmt_count(row['achieved_flops_per_sec'])} flops/s "
            f"achieved; {base}")


def kernel_vmem_note(kernel: str, **kw) -> Optional[dict]:
    """VMEM envelope for a known Pallas kernel at a block config — the
    ``tune/vmem.py`` tile accounting, surfaced next to a profile row so
    an ops scope's on-chip working set sits beside its HBM traffic.
    Returns None for unknown kernels (never raises)."""
    try:
        from apex_tpu.tune import vmem
        return {"kernel": kernel,
                "vmem_bytes": vmem.vmem_estimate(kernel, **kw),
                "vmem_budget_bytes": vmem.budget_for(kernel)}
    except Exception:
        return None
