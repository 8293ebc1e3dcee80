"""Operations and bytes the mathematics of a Mellum-2-shaped model's own
kernels needs, from shapes (beside ``flops.py``, ``bytes.py`` and
``counts_deepseek.py``, which hold the other kernels'): attention over a
BAND (a sliding window, or the causal triangle) with key/value heads shared
by a group, and the expert layer's grouped matmuls forward and backward.
Multiply-adds count 2; bf16 unless said; recomputation is never counted.
And what the traced steps' expert layers were handed, from the program's
own counters (``models/mellum.py:record_step``)."""

from __future__ import annotations

SLIDING = "sliding_attention"


# -- attention over a band -------------------------------------------------------

def band_pairs(seq: int, window=None) -> float:
    """(query, key) pairs a causal call of ``seq`` tokens has to score:
    query ``i`` sees ``min(i + 1, window)`` keys. ``window=None``: the
    causal triangle, ``seq (seq + 1) / 2``."""
    w = seq if window is None else min(int(window), seq)
    return w * (w + 1) / 2.0 + (seq - w) * float(w)


def attention_fwd_flops(batch, heads, seq, head_dim, window=None) -> float:
    """QK^T and PV over the band: 2 matmuls of 2 * pairs * d a head."""
    return 4.0 * batch * heads * band_pairs(seq, window) * head_dim


def attention_bwd_flops(batch, heads, seq, head_dim, window=None) -> float:
    """dV, dP, dQ, dK over the band; the kernels also recompute QK^T (twice:
    the two-kernel form), which is not counted."""
    return 2.0 * attention_fwd_flops(batch, heads, seq, head_dim, window)


def attention_fwd_bytes(batch, heads, kv_heads, seq, head_dim,
                        itemsize: int = 2) -> float:
    """Read q and, ONCE a key/value head, k and v; write o and the float32
    log-sum-exp row."""
    row = batch * seq * head_dim * itemsize
    return (2.0 * heads + 2.0 * kv_heads) * row + batch * heads * seq * 4


def attention_bwd_bytes(batch, heads, kv_heads, seq, head_dim,
                        itemsize: int = 2) -> float:
    """Read q, o, do (a query head) and k, v (a key/value head) and the
    log-sum-exp; write dq (a query head) and dk, dv (a key/value head)."""
    row = batch * seq * head_dim * itemsize
    return (4.0 * heads + 4.0 * kv_heads) * row + batch * heads * seq * 4


def attention_roofline(run, kind: str):
    """Percent of their roofline that the flash kernels of the ``kind``
    (``window`` | ``full``) layers reach in the traced train steps: the
    least time the chip could take for the band's products and bytes,
    forward and backward, over the device time of the instructions the
    family names for that kind; None where the run has no such layers,
    kernels or trace."""
    from . import bytes as bytes_mod
    from . import trace_reduce
    a = run["program"].attention
    if run["trace"] is None or a.get("kind") != "banded" \
            or not a[f"{kind}_layers"]:
        return None
    took = trace_reduce.kernel_seconds(run["trace"], a[f"{kind}_kernel"])
    if not took:
        return None
    window = a["window"] if kind == "window" else None
    flops = (a["batch"], a["heads"], a["seq"], a["head_dim"], window)
    nbytes = (a["batch"], a["heads"], a["kv_heads"], a["seq"], a["head_dim"])
    t_f, b_f = bytes_mod.roofline_seconds(
        attention_fwd_flops(*flops), attention_fwd_bytes(*nbytes),
        run["peak"])
    t_b, b_b = bytes_mod.roofline_seconds(
        attention_bwd_flops(*flops), attention_bwd_bytes(*nbytes),
        run["peak"])
    least = (t_f + t_b) * a[f"{kind}_layers"] * run["traced"]["steps"]
    run["notes"][f"{kind}_attention_roofline"] = {
        "forward_bound": b_f, "backward_bound": b_b, "kernel_s": took,
        "least_s": least}
    return 100.0 * least / took


# -- the expert layer's grouped matmuls, trained -----------------------------------

def moe_train_flops(assignments: float, hidden: int, inter: int) -> float:
    """One layer's two grouped matmuls (gate|up: hidden x 2 inter; down:
    inter x hidden) for ``assignments`` (token, expert) rows, three products
    each: forward, dx and dw."""
    return 3.0 * 2.0 * assignments * (hidden * 2 * inter + inter * hidden)


def moe_train_bytes(assignments: float, experts_held: int, hidden: int,
                    inter: int, itemsize: int = 2) -> float:
    """One layer: the held experts' weights read by the forward and by dx
    and their gradients written by dw, and each product's row operands: x
    in and gate|up out, the product in and y out (forward); the same widths
    for dx (dy in, dx out) and for dw (x and dy in), of both matmuls."""
    weights = 3.0 * experts_held * 3 * hidden * inter * itemsize
    rows = 3.0 * assignments * (hidden + 2 * inter + inter + hidden) \
        * itemsize
    return weights + rows


# -- the whole step ------------------------------------------------------------------

def forward_flops_per_token(pub: dict, layer_types, seq: int, *,
                            experts_held: int, vocab_held: int) -> float:
    """The matmul work of one token's forward on this chip's share: the
    projections, attention over the BAND of each layer's kind (mean keys a
    query), the router, the ``k x held / E`` experts a token meets here on
    average, and the head's held columns. ``pub``: the published keys."""
    h, d = pub["hidden_size"], pub["head_dim"]
    n, m = pub["num_attention_heads"], pub["num_key_value_heads"]
    E, k = pub["num_experts"], pub["num_experts_per_tok"]
    im = pub["moe_intermediate_size"]
    total = 0.0
    for kind in layer_types:
        window = pub["sliding_window"] if kind == SLIDING else None
        total += 2.0 * h * (2 * n * d + 2 * m * d)          # q, o; k, v
        total += 4.0 * band_pairs(seq, window) / seq * n * d
        total += 2.0 * h * E                                # the router
        total += k * experts_held / E * 2.0 * 3 * h * im
    return total + 2.0 * h * vocab_held


# -- the program's counters of the traced steps -----------------------------------

def traced_steps(run) -> list:
    """``[{name: [value of layer 0, layer 1, ...]}, ...]``, one dict a
    traced step, of the counters ``moe/<name>`` that the program's
    ``record_step`` emits for the ``aux`` the family kept of those steps;
    ``[]`` where the program keeps none (or the run traced no step)."""
    if "_mellum_steps" in run:
        return run["_mellum_steps"]
    log = getattr(run["program"], "aux_log", None)
    n = (run.get("traced") or {}).get("steps", 0)
    steps = []
    if log and n:
        import jax
        from apex_tpu import monitor
        from apex_tpu.models import mellum
        rec = monitor.Recorder(name="mellum-steps", traced_hooks=False)
        monitor.attach(rec)
        try:
            for aux in jax.device_get(list(log)[-n:]):
                mellum.record_step(aux)
        finally:
            monitor.detach()
        for e in rec.records():
            if e.get("kind") == "counter" and e["name"].startswith("moe/"):
                if e["name"] == "moe/assignments_local" \
                        and e.get("layer", 0) == 0:
                    steps.append({})
                steps[-1].setdefault(e["name"][4:], []).append(
                    float(e["value"]))
    run["_mellum_steps"] = steps
    return steps
