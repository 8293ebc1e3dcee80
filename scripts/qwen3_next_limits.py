"""The second readings behind ``benchmarks/configs/qwen3-next-80b-ep32-l4
.json``'s three limits, on the chip, at the published widths and 16,384
tokens:

    python3 scripts/qwen3_next_limits.py [--seed N] [--tokens 16384]
                                         [--only NAME ...]

prints one JSON line a reading: ``bf16`` (the program as the cell runs it:
what the limits have to ADMIT) and, each of which at least one limit has to
REFUSE: ``bf16-state`` (the scan's state rounded to bf16 between chunks),
``no-delta`` (the rule without its correction: ``u = beta v``, a decay-and-
add linear attention), ``no-decay`` (``g = 0``), ``rotary-all-lanes`` (the
rotary embedding over all 256 lanes), ``bf16-decay-leaves`` (``A_log`` and
``dt_bias`` rounded to bf16, what amp would hold without the family's
``keep_fp32``: a reading, not a fault) and ``e4m3-weights`` (the float32
reference with every weight matrix rounded to 4 exponent and 3 mantissa
bits, per-tensor amax scale, against itself). Not a benchmark: it measures
no time. PERF.md section 6 quotes its output.
"""

import argparse
import contextlib
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

READINGS = ("bf16", "bf16-state", "no-delta", "no-decay", "rotary-all-lanes",
            "bf16-decay-leaves", "e4m3-weights")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--config", default="qwen3-next-80b-ep32-l4")
    ap.add_argument("--only", nargs="*", default=READINGS, choices=READINGS)
    args = ap.parse_args()
    from apex_tpu.utils import compile_cache
    from benchmarks.harness import manifest
    compile_cache.enable()
    readings(manifest.load_config(manifest.load_manifest(), args.config),
             args)


@contextlib.contextmanager
def _patched(obj, name, value):
    """``obj.name = value`` for the block, with the scan's jitted kernel
    calls traced anew on both sides (what a chunk does is decided there)."""
    from apex_tpu.ops import gated_delta as gd
    old = getattr(obj, name)
    calls = (gd._fwd_call, gd._bwd_call, gd._local_fwd_call,
             gd._local_bwd_call)
    setattr(obj, name, value)
    for call in calls:
        call.clear_cache()
    try:
        yield
    finally:
        setattr(obj, name, old)
        for call in calls:
            call.clear_cache()


def readings(config, args):
    import jax
    import jax.numpy as jnp
    from apex_tpu.models import qwen3_next as qn
    from apex_tpu.ops import gated_delta as gd
    from benchmarks.families import _amp, qwen3_next as fam
    from benchmarks.reference import qwen3_next as ref

    cfg, sizes = fam.model_config(config), fam.reference_sizes(config)
    k_init, k_ids = jax.random.split(jax.random.PRNGKey(args.seed))

    def cast(path, x):
        # as amp O2's cast_params with the family's keep_fp32
        keep = path[-1].key in qn.FP32_LEAVES
        return x if keep else x.astype(jnp.bfloat16)

    params = jax.jit(lambda k: jax.tree_util.tree_map_with_path(
        cast, qn.init_params(cfg, k)))(k_init)
    ids = jax.random.randint(k_ids, (1, args.tokens), 0, cfg.vocab_size,
                             jnp.int32)

    def say(name, reading):
        print(json.dumps({"reading": name, "seed": args.seed,
                          "tokens": args.tokens,
                          "device": jax.devices()[0].device_kind,
                          **reading}), flush=True)

    def program(name, cfg=cfg, params=params):
        if name in args.only:
            say(name, fam.compare(cfg, sizes, params, ids))

    program("bf16")

    walk = gd._walk_chunk

    def rounded_state(*a):
        o, S = walk(*a)
        return o, S.astype(jnp.bfloat16).astype(jnp.float32)

    with _patched(gd, "_walk_chunk", rounded_state):
        program("bf16-state")

    local = gd._local

    def no_delta(*a):
        # u = beta v: no correction from the state a chunk meets (W_k = 0)
        w_v, w_k, *rest = local(*a)
        return (w_v, jnp.zeros_like(w_k), *rest)

    # ... nor inside a chunk (T = diag(beta))
    with _patched(gd, "_unit_lower_inverse",
                  lambda B, ri, ci: (ri == ci).astype(B.dtype)), \
            _patched(gd, "_local", no_delta):
        program("no-delta")

    rule = qn.gated_delta_rule
    with _patched(qn, "gated_delta_rule",
                  lambda q, k, v, g, beta, **kw: rule(
                      q, k, v, jnp.zeros_like(g), beta, **kw)):
        program("no-decay")

    program("rotary-all-lanes",
            cfg=dataclasses.replace(cfg, partial_rotary_factor=1.0))
    program("bf16-decay-leaves", params=jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(x.dtype), params))

    if "e4m3-weights" not in args.only:
        return

    # the reference in 8 bits against itself, on the experts it chooses
    def e4m3(w):
        # 4 exponent and 3 mantissa bits by ``reduce_precision`` (a convert
        # to float8 and back is folded away by the chip's compiler); the
        # largest value lands on 240, the top of that format's range
        if w.ndim < 2:
            return w
        scale = 240.0 / jnp.max(jnp.abs(w.astype(jnp.float32)))
        q = jax.lax.reduce_precision(w.astype(jnp.float32) * scale, 4, 3)
        return (q / scale).astype(w.dtype)

    low = jax.jit(lambda p: jax.tree.map(e4m3, p))(params)
    labels = jnp.roll(ids, -1, axis=1)
    rel = _amp.rel_err_fn()
    want, chosen, _ = ref.forward(params, ids, sizes, routing=True,
                                  first_expert=cfg.first_expert)
    got = ref.forward(low, ids, sizes, first_expert=cfg.first_expert,
                      forced=chosen)
    err, _ = rel(got, want)
    del got, want
    grads = jax.jit(lambda p, f: ref.grads(
        p, ids, labels, sizes, fam.GRAD_LEAVES, reduce=jnp.sum,
        first_expert=cfg.first_expert, forced=f))
    g_want, g_low = grads(params, chosen), grads(low, chosen)
    say("e4m3-weights", {
        "logit_rel_err": float(err),
        "grad_rel_err": {"/".join(p): float(rel(g_low[p], g_want[p])[0])
                         for p in fam.GRAD_LEAVES}})


if __name__ == "__main__":
    main()
