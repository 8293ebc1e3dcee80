"""The second readings behind ``benchmarks/configs/mellum2-12b-ep4-l4.json``'s
three limits, on the chip, at the published widths and 8,192 tokens:

    python3 scripts/mellum_limits.py [--seed N] [--tokens 8192]

prints one JSON line a reading: ``bf16`` (the program as the cell runs it:
what the limits have to ADMIT), ``window-512`` / ``window-1536`` (the
program with its band a 512-block short or long: a window off by one block)
and ``e4m3-weights`` (the float32 reference with every weight matrix rounded
to 4 exponent and 3 mantissa bits (e4m3), per-tensor amax scale, against itself: an expert product or a
projection in 8 bits), each of which at least one limit has to REFUSE.
Not a benchmark: it measures no time. PERF.md section 6 quotes its output.
"""

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--config", default="mellum2-12b-ep4-l4")
    args = ap.parse_args()
    from apex_tpu.utils import compile_cache
    from benchmarks.harness import manifest
    compile_cache.enable()
    readings(manifest.load_config(manifest.load_manifest(), args.config),
             args, step=512)


def readings(config, args, step):
    """``step``: by how many keys the off-by-one-block windows differ."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.models import mellum as ml
    from benchmarks.families import _amp, mellum as fam
    from benchmarks.reference import mellum as ref

    cfg, sizes = fam.model_config(config), fam.reference_sizes(config)
    k_init, k_ids = jax.random.split(jax.random.PRNGKey(args.seed))
    # every leaf in bf16, as amp O2's cast_params leaves the model's copy
    params = jax.jit(lambda k: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), ml.init_params(cfg, k)))(k_init)
    ids = jax.random.randint(k_ids, (1, args.tokens), 0, cfg.vocab_size,
                             jnp.int32)

    def say(name, reading):
        print(json.dumps({"reading": name, "seed": args.seed,
                          "tokens": args.tokens,
                          "device": jax.devices()[0].device_kind,
                          **reading}), flush=True)

    say("bf16", fam.compare(cfg, sizes, params, ids))
    for window in (cfg.sliding_window - step, cfg.sliding_window + step):
        off = dataclasses.replace(cfg, sliding_window=window)
        say(f"window-{window}", fam.compare(off, sizes, params, ids))

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
