"""Multihead Attention Standalone Perf Test (TPU).

Reference harness:
``apex/contrib/examples/multihead_attn/perf_test_multihead_attn.py`` —
sweeps batch (num_seqs) for a stack of attention layers, fast vs
reference impl, self vs encdec, fwd or fwd+bwd, reporting ms/eval.
Same CLI surface here, on the Pallas flash-attention fast path.

Run on TPU:  python examples/perf_test_multihead_attn.py --trials 10
On CPU it still runs (interpret mode) — use tiny sizes.

Timing: dispatch is asynchronous, so every timed region ends in
``jax.block_until_ready``.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def parse_args():
    p = argparse.ArgumentParser(description="Multihead Attention Standalone Test")
    p.add_argument("--seq-length", default=64, type=int)
    p.add_argument("--num-seqs-start", default=10, type=int)
    p.add_argument("--num-seqs-stop", default=120, type=int)
    p.add_argument("--num-seqs-inc", default=5, type=int)
    p.add_argument("--trials", default=20, type=int)
    p.add_argument("--warmup-trials", default=5, type=int)
    p.add_argument("--layers", default=18, type=int)
    p.add_argument("--hidden-dim", default=1024, type=int)
    p.add_argument("--heads", default=16, type=int)
    p.add_argument("--encdec-attn", action="store_true")
    p.add_argument("--norm-add", action="store_true")
    p.add_argument("--ref", action="store_true",
                   help="unfused reference composition (impl='default')")
    p.add_argument("--fwd", action="store_true", help="forward only")
    p.add_argument("--biases", action="store_true")
    return p.parse_args()


def main():
    args = parse_args()
    from apex_tpu.contrib.multihead_attn import (EncdecMultiheadAttn,
                                                 SelfMultiheadAttn)
    from apex_tpu.utils import compile_cache
    compile_cache.enable()

    impl = "default" if args.ref else "fast"
    cls = EncdecMultiheadAttn if args.encdec_attn else SelfMultiheadAttn
    kwargs = dict(embed_dim=args.hidden_dim, num_heads=args.heads,
                  dropout=0.1, use_bias=args.biases,
                  include_norm_add=args.norm_add, impl=impl)
    layers = [cls(**kwargs) for _ in range(args.layers)]

    key = jax.random.PRNGKey(111)

    def stack_apply(variables_list, x, rngs):
        for layer, v, r in zip(layers, variables_list, rngs):
            if args.encdec_attn:
                y = layer.apply(v, x, x, is_training=True,
                                rngs={"dropout": r})
            else:
                y = layer.apply(v, x, is_training=True, rngs={"dropout": r})
            x = y
        return x

    def loss(variables_list, x, rngs):
        return jnp.sum(stack_apply(variables_list, x, rngs)
                       .astype(jnp.float32))

    print(f"impl={impl} {'encdec' if args.encdec_attn else 'self'} "
          f"layers={args.layers} hidden={args.hidden_dim} heads={args.heads} "
          f"seq={args.seq_length} {'fwd' if args.fwd else 'fwd+bwd'}")
    for num_seqs in range(args.num_seqs_start, args.num_seqs_stop + 1,
                          args.num_seqs_inc):
        x = jax.random.normal(
            key, (args.seq_length, num_seqs, args.hidden_dim), jnp.bfloat16)
        init_rngs = {"params": key, "dropout": key}
        if args.encdec_attn:
            variables = [l.init(init_rngs, x, x, is_training=False)
                         for l in layers]
        else:
            variables = [l.init(init_rngs, x, is_training=False)
                         for l in layers]
        rngs = list(jax.random.split(key, args.layers))

        if args.fwd:
            fn = jax.jit(lambda v, x, r: jnp.sum(
                stack_apply(v, x, r).astype(jnp.float32)))
        else:
            fn = jax.jit(lambda v, x, r: jax.grad(loss)(v, x, r))

        out = fn(variables, x, rngs)
        jax.block_until_ready(out)
        for _ in range(args.warmup_trials):
            out = fn(variables, x, rngs)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(args.trials):
            out = fn(variables, x, rngs)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / args.trials
        per_layer_us = dt / args.layers * 1e6
        print(f"[ {'fwd' if args.fwd else 'fwd+bwd'} ] "
              f"num_seqs {num_seqs:4d} time/trial {dt*1e3:8.2f} ms "
              f"per-layer {per_layer_us:8.1f} us")


if __name__ == "__main__":
    main()
