"""``python -m apex_tpu.ops tune`` — the offline Pallas-kernel autotune
sweep. Subsumes the historical throwaway sweep scripts (two remain
under ``scripts/`` as thin default-shape wrappers over this CLI):
one sweep implementation (``apex_tpu.tune``), one persistent cache that
the runtime lookup in ``flash_attention`` / ``fused_lm_head_cross_
entropy`` then serves from.

Examples::

    # sweep both kernels at the bench model shapes into the default cache
    python -m apex_tpu.ops tune

    # one kernel, explicit shape + cache dir, quick single-window timing
    python -m apex_tpu.ops tune --kernel flash_attention \\
        --shapes "b=8,h=16,s=1024,d=64,dtype=bf16,causal=1" \\
        --cache /tmp/tune --median-of 3

    # inspect what a cache holds
    python -m apex_tpu.ops tune --list [--cache DIR]

Shape specs are ``key=value`` comma lists — flash: ``b,h,s`` (or
``sq``/``sk``), ``d``, ``dtype``, ``causal/bias/dropout/segments``;
lm_head_ce: ``n,v,h,dtype,smoothing``; decode_attention (the serve
KV-cache page-size sweep): ``b,kv,group,s,d,dtype,fp8``;
fused_layer_norm: ``n,h,dtype``; xentropy: ``n,v,dtype,smoothing``;
multi_tensor_update (the fused optimizer sweep; fp32 by contract):
``n,lamb``; fp8_matmul (the serve weight-streaming dequant-matmul):
``m,k,n,dtype``. Flash sweeps tune the forward and backward
INDEPENDENTLY (two cache entries per shape).
"""

from __future__ import annotations

import argparse
import json
import sys


def _cmd_tune(args) -> int:
    from apex_tpu.tune import kernels as tk
    from apex_tpu.tune.cache import TuneCache

    cache = TuneCache(directory=args.cache)
    kernels = (["flash_attention", "lm_head_ce", "decode_attention",
                "fused_layer_norm", "xentropy", "multi_tensor_update",
                "fp8_matmul"]
               if args.kernel == "all" else [args.kernel])
    if args.list:
        print("tunable kernels (default sweep shapes):")
        for kernel, specs in sorted(tk.DEFAULT_SHAPES.items()):
            for spec in specs:
                fields = ",".join(f"{k}={v}" for k, v in spec.items())
                print(f"  {kernel}  {fields}")
        print(f"cache: {cache.path} (device_kind={cache.device_kind})")
        for key, row in sorted(cache.entries().items()):
            cfg = row.get("config", {})
            ms = row.get("ms")
            ms_s = f"  {ms:.3f} ms" if isinstance(ms, (int, float)) else ""
            print(f"  {key}  ->  {cfg}{ms_s}  (swept {row.get('swept', '?')})")
        return 0

    # route each --shapes spec to the FIRST selected kernel (in the
    # --kernel all order above) that accepts its fields. The field sets
    # overlap since r13 (lm_head_ce n/v/h ⊃ xentropy n/v ⊃
    # multi_tensor_update n), so an under-specified spec can route to a
    # later kernel instead of erroring — the per-sweep banner names the
    # kernel that actually runs; pass --kernel explicitly to pin it.
    # With --kernel all and no --shapes, every kernel sweeps its
    # bench-model defaults.
    per_kernel: dict = {k: [] for k in kernels}
    for s in args.shapes or []:
        errors = []
        for kernel in kernels:
            try:
                per_kernel[kernel].append(tk.parse_shape_spec(kernel, s))
                break
            except ValueError as e:
                errors.append(str(e))
        else:
            print(f"error: shape spec {s!r} fits no selected kernel:",
                  file=sys.stderr)
            for msg in errors:
                print(f"  {msg}", file=sys.stderr)
            return 2

    report = []
    rc = 0
    for kernel in kernels:
        specs = (per_kernel[kernel] if args.shapes
                 else tk.DEFAULT_SHAPES[kernel])
        phases = (["flash_attention_fwd", "flash_attention_bwd"]
                  if kernel == "flash_attention" else [kernel])
        for spec in specs:
            for phase in phases:
                if not args.json:
                    print(f"== tune {phase} {spec} ==", flush=True)
                row = tk.tune_and_store(
                    phase, spec, cache, interpret=args.interpret or None,
                    median_of=args.median_of, warmup=args.warmup,
                    config_timeout_s=args.timeout)
                report.append(row)
                if row["best"] is None:
                    rc = 1
                if not args.json:
                    for r in row["results"]:
                        print(f"  {r['config']}  {r['median_s']*1e3:9.3f} ms"
                              f"  (build {r['build_s']:.2f}s)")
                    for f in row["failed"]:
                        print(f"  {f['config']}  FAILED {f['error'][:80]}")
                    best = row["best"]
                    print(f"  -> {row['key']}")
                    print(f"  -> best {best} "
                          f"{(row['best_s'] or 0)*1e3:.3f} ms "
                          f"({row['n_candidates']} candidates, "
                          f"{row['n_failed']} failed)", flush=True)
    if args.json:
        slim = [{k: v for k, v in row.items()
                 if k not in ("results", "failed")} for row in report]
        print(json.dumps({"cache": cache.path, "tuned": slim}))
    elif report:
        print(f"cache written: {cache.path}")
    return rc


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m apex_tpu.ops")
    sub = p.add_subparsers(dest="cmd", required=True)
    t = sub.add_parser("tune", help="measure-and-cache block autotuning")
    t.add_argument("--kernel", default="all",
                   choices=["all", "flash_attention", "lm_head_ce",
                            "decode_attention", "fused_layer_norm",
                            "xentropy", "multi_tensor_update",
                            "fp8_matmul"])
    t.add_argument("--shapes", action="append", metavar="SPEC",
                   help="key=value,... shape spec (repeatable); default: "
                        "the bench model shapes")
    t.add_argument("--cache", default=None, metavar="DIR",
                   help="cache dir (default: $APEX_TPU_TUNE_CACHE or "
                        "~/.cache/apex_tpu/tune)")
    t.add_argument("--median-of", type=int, default=5)
    t.add_argument("--warmup", type=int, default=1)
    t.add_argument("--timeout", type=float, default=120.0,
                   help="per-config build+measure budget, seconds")
    t.add_argument("--interpret", action="store_true",
                   help="force Pallas interpret mode (default: auto — "
                        "interpret off-TPU)")
    t.add_argument("--json", action="store_true")
    t.add_argument("--list", action="store_true",
                   help="print the cache contents and exit")
    t.set_defaults(fn=_cmd_tune)
    args = p.parse_args(argv)
    from apex_tpu.utils import compile_cache
    compile_cache.enable()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
