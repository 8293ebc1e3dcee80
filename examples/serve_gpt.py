"""Serve a (randomly initialized) tiny GPT with apex_tpu.serve.

Demonstrates the full serving loop: paged KV cache, continuous-batching
scheduler, greedy decode — plus the fp8-KV capacity accounting and the
naive full-recompute comparison. Runs anywhere (CPU included: the
engine picks the XLA reference attention paths off-TPU).

    python examples/serve_gpt.py [--fp8-kv] [--requests 6]
        [--monitor [RUN.jsonl]] [--export-port N]

``--monitor`` attaches a host-only observer Recorder (the
``main_amp.py`` precedent) and prints the request-level telemetry at
exit: the per-request span table (queue wait / TTFT / e2e / preempts),
the span-derived SLO percentiles, and the page-pool occupancy summary;
an optional path also dumps the raw event JSONL for
``python -m apex_tpu.monitor report``. ``--export-port`` additionally
serves live Prometheus text exposition at ``/metrics`` while the
engine drains (``ServeEngine.serve``).
"""

import argparse
import time

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--requests", type=int, default=4)
    p.add_argument("--max-new-tokens", type=int, default=16)
    p.add_argument("--fp8-kv", action="store_true",
                   help="store the KV cache as e4m3 pages (amp.fp8 codec)")
    p.add_argument("--compare-naive", action="store_true",
                   help="also run the no-cache full-recompute baseline")
    p.add_argument("--monitor", nargs="?", const="", default=None,
                   metavar="RUN.jsonl",
                   help="attach a Recorder; print the per-request span "
                        "table + pool-occupancy summary at exit "
                        "(optional arg: also dump the event JSONL)")
    p.add_argument("--export-port", type=int, default=None,
                   help="serve live /metrics (Prometheus text "
                        "exposition) on this port while draining "
                        "(0 = ephemeral; implies --monitor)")
    args = p.parse_args()

    import contextlib

    import jax
    import jax.numpy as jnp
    from apex_tpu import monitor, serve
    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.utils import compile_cache
    compile_cache.enable()

    cfg = GPTConfig(vocab_size=128, max_seq_len=128, hidden_size=64,
                    num_layers=2, num_heads=4, dtype=jnp.float32)
    params = GPT(cfg).init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"]

    engine = serve.ServeEngine(cfg, params, num_pages=64, max_seq_len=64,
                               max_prompt_len=32, max_batch=4,
                               fp8_kv=args.fp8_kv)
    monitoring = args.monitor is not None or args.export_port is not None
    rec = monitor.Recorder(traced_hooks=False, name="serve_gpt") \
        if monitoring else None
    ctx = monitor.attached(rec) if rec is not None \
        else contextlib.nullcontext()
    with ctx:
        rng = np.random.RandomState(0)
        prompts = {}
        for _ in range(args.requests):
            prompt = list(rng.randint(0, cfg.vocab_size,
                                      int(rng.randint(4, 16))))
            rid = engine.add_request(prompt, args.max_new_tokens)
            prompts[rid] = prompt

        t0 = time.perf_counter()
        if args.export_port is None:
            outputs = engine.run()
        else:
            outputs = monitor.export.serve_engine(
                engine, export_port=args.export_port,
                on_export=lambda _eng, port: print(
                    f"serving /metrics on port {port} during the drain"))
        dt = time.perf_counter() - t0
    for rid in sorted(outputs):
        print(f"request {rid}: prompt[{len(prompts[rid])}] -> "
              f"{outputs[rid]}")
    ccfg = engine.ccfg
    print(f"generated {engine.tokens_generated} tokens in {dt:.2f}s "
          f"({engine.tokens_generated / dt:.1f} tok/s) over "
          f"{len(engine.decode_step_times)} decode steps")
    print(f"cache: {ccfg.num_pages} pages x {ccfg.page_size} slots, "
          f"{ccfg.bytes_per_page()} B/page "
          f"({'e4m3' if ccfg.fp8 else str(jnp.dtype(ccfg.dtype).name)}), "
          f"pool {ccfg.pool_bytes() / 1e6:.1f} MB")
    if args.fp8_kv:
        bf16 = serve.CacheConfig(
            num_layers=ccfg.num_layers, kv_heads=ccfg.kv_heads,
            head_dim=ccfg.head_dim, num_pages=ccfg.num_pages,
            page_size=ccfg.page_size, dtype=jnp.bfloat16)
        budget = bf16.pool_bytes()
        print(f"fp8-KV capacity at {budget} pool bytes: "
              f"{ccfg.max_concurrent_seqs(budget, 64)} seqs vs bf16's "
              f"{bf16.max_concurrent_seqs(budget, 64)} (seq_len 64)")

    if args.compare_naive:
        reqs = [(prompts[r], args.max_new_tokens) for r in sorted(prompts)]
        serve.naive_generate(cfg, params, reqs[:1],
                             max_seq_len=64)          # compile
        t0 = time.perf_counter()
        naive_out, _ = serve.naive_generate(cfg, params, reqs,
                                            max_seq_len=64)
        ndt = time.perf_counter() - t0
        ntok = sum(len(o) for o in naive_out)
        print(f"naive full-recompute: {ntok} tokens in {ndt:.2f}s "
              f"({ntok / ndt:.1f} tok/s)")
        if not args.fp8_kv:
            # quantized KV can flip near-tied argmaxes; the exact-cache
            # engine must match the no-cache decode token for token
            assert naive_out == [outputs[r] for r in sorted(outputs)], \
                "paged and naive greedy decode disagree"
            print("paged == naive greedy decode: ok")

    if rec is not None:
        print("\nserve telemetry (request-level spans + SLO histograms):")
        agg = rec.aggregate()
        rendered = monitor.render_serve(agg)
        print(rendered if rendered else "(no serve telemetry recorded)")
        if args.monitor:
            n = rec.dump_jsonl(args.monitor)
            print(f"dumped {n} events to {args.monitor} "
                  f"(render: python -m apex_tpu.monitor report "
                  f"{args.monitor})")
    print("serve ok")


if __name__ == "__main__":
    main()
