"""Kernel-specific sweep builders: the bridge between the generic
harness and the two tunable Pallas kernel families.

Everything here builds synthetic operands from static shape/dtype
(numpy RNG — no PRNG key plumbing, and it works at trace time for the
``"online"`` policy: the sweep's own jits execute eagerly on concrete
arrays). The flash backward is tuned INDEPENDENTLY of the forward: its
runner times only the vjp closure (the forward runs once, untimed, to
produce residuals), with the forward pinned at its own resolution so a
backward candidate never perturbs the forward measurement.

jax/ops imports are all lazy — this module sits below ops in the import
graph (ops imports tune.runtime) and must not close the cycle.
"""

from __future__ import annotations

from typing import Optional

from apex_tpu.tune import harness, space
from apex_tpu.tune.cache import TuneCache, cache_key

_DTYPES = {"bf16": "bfloat16", "bfloat16": "bfloat16",
           "fp32": "float32", "float32": "float32",
           "f32": "float32", "fp16": "float16", "float16": "float16"}

# the offline default sweep matrix: the shapes the kernels' defaults were
# measured at (a GPT step of b8 s1024 h1024, a BERT-base step of b32 s512)
DEFAULT_SHAPES = {
    "flash_attention": [
        dict(b=8, h=16, sq=1024, sk=1024, d=64, dtype="bfloat16",
             causal=True),
        dict(b=32, h=12, sq=512, sk=512, d=64, dtype="bfloat16",
             causal=False),
    ],
    "lm_head_ce": [
        dict(n=8192, v=32768, h=1024, dtype="bfloat16"),
        dict(n=16384, v=30522, h=768, dtype="bfloat16"),
    ],
    # the serve decode shapes: GPT bench heads at chat-scale contexts,
    # bf16 and fp8-KV pools (the page size is the pool's allocation
    # granule — serve.cache resolves it from these entries)
    "decode_attention": [
        dict(b=16, kv=16, group=1, s=1024, d=64, dtype="bfloat16"),
        dict(b=16, kv=16, group=1, s=1024, d=64, dtype="bfloat16",
             fp8=True),
    ],
    # the r13 kernels (ISSUE 13): LN at the GPT/BERT bench geometries,
    # fused CE at the BERT logits shape (the GPT path goes through
    # lm_head_ce), and the optimizer sweep at a GPT-125M-sized flat
    # shard per rank (world=8) and the whole-model shard (world=1)
    "fused_layer_norm": [
        dict(n=8192, h=1024, dtype="bfloat16"),
        dict(n=16384, h=768, dtype="bfloat16"),
    ],
    "xentropy": [
        dict(n=16384, v=30522, dtype="bfloat16"),
        dict(n=16384, v=30522, dtype="bfloat16", smoothing=True),
    ],
    "multi_tensor_update": [
        dict(n=16 * 1024 * 1024, dtype="float32"),
        dict(n=128 * 1024 * 1024, dtype="float32", lamb=True),
    ],
    # the serve weight-streaming dequant-matmul at the GPT bench
    # geometry: qkv ([h, 3h]) and fc2 ([4h, h]) at decode batch sizes
    "fp8_matmul": [
        dict(m=8, k=768, n=2304, dtype="bfloat16"),
        dict(m=8, k=3072, n=768, dtype="bfloat16"),
    ],
}


def _np_dtype(dtype: str):
    import jax.numpy as jnp
    return jnp.dtype(_DTYPES.get(dtype, dtype))


def parse_shape_spec(kernel: str, spec: str) -> dict:
    """``"b=8,h=16,s=1024,d=64,dtype=bf16,causal=1"`` -> shape dict.
    ``s=`` sets both sq and sk for flash. Unknown keys raise."""
    flash = kernel.startswith("flash_attention")
    decode = kernel == "decode_attention"
    if flash:
        known = {"b", "h", "s", "sq", "sk", "d", "dtype", "causal", "bias",
                 "dropout", "segments"}
    elif decode:
        known = {"b", "kv", "group", "s", "d", "dtype", "fp8"}
    elif kernel == "fused_layer_norm":
        known = {"n", "h", "dtype"}
    elif kernel == "xentropy":
        known = {"n", "v", "dtype", "smoothing"}
    elif kernel == "multi_tensor_update":
        known = {"n", "dtype", "lamb"}
    elif kernel == "fp8_matmul":
        known = {"m", "k", "n", "dtype"}
    else:
        known = {"n", "v", "h", "dtype", "smoothing"}
    # the optimizer update is fp32 math by contract (zero/update.py);
    # every other kernel defaults to the bf16 fast path
    out: dict = {"dtype": "float32" if kernel == "multi_tensor_update"
                 else "bfloat16"}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"bad shape field {part!r} (want key=value)")
        k, val = part.split("=", 1)
        k = k.strip()
        if k not in known:
            raise ValueError(f"unknown shape field {k!r} for {kernel} "
                             f"(known: {sorted(known)})")
        if k == "dtype":
            raw = val.strip()
            dt = _DTYPES.get(raw, raw)
            try:
                _np_dtype(dt)
            except Exception:
                raise ValueError(f"unknown dtype {raw!r} (known aliases: "
                                 f"{sorted(_DTYPES)})")
            out[k] = dt
        elif k in ("causal", "bias", "dropout", "segments", "smoothing",
                   "fp8", "lamb"):
            out[k] = val.strip() not in ("0", "false", "False", "")
        elif k == "s" and flash:
            out["sq"] = out["sk"] = int(val)
        else:
            out[k] = int(val)
    if decode:
        out.setdefault("b", 1)
        out.setdefault("kv", 1)
        out.setdefault("group", 1)
        for req in ("s", "d"):
            if req not in out:
                raise ValueError(f"decode_attention shape spec needs {req}")
    elif flash:
        out.setdefault("b", 1)
        out.setdefault("h", 1)
        for req in ("sq", "sk", "d"):
            if req not in out:
                raise ValueError(f"flash shape spec needs {req} (or s)")
    elif kernel == "fused_layer_norm":
        for req in ("n", "h"):
            if req not in out:
                raise ValueError(f"fused_layer_norm shape spec needs {req}")
    elif kernel == "xentropy":
        for req in ("n", "v"):
            if req not in out:
                raise ValueError(f"xentropy shape spec needs {req}")
    elif kernel == "multi_tensor_update":
        if "n" not in out:
            raise ValueError("multi_tensor_update shape spec needs n")
    elif kernel == "fp8_matmul":
        out.setdefault("m", 8)
        for req in ("k", "n"):
            if req not in out:
                raise ValueError(f"fp8_matmul shape spec needs {req}")
    else:
        for req in ("n", "v", "h"):
            if req not in out:
                raise ValueError(f"lm_head_ce shape spec needs {req}")
    return out


def split_shape(kernel: str, spec: dict):
    """(shape, dtype, flags) triplet in the cache-key vocabulary."""
    spec = dict(spec)
    raw = spec.pop("dtype", "bfloat16")
    dtype = _DTYPES.get(raw, raw)
    try:
        _np_dtype(dtype)
    except Exception:
        raise ValueError(
            f"unknown dtype {raw!r} (known aliases: {sorted(_DTYPES)})")
    if kernel.startswith("flash_attention"):
        flags = {k: bool(spec.pop(k, False))
                 for k in ("causal", "bias", "dropout", "segments")}
    elif kernel == "decode_attention":
        flags = {"fp8": bool(spec.pop("fp8", False))}
    elif kernel in ("fused_layer_norm", "fp8_matmul"):
        flags = {}
    elif kernel == "multi_tensor_update":
        flags = {"lamb": bool(spec.pop("lamb", False))}
    else:
        flags = {"smoothing": bool(spec.pop("smoothing", False))}
    spec["itemsize"] = _np_dtype(dtype).itemsize
    return spec, dtype, flags


def _flash_operands(shape: dict, dtype: str, flags: dict):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(0)
    b, h = shape.get("b", 1), shape.get("h", 1)
    sq, sk, d = shape["sq"], shape["sk"], shape["d"]
    dt = _np_dtype(dtype)
    q = jnp.asarray(rng.randn(b, h, sq, d) * 0.1, dt)
    k = jnp.asarray(rng.randn(b, h, sk, d) * 0.1, dt)
    v = jnp.asarray(rng.randn(b, h, sk, d) * 0.1, dt)
    kw = dict(causal=bool(flags.get("causal")), autotune="off")
    if flags.get("bias"):
        kw["bias"] = jnp.asarray(rng.randn(1, 1, sq, sk) * 0.2, jnp.float32)
    if flags.get("dropout"):
        kw.update(dropout_rate=0.1, dropout_seed=17)
    if flags.get("segments"):
        import numpy as _np
        sid = _np.zeros((b, sq), _np.int32)
        sid[:, sq // 2:] = 1
        kw["segment_ids_q"] = jnp.asarray(sid)
        if sk != sq:
            sidk = _np.zeros((b, sk), _np.int32)
            sidk[:, sk // 2:] = 1
            kw["segment_ids_kv"] = jnp.asarray(sidk)
    return (q, k, v), kw


def build_flash_fwd(shape: dict, dtype: str, flags: dict, *,
                    interpret: Optional[bool] = None):
    """``build(config)`` for the harness: a jitted forward-only call at
    the candidate tiling (backward pinned too, so the traced program is
    complete and the warning path stays quiet)."""
    import jax
    (q, k, v), kw = _flash_operands(shape, dtype, flags)

    def build(config):
        from apex_tpu.ops.flash_attention import flash_attention
        fn = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, block_q=config["block_q"], block_k=config["block_k"],
            block_q_bwd=config["block_q"], block_k_bwd=config["block_k"],
            interpret=interpret, **kw))
        return lambda: jax.block_until_ready(fn(q, k, v))
    return build


def build_flash_bwd(shape: dict, dtype: str, flags: dict, *,
                    interpret: Optional[bool] = None):
    """``build(config)``: times ONLY the backward — ``jax.vjp`` runs
    the forward once per build (untimed, heuristic-default tiling) and
    the timed callable applies the jitted vjp closure."""
    import jax
    import jax.numpy as jnp
    (q, k, v), kw = _flash_operands(shape, dtype, flags)

    def build(config):
        from apex_tpu.ops.flash_attention import flash_attention

        def f(q, k, v):
            return flash_attention(
                q, k, v, block_q_bwd=config["block_q"],
                block_k_bwd=config["block_k"], interpret=interpret, **kw)

        out, vjp = jax.vjp(f, q, k, v)
        do = jnp.ones_like(out)
        vjp_j = jax.jit(vjp)
        return lambda: jax.block_until_ready(vjp_j(do))
    return build


def build_lm_head_ce(shape: dict, dtype: str, flags: dict, *,
                     interpret: Optional[bool] = None):
    """``build(config)``: jitted fwd+bwd of the fused loss at the
    candidate (block_t, block_v) — the two phases share the knobs, so
    the sweep times them together (what a train step pays)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(0)
    n, v_, h = shape["n"], shape["v"], shape["h"]
    dt = _np_dtype(dtype)
    x = jnp.asarray(rng.randn(n, h) * 0.05, dt)
    emb = jnp.asarray(rng.randn(v_, h) * 0.05, dt)
    tgt = jnp.asarray(rng.randint(0, v_, (n,)), jnp.int32)
    smoothing = 0.1 if flags.get("smoothing") else 0.0

    def build(config):
        from apex_tpu.ops.lm_head_ce import fused_lm_head_cross_entropy

        def loss(x, emb):
            return jnp.mean(fused_lm_head_cross_entropy(
                x, emb, tgt, label_smoothing=smoothing,
                block_t=config["block_t"], block_v=config["block_v"],
                interpret=interpret, autotune="off"))

        fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
        return lambda: jax.block_until_ready(fn(x, emb))
    return build


def build_decode_attention(shape: dict, dtype: str, flags: dict, *,
                           interpret: Optional[bool] = None):
    """``build(config)``: jitted paged decode step at the candidate
    page size. Unlike the flash builders the OPERANDS depend on the
    config — the page size shapes the pool — so each candidate builds
    its own synthetic pool (disjoint per-sequence pages, full-context
    sequence lengths: every page live, the steady-state decode load)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(0)
    b, kv = shape.get("b", 1), shape.get("kv", 1)
    g, s, d = shape.get("group", 1), shape["s"], shape["d"]
    dt = _np_dtype(dtype)
    fp8 = bool(flags.get("fp8"))
    q = jnp.asarray(rng.randn(b, kv, g, d) * 0.1, dt)

    def build(config):
        from apex_tpu.ops.paged_attention import paged_decode_attention
        bs = config["block_kv"]
        m = -(-s // bs)
        n_pages = b * m + 1                      # page 0 stays null
        # one layer's pool leaf: K in lanes 0:d, V in d:2d
        pool = rng.randn(kv, n_pages, bs, 2 * d) * 0.1
        scales = {}
        if fp8:
            from apex_tpu.amp import fp8 as f8
            pool = jnp.clip(jnp.asarray(pool, jnp.float32), -f8.E4M3_MAX,
                            f8.E4M3_MAX).astype(f8.E4M3)
            scales = dict(k_scales=jnp.ones((kv, n_pages), jnp.float32),
                          v_scales=jnp.ones((kv, n_pages), jnp.float32))
        else:
            pool = jnp.asarray(pool, dt)
        bt = jnp.asarray(1 + np.arange(b * m).reshape(b, m), jnp.int32)
        sl = jnp.full((b,), s, jnp.int32)
        fn = jax.jit(lambda q, pool, bt, sl: paged_decode_attention(
            q, pool, bt, sl, interpret=interpret, **scales))
        return lambda: jax.block_until_ready(fn(q, pool, bt, sl))
    return build


def build_fused_layer_norm(shape: dict, dtype: str, flags: dict, *,
                           interpret: Optional[bool] = None):
    """``build(config)``: jitted fwd+bwd of the fused LN at the
    candidate ``block_r`` — the kernel pair shares the knob, so the
    sweep times them together (what a train step pays)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(0)
    n, h = shape["n"], shape["h"]
    dt = _np_dtype(dtype)
    x = jnp.asarray(rng.randn(n, h) * 0.5, dt)
    w = jnp.asarray(1.0 + rng.randn(h) * 0.02, jnp.float32)
    b = jnp.asarray(rng.randn(h) * 0.02, jnp.float32)

    def build(config):
        from apex_tpu.ops.layer_norm import fused_layer_norm_affine

        def loss(x, w, b):
            y = fused_layer_norm_affine(
                x, w, b, (h,), block_r=config["block_r"],
                interpret=interpret, out_dtype=dt)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        return lambda: jax.block_until_ready(fn(x, w, b))
    return build


def build_xentropy(shape: dict, dtype: str, flags: dict, *,
                   interpret: Optional[bool] = None):
    """``build(config)``: jitted fwd+bwd of the fused softmax-CE at the
    candidate (block_t, block_v)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(0)
    n, v_ = shape["n"], shape["v"]
    dt = _np_dtype(dtype)
    logits = jnp.asarray(rng.randn(n, v_) * 0.1, dt)
    labels = jnp.asarray(rng.randint(0, v_, (n,)), jnp.int32)
    smoothing = 0.1 if flags.get("smoothing") else 0.0

    def build(config):
        from apex_tpu.ops.fused_ce import softmax_cross_entropy_with_smoothing

        def loss(logits):
            return jnp.mean(softmax_cross_entropy_with_smoothing(
                logits, labels, smoothing,
                block_t=config["block_t"], block_v=config["block_v"],
                interpret=interpret))

        fn = jax.jit(jax.value_and_grad(loss))
        return lambda: jax.block_until_ready(fn(logits))
    return build


def build_multi_tensor_update(shape: dict, dtype: str, flags: dict, *,
                              interpret: Optional[bool] = None):
    """``build(config)``: one jitted fused shard update (Adam or the
    LAMB term) over a synthetic flat fp32 shard at the candidate
    ``block_n`` chunk."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(0)
    n = shape["n"]
    p = jnp.asarray(rng.randn(n) * 0.05, jnp.float32)
    g = jnp.asarray(rng.randn(n) * 0.01, jnp.float32)
    m = jnp.asarray(rng.randn(n) * 0.001, jnp.float32)
    v = jnp.asarray(np.abs(rng.randn(n)) * 1e-4, jnp.float32)
    step = jnp.asarray(7, jnp.int32)
    kind = "lamb" if flags.get("lamb") else "adam"

    def build(config):
        from apex_tpu.zero.fused_update import fused_shard_update

        fn = jax.jit(lambda p, g, m, v: fused_shard_update(
            p, g, m, v, step, kind=kind, lr=1e-3, betas=(0.9, 0.999),
            eps=1e-8, weight_decay=0.01, adam_w_mode=True,
            bias_correction=True, block_n=config["block_n"],
            interpret=interpret))
        return lambda: jax.block_until_ready(fn(p, g, m, v))
    return build


def build_fp8_matmul(shape: dict, dtype: str, flags: dict, *,
                     interpret: Optional[bool] = None):
    """``build(config)``: one jitted fused dequant-matmul over a
    synthetic e4m3-quantized weight at the candidate
    ``(block_k, block_n)`` tiles (serve weight-streaming's decode
    read)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(0)
    m, k, n = shape.get("m", 8), shape["k"], shape["n"]
    dt = _np_dtype(dtype)
    x = jnp.asarray(rng.randn(m, k) * 0.1, dt)
    from apex_tpu.ops.fp8_matmul import quantize_weight
    q, scale = quantize_weight(jnp.asarray(rng.randn(k, n) * 0.05,
                                           jnp.float32))

    def build(config):
        from apex_tpu.ops.fp8_matmul import fp8_dequant_matmul

        fn = jax.jit(lambda x, q, scale: fp8_dequant_matmul(
            x, q, scale, block_k=config["block_k"],
            block_n=config["block_n"], interpret=interpret))
        return lambda: jax.block_until_ready(fn(x, q, scale))
    return build


_BUILDERS = {"flash_attention_fwd": build_flash_fwd,
             "flash_attention_bwd": build_flash_bwd,
             "lm_head_ce": build_lm_head_ce,
             "decode_attention": build_decode_attention,
             "fused_layer_norm": build_fused_layer_norm,
             "xentropy": build_xentropy,
             "multi_tensor_update": build_multi_tensor_update,
             "fp8_matmul": build_fp8_matmul}


def tune_one(kernel: str, shape: dict, dtype: str, flags: dict, *,
             interpret: Optional[bool] = None, median_of: int = 5,
             warmup: int = 1, config_timeout_s: Optional[float] = 120.0,
             timer=None) -> dict:
    """Sweep one (kernel, shape bucket): enumerate the legal config
    space, measure, return the harness result dict."""
    candidates = space.config_space(kernel, shape, flags)
    build = _BUILDERS[kernel](shape, dtype, flags, interpret=interpret)
    return harness.sweep(candidates, build, timer=timer,
                         median_of=median_of, warmup=warmup,
                         config_timeout_s=config_timeout_s, label=kernel)


def tune_and_store(kernel: str, spec: dict, cache: TuneCache, *,
                   interpret: Optional[bool] = None, median_of: int = 5,
                   warmup: int = 1, config_timeout_s: Optional[float] = 120.0,
                   timer=None) -> dict:
    """Sweep + persist: the offline CLI's unit of work. Returns
    ``{key, kernel, best, best_s, n_candidates, n_failed}``."""
    shape, dtype, flags = split_shape(kernel, spec)
    result = tune_one(kernel, shape, dtype, flags, interpret=interpret,
                      median_of=median_of, warmup=warmup,
                      config_timeout_s=config_timeout_s, timer=timer)
    key = cache_key(kernel, shape, dtype, flags)
    if result["best"] is not None:
        cache.put(key, result["best"],
                  ms=(result["best_s"] or 0.0) * 1e3,
                  swept=len(result["results"]))
    return {"key": key, "kernel": kernel, "best": result["best"],
            "best_s": result["best_s"],
            "n_candidates": len(result["results"]) + len(result["failed"]),
            "n_failed": len(result["failed"]),
            "results": result["results"], "failed": result["failed"]}
