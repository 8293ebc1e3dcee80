"""chip_smoke.py — does today's tree run on the chip? One process, public API.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # one four-chip host: ONLY the multi-chip
                                     # paths and what they are compared with

Run from the root of a checkout, no PYTHONPATH, no network; weights and
tokens come from ``--seed``. It trains and serves the GPT the repo benches
(12L / h1024 / 16 heads / V32768, bf16) at full width through the entry
points a user calls — ``amp.initialize`` -> ``amp.make_train_step`` and
``serve.ServeEngine`` with default arguments — and checks the results by the
repo's own means. Every phase prints one JSON line that names the device;
the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Anything that goes wrong is a traceback and a non-zero exit: no phase is
wrapped in a catch-and-continue. Where JAX finds no TPU the script exits 2
before the train phase and prints no result line. Times printed here are
smoke timings of a handful of steps, not benchmarks.

The phase functions take their sizes as arguments so that
``tests/test_chip_smoke.py`` can rehearse the control flow at a tiny size on
the CPU; the script itself has no size or fallback options.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib.metadata
import importlib.util
import json
import math
import os
import re
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: what a tuner miss means for each tuner-gated op on this path
#: (tune/runtime.py resolves explicit > cache > heuristic-or-shim): the
#: attention and CE kernels still run, with heuristic blocks; LN falls to
#: the jnp shim.
_ON_MISS = {
    "flash_attention_fwd": "kernel, heuristic blocks",
    "flash_attention_bwd": "kernel, heuristic blocks",
    "lm_head_ce": "kernel, heuristic blocks",
    "decode_attention": "kernel, heuristic page size",
    "fused_layer_norm": "jnp shim",
}

#: kernel-vs-reference logit tolerance, as max|a-b| / max|b|. bf16 keeps 8
#: significand bits (eps = 2**-8 ~ 3.9e-3). The two attention paths order
#: the softmax reductions differently in each of 12 layers and the
#: difference rides 24 bf16 residual adds, so a few eps accumulate; 5e-2
#: (~13 eps) admits that and nothing else — a wrong page, mask or head
#: gives an O(1) error. Token identity would be too strict: bf16 ties
#: between near-equal top logits flip the argmax legitimately. Measured on
#: a v5e (PR 21): 5e-3 kernel vs reference, 1.2e-2 tp=4 vs tp=1 (whose
#: row-parallel all-reduces add bf16 partial sums in another order).
LOGIT_TOL = 5e-2

#: dp=4 vs one-device loss tolerance (relative). Same weights, same global
#: batch, bf16 compute: the per-device batch (2 vs 8) changes the matmul
#: accumulation order and the fp32 grad psum order; Adam's first steps are
#: sign-like, which amplifies last-bit gradient differences a little.
#: Measured on four v5e chips (PR 21): 1.7e-5.
DP_LOSS_TOL = 1e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int = 32768
    max_seq_len: int = 1024
    hidden: int = 1024
    layers: int = 12
    heads: int = 16
    batch: int = 8
    seq: int = 1024
    steps: int = 6
    # serve
    max_prompt_len: int = 512
    max_batch: int = 8
    num_pages: int = 64
    n_requests: int = 6
    prompt_lo: int = 64
    new_tokens: int = 32
    # four chips: depth cut to 4 layers (widths are not), 3 steps
    mc_layers: int = 4
    mc_steps: int = 3


FULL = Sizes()


def _device_fields():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def emit(phase: str, **fields):
    print(json.dumps({"phase": phase, **_device_fields(), **fields}),
          flush=True)


def _gpt_config(sz: Sizes, layers=None, **kw):
    import jax.numpy as jnp
    from apex_tpu.models import GPTConfig
    return GPTConfig(vocab_size=sz.vocab, max_seq_len=sz.max_seq_len,
                     hidden_size=sz.hidden,
                     num_layers=sz.layers if layers is None else layers,
                     num_heads=sz.heads, dtype=jnp.bfloat16, **kw)


def _batch(sz: Sizes, seed: int):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, sz.vocab, (sz.batch, sz.seq)).astype(np.int32)
    return jnp.asarray(ids), jnp.asarray(np.roll(ids, -1, axis=1))


def _dtypes(tree) -> list:
    import jax
    return sorted({str(x.dtype) for x in jax.tree.leaves(tree)})


def _engine_kw(sz: Sizes) -> dict:
    return dict(num_pages=sz.num_pages, max_seq_len=sz.max_seq_len,
                max_prompt_len=sz.max_prompt_len, max_batch=sz.max_batch,
                record_logits=True)


def _counters(rec, prefix: str) -> dict:
    return {k: v for k, v in rec.counters().items() if k.startswith(prefix)}


# ---------------------------------------------------------------------------
# phases (one chip)
# ---------------------------------------------------------------------------

def phase_device(cache_dir: str, need_devices: int):
    """First thing after import: is this a TPU, and enough of it?"""
    import jax
    import jaxlib
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX came up on {dev.platform!r}, not a TPU",
              file=sys.stderr)
        sys.exit(2)
    if len(jax.devices()) < need_devices:
        print(f"chip_smoke: need {need_devices} devices, JAX reports "
              f"{len(jax.devices())}", file=sys.stderr)
        sys.exit(2)
    emit("device", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=importlib.metadata.version("libtpu"),
         compile_cache_dir=cache_dir)


def phase_dispatch(n: int = 20, dim: int = 4096):
    """ROADMAP S0(d): what does one dispatch cost on this machine, and does
    ``block_until_ready`` wait for the device? Prints both; decides nothing."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def noop(x):
        return x + 1.0

    one = jnp.float32(1.0)
    jax.block_until_ready(noop(one))
    trips = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(noop(one))
        trips.append(time.perf_counter() - t0)

    @jax.jit
    def work(a):            # 8 x dim^3 bf16 matmuls: milliseconds of device
        for _ in range(8):
            a = jnp.dot(a, a, preferred_element_type=jnp.float32
                        ).astype(a.dtype) * 0.01
        return a, jnp.sum(a.astype(jnp.float32))

    a = jnp.full((dim, dim), 0.01, jnp.bfloat16)
    jax.block_until_ready(work(a))

    def timed(close):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            close(work(a))
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    t_block = timed(lambda out: jax.block_until_ready(out))
    t_float = timed(lambda out: float(out[1]))
    emit("dispatch",
         noop_roundtrip_ms_median=1e3 * statistics.median(trips),
         noop_roundtrip_ms_min=1e3 * min(trips), n=n,
         work_ms_closed_by_block_until_ready=1e3 * t_block,
         work_ms_closed_by_scalar_float=1e3 * t_float,
         block_until_ready_blocks=bool(t_block > 0.5 * t_float),
         note="smoke timing, not a benchmark")


def build_train(sz: Sizes):
    """The train phase's program, through the public entry points:
    ``(cfg, step, init_state)`` with ``init_state(key, ids) -> (params,
    opt_state, scaler_state)`` pure, so tests/test_tpu_compile.py can take
    its shapes and lower the same step for a described chip."""
    from apex_tpu import amp
    from apex_tpu.models import GPT
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state as ps

    ps.destroy_model_parallel()
    cfg = _gpt_config(sz)
    model = GPT(cfg)
    amp_model, opt = amp.initialize(model.apply, FusedAdam(lr=3e-4),
                                    opt_level="O2", verbosity=0)

    def init_state(key, ids):
        params = amp_model.cast_params(model.init(key, ids)["params"])
        return params, opt.init(params), \
            opt._amp_stash.loss_scalers[0].state

    step = amp.make_train_step(
        lambda p, i, l: model.loss({"params": p}, i, l), opt)
    return cfg, step, init_state


def phase_train(sz: Sizes, seed: int):
    import jax
    import numpy as np

    cfg, step, init_state = build_train(sz)
    ids, labels = _batch(sz, seed)
    params, opt_state, sstate = jax.jit(init_state)(
        jax.random.PRNGKey(seed), ids[:1])
    dtypes = _dtypes(params)
    # ONE compile: the same executable is inspected (kernels-present,
    # memory) and then stepped
    lowered = step._jitted.lower(False, params, opt_state, sstate, ids,
                                 labels)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()

    losses, overflow, scales, step_ms = [], [], [], []
    for i in range(sz.steps + 1):
        # the last step is closed by a scalar float(), the others by
        # block_until_ready: the record says whether the two agree here
        close = float if i == sz.steps else jax.block_until_ready
        t0 = time.perf_counter()
        params, opt_state, sstate, loss = compiled(
            params, opt_state, sstate, ids, labels)
        close(loss)
        step_ms.append(1e3 * (time.perf_counter() - t0))
        losses.append(float(loss))
        overflow.append(bool(sstate.overflow))
        scales.append(float(sstate.loss_scale))

    stats = jax.devices()[0].memory_stats() or {}
    emit("train", model=dict(vocab=sz.vocab, hidden=sz.hidden,
                             layers=sz.layers, heads=sz.heads,
                             batch=sz.batch, seq=sz.seq, dtype="bfloat16"),
         opt_level="O2", optimizer="FusedAdam", param_dtypes=dtypes,
         compile_s=compile_s, steps=len(losses), losses=losses,
         loss_scale=scales[-1], skipped_steps=sum(overflow),
         step_ms=step_ms[1:-1], step_ms_closed_by_scalar_float=step_ms[-1],
         timing_note="smoke timing, not a benchmark",
         temp_bytes=mem.temp_size_in_bytes,
         argument_bytes=mem.argument_size_in_bytes,
         output_bytes=mem.output_size_in_bytes,
         alias_bytes=mem.alias_size_in_bytes,
         peak_bytes_in_use=stats.get("peak_bytes_in_use"))

    ln_v = math.log(sz.vocab)
    assert all(np.isfinite(losses)), losses
    assert abs(losses[0] - ln_v) <= 0.1 * ln_v, (losses[0], ln_v)
    assert losses[-1] < losses[0], losses
    assert all(np.isfinite(scales)), scales
    assert not any(overflow[2:]), f"steps skipped after the first two: " \
                                  f"{overflow}"
    return cfg, params, compiled.as_text()


def _serve_programs(eng, sharding=None):
    """Lower the engine's own decode and prefill programs at the shapes its
    host loop feeds them (engine.py ``_step_inner`` / ``_do_prefill``).
    ``sharding`` places the small host-fed arguments (a described chip, in
    tests/test_tpu_compile.py; None = wherever jit puts them)."""
    import jax
    import jax.numpy as jnp

    def i32(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    B, m, S = eng.max_batch, eng.pages_per_seq, eng.max_prompt_len
    decode = eng._decode.lower(
        eng.params, eng.state, i32(B, m), i32(B), i32(B),
        i32(B, dtype=jnp.bool_))
    # an engine that chunks its prompts: one chunk, from a start offset
    chunk = (i32(),) if eng.prefill_chunk else ()
    prefill = eng._prefill.lower(
        eng.params, eng.state, i32(eng.prefill_pages), i32(),
        i32(eng.prefill_chunk or S), i32(B), i32(), *chunk)
    return decode, prefill


def _requests(sz: Sizes, seed: int):
    import numpy as np
    rng = np.random.RandomState(seed + 1)
    lens = rng.randint(sz.prompt_lo, sz.max_prompt_len + 1, sz.n_requests)
    lens[0], lens[1] = sz.prompt_lo, sz.max_prompt_len     # both edges
    return [rng.randint(0, sz.vocab, int(n)).tolist() for n in lens]


def _compare_logits(eng, ref, ids, prompts) -> dict:
    """Per request: relative error of the prefill logits and — where both
    engines sampled the same first token, so the step saw the same input —
    of the first decode step's logits. Returns the phase line's fields
    after holding them to ``LOGIT_TOL``."""
    import numpy as np

    def rel(a, b):
        assert np.isfinite(a).all() and np.isfinite(b).all()
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    prefill, decode = [], []
    for sid, prompt in zip(ids, prompts):
        n = len(prompt)
        a, b = eng.logits_log[sid], ref.logits_log[sid]
        prefill.append(rel(a[n], b[n]))
        if eng.seqs[sid].tokens[n] == ref.seqs[sid].tokens[n]:
            decode.append(rel(a[n + 1], b[n + 1]))
    assert max(prefill) <= LOGIT_TOL, prefill
    assert decode, "no request sampled the same first token on both " \
                   "paths: nothing to compare the decode step on"
    assert max(decode) <= LOGIT_TOL, decode
    return dict(logit_tolerance=LOGIT_TOL, prefill_logit_rel_err=prefill,
                first_decode_logit_rel_err=decode,
                first_token_agrees=f"{len(decode)}/{len(ids)}")


def phase_serve(sz: Sizes, cfg, params, seed: int):
    from apex_tpu import serve

    kw = _engine_kw(sz)
    eng = serve.ServeEngine(cfg, params, **kw)     # no impl/interpret/tune
    lowered_decode, lowered_prefill = _serve_programs(eng)
    t0 = time.perf_counter()
    decode_text = lowered_decode.compile().as_text()
    decode_compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    lowered_prefill.compile()
    prefill_compile_s = time.perf_counter() - t0

    prompts = _requests(sz, seed)
    ids = [eng.add_request(p, sz.new_tokens) for p in prompts]
    t0 = time.perf_counter()
    out = eng.run()
    drain_s = time.perf_counter() - t0
    assert all(len(out[i]) == sz.new_tokens for i in ids), \
        {i: len(out[i]) for i in ids}

    ref = serve.ServeEngine(cfg, params, paged_impl="reference",
                            attention_impl="reference", **kw)
    ref_ids = [ref.add_request(p, sz.new_tokens) for p in prompts]
    assert ref_ids == ids
    ref.run()

    emit("serve", paged_impl=eng.paged_impl,
         attention_impl=eng.attention_impl, param_dtypes=_dtypes(params),
         requests=len(ids), prompt_lens=[len(p) for p in prompts],
         new_tokens=sz.new_tokens, tokens_generated=eng.tokens_generated,
         decode_steps=len(eng.decode_step_times),
         decode_compile_s=decode_compile_s,
         prefill_compile_s=prefill_compile_s, drain_s=drain_s,
         decode_step_ms_median=1e3 * statistics.median(
             eng.decode_step_times[1:]),
         timing_note="smoke timing, not a benchmark",
         page_size=eng.ccfg.page_size, num_pages=eng.ccfg.num_pages,
         pool_bytes=eng.ccfg.pool_bytes(),
         **_compare_logits(eng, ref, ids, prompts))
    return decode_text


def _kernel_calls(text: str) -> dict:
    """Pallas kernels in a compiled program: ``tpu_custom_call``s counted
    under every ``apx:`` profile scope in their op_name metadata."""
    found: dict = {}
    for line in text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        scopes = {sc for sc in re.findall(r"apx:(\w+)", m.group(1))
                  if not re.fullmatch(r"block_\d+", sc)} if m else set()
        for key in scopes or {"unattributed"}:
            found[key] = found.get(key, 0) + 1
    return found


def _require_kernels(found: dict, **at_least):
    """The gate against interpret mode and the XLA reference standing in."""
    for scope, n in at_least.items():
        assert found.get(scope, 0) >= n, \
            f"expected >= {n} tpu_custom_call under apx:{scope}, got {found}"


def phase_kernels_present(train_text: str, decode_text: str, rec):
    """Interpret mode or the XLA reference must not have stood in for the
    kernels; and say which tuner-gated ops resolved to what."""
    train, decode = _kernel_calls(train_text), _kernel_calls(decode_text)
    resolutions: dict = {}
    for ev in rec.records("tune"):
        row = resolutions.setdefault(
            ev["name"], {"lookups": 0, "hits": 0, "on_miss":
                         _ON_MISS.get(ev["name"], "unknown")})
        row["lookups"] += 1
        row["hits"] += bool(ev.get("hit"))
    for row in resolutions.values():
        row["resolved"] = ("tuned kernel" if row["hits"] == row["lookups"]
                           else row["on_miss"])
    emit("kernels-present", train_tpu_custom_calls=train,
         decode_tpu_custom_calls=decode, tuner=resolutions,
         tuner_counters=_counters(rec, "tune/"))
    # attention and CE each forward + backward; decode's paged attention
    _require_kernels(train, flash_attention=2, lm_head_ce=2)
    _require_kernels(decode, paged_attn=1)


def phase_loader():
    """Build and load the native library here, push two batches through
    ``apex_tpu.data`` onto the device."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np
    from apex_tpu import data

    native = data.native_available()
    if shutil.which("g++") and not native:
        raise RuntimeError("g++ is present but the native loader library "
                           "did not build or load (see the warning above)")
    rng = np.random.RandomState(0)
    images = rng.randint(0, 256, (64, 40, 40, 3), dtype=np.uint8)
    labels = np.arange(64, dtype=np.int32)
    mean, std = (0.485, 0.456, 0.406), (0.229, 0.224, 0.225)
    loader = data.DataLoader(images, labels, 16, crop=(32, 32), mean=mean,
                             std=std, out_bf16=True, augment=False,
                             shuffle=False)
    n = 0
    for x, y in loader:
        on_dev = jax.device_put(x.view(ml_dtypes.bfloat16))
        got = np.asarray(on_dev.astype(jnp.float32))
        want = (images[y, 4:36, 4:36].astype(np.float32) / 255.0
                - np.float32(mean)) / np.float32(std)
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -7)
        n += 1
        if n == 2:
            break
    assert n == 2
    emit("loader", native=native, batches=n, batch_shape=list(x.shape),
         on_device=str(on_dev.sharding))


# ---------------------------------------------------------------------------
# phases (four chips)
# ---------------------------------------------------------------------------

def _main_gpt():
    """``examples/gpt/main_gpt.py`` as a module (examples/ is not a
    package)."""
    path = os.path.join(ROOT, "examples", "gpt", "main_gpt.py")
    spec = importlib.util.spec_from_file_location("main_gpt", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _collectives(text: str) -> dict:
    """Collective instructions in a compiled program, by kind."""
    kinds = ("all-reduce", "all-gather", "reduce-scatter",
             "collective-permute", "all-to-all")
    return {k: len(re.findall(rf"\b{k}(?:-start)?\(", text)) for k in kinds}


def _per_device(x):
    """One host value per device holding a shard of ``x``."""
    import numpy as np
    return [np.asarray(s.data).item() for s in x.addressable_shards]


def _bytes_in_use(devices):
    return [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]


def _run_example_step(make_step_fns, sz: Sizes, seed: int, devices,
                      tp: int, sp: bool):
    """``mc_steps`` of the example's train step on a mesh over ``devices``;
    returns per-step losses and evidence of where things sit."""
    import jax
    import numpy as np
    from apex_tpu.models import GPT
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state as ps

    ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(tensor_model_parallel_size_=tp,
                                        devices=devices)
    model = GPT(_gpt_config(sz, layers=sz.mc_layers, sequence_parallel=sp))
    init_f, step_f = make_step_fns(
        mesh, model, FusedAdam(lr=3e-4, master_weights=True))
    ids, labels = _batch(sz, seed)
    variables, opt_state, sstate = init_f(ids)
    t0 = time.perf_counter()
    compiled = step_f.lower(variables, opt_state, sstate, ids,
                            labels).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    losses, step_ms = [], []
    for _ in range(sz.mc_steps):
        t0 = time.perf_counter()
        variables, opt_state, sstate, loss = compiled(
            variables, opt_state, sstate, ids, labels)
        jax.block_until_ready(loss)
        step_ms.append(1e3 * (time.perf_counter() - t0))
        per_dev = _per_device(loss)
        overflow = _per_device(sstate.overflow)
        scale = _per_device(sstate.loss_scale)
        # every rank reports the same loss, skip decision and scale
        assert len(set(per_dev)) == 1, per_dev
        assert len(set(overflow)) == 1 and len(set(scale)) == 1, \
            (overflow, scale)
        assert not overflow[0], "step skipped"
        losses.append(per_dev[0])
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    mem = compiled.memory_analysis()
    info = dict(
        mesh={k: int(v) for k, v in mesh.shape.items() if v > 1} or
        {"devices": 1},
        mesh_device_ids=[int(d.id) for d in mesh.devices.flat],
        layers=sz.mc_layers, sequence_parallel=sp, compile_s=compile_s,
        losses=losses, loss_scale=scale[0], step_ms=step_ms[1:],
        timing_note="smoke timing, not a benchmark",
        collectives=_collectives(text),
        tpu_custom_calls=_kernel_calls(text),
        per_device_argument_bytes=mem.argument_size_in_bytes,
        per_device_temp_bytes=mem.temp_size_in_bytes,
        bytes_in_use=_bytes_in_use(devices))
    ps.destroy_model_parallel()
    return info


def _check_placement(bytes_in_use, what: str):
    """No device near zero, device 0 not holding a multiple of the others."""
    assert all(b is not None for b in bytes_in_use), bytes_in_use
    lo, hi = min(bytes_in_use), max(bytes_in_use)
    assert lo > 0.5 * hi, f"{what}: uneven placement {bytes_in_use}"


def phase_train_4chip(sz: Sizes, seed: int):
    import jax
    devs = jax.devices()[:4]
    run = functools.partial(_run_example_step, _main_gpt().make_step_fns,
                            sz, seed)

    tp2 = run(devs, tp=2, sp=True)
    emit("train-dp2xtp2", **tp2)
    _check_placement(tp2["bytes_in_use"], "dp2 x tp2 train")
    for c in ("all-reduce", "all-gather", "reduce-scatter"):
        # dp grad psum + row-parallel / SP grad all-reduce; SP gather and
        # reduce-scatter around every Column/Row pair
        assert tp2["collectives"][c] > 0, tp2["collectives"]
    _require_kernels(tp2["tpu_custom_calls"], flash_attention=2)

    dp4 = run(devs, tp=1, sp=False)
    emit("train-dp4", **dp4)
    one = run(devs[:1], tp=1, sp=False)
    emit("train-one-device", **one)
    rel = [abs(a - b) / abs(b) for a, b in zip(dp4["losses"],
                                               one["losses"])]
    emit("train-dp4-vs-one-device", tolerance=DP_LOSS_TOL,
         loss_rel_diff=rel, dp4_losses=dp4["losses"],
         one_device_losses=one["losses"])
    assert dp4["collectives"]["all-reduce"] > 0, dp4["collectives"]
    assert max(rel) <= DP_LOSS_TOL, rel


def _sharding_census(tree):
    """{sharding description: leaf count} and bytes per device id."""
    import jax
    kinds: dict = {}
    per_dev: dict = {}
    for leaf in jax.tree.leaves(tree):
        s = leaf.sharding
        spec = getattr(s, "spec", None)
        key = (f"{type(s).__name__}({spec})" if spec is not None else
               f"{type(s).__name__}({sorted(d.id for d in s.device_set)})")
        kinds[key] = kinds.get(key, 0) + 1
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    return {"leaves_by_sharding": kinds,
            "bytes_by_device": {str(k): per_dev[k] for k in sorted(per_dev)}}


def _inputs_match(compiled, *trees):
    """Do the arrays handed to a jitted program already have the shardings
    its compiled form wants (else every call re-lays them out)?"""
    import jax
    want = jax.tree.leaves(compiled.input_shardings[0])
    have = jax.tree.leaves(trees)
    n = sum(w.is_equivalent_to(h.sharding, h.ndim)
            for w, h in zip(want, have))
    return {"matching": int(n), "of": len(have)}


def phase_serve_4chip(sz: Sizes, seed: int):
    import jax
    from apex_tpu import amp, serve
    from apex_tpu.models import GPT
    from apex_tpu.transformer import parallel_state as ps

    ps.destroy_model_parallel()
    cfg = _gpt_config(sz)
    model = GPT(cfg)
    ids, _ = _batch(sz, seed)
    amp_model = amp.initialize(model.apply, opt_level="O2", verbosity=0)
    params = amp_model.cast_params(jax.jit(model.init)(
        jax.random.PRNGKey(seed), ids[:1])["params"])      # full tp=1 tree
    handed_in = _sharding_census(params)
    kw = _engine_kw(sz)
    prompts = _requests(sz, seed)

    ps.initialize_model_parallel(tensor_model_parallel_size_=4,
                                 devices=jax.devices()[:4])
    eng = serve.ServeEngine(cfg, params, **kw)
    built = {"params": _sharding_census(eng.params),
             "state": _sharding_census(eng.state)}
    compiled_decode = _serve_programs(eng)[0].compile()
    decode_text = compiled_decode.as_text()
    match_before = _inputs_match(compiled_decode, eng.params, eng.state)
    sids = [eng.add_request(p, sz.new_tokens) for p in prompts]
    eng.step()
    after = {"params": _sharding_census(eng.params),
             "state": _sharding_census(eng.state)}
    match_after = _inputs_match(compiled_decode, eng.params, eng.state)
    out = eng.run()
    assert all(len(out[i]) == sz.new_tokens for i in sids)
    in_use = _bytes_in_use(jax.devices()[:4])
    ps.destroy_model_parallel()

    one = serve.ServeEngine(cfg, params, **kw)             # tp=1, device 0
    assert [one.add_request(p, sz.new_tokens) for p in prompts] == sids
    one.run()

    emit("serve-tp4-vs-tp1", params_handed_in=handed_in,
         engine_after_build=built, engine_after_first_step=after,
         decode_inputs_match_compiled_shardings_before=match_before,
         decode_inputs_match_compiled_shardings_after=match_after,
         decode_collectives=_collectives(decode_text),
         decode_tpu_custom_calls=_kernel_calls(decode_text),
         bytes_in_use=in_use, page_size=eng.ccfg.page_size,
         **_compare_logits(eng, one, sids, prompts))
    for census in (built, after):
        pb = list(census["params"]["bytes_by_device"].values())
        sb = list(census["state"]["bytes_by_device"].values())
        assert len(pb) == 4 and min(pb) > 0.5 * max(pb), pb
        assert len(sb) == 4 and min(sb) == max(sb), sb
    assert match_before["matching"] == match_before["of"], match_before
    assert match_after["matching"] == match_after["of"], match_after
    assert _collectives(decode_text)["all-reduce"] > 0
    _require_kernels(_kernel_calls(decode_text), paged_attn=1)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="chip_smoke.py")
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the four-chip paths and what they are "
                        "compared with (needs one host with four chips)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    from apex_tpu import monitor
    from apex_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    phase_device(cache_dir, need_devices=args.chips)

    # host-only observer: compile-cache and tuner counters flow into it;
    # the compiled programs stay uninstrumented (traced_hooks=False)
    rec = monitor.Recorder(name="chip_smoke", traced_hooks=False)
    monitor.trace.install_compile_logging()
    monitor.attach(rec)

    if args.chips == 4:
        phase_train_4chip(FULL, args.seed)
        phase_serve_4chip(FULL, args.seed)
    else:
        phase_dispatch()
        cfg, params, train_text = phase_train(FULL, args.seed)
        decode_text = phase_serve(FULL, cfg, params, args.seed)
        phase_kernels_present(train_text, decode_text, rec)
        phase_loader()

    emit("compile-cache", dir=cache_dir, **_counters(rec, "jax/compile/"))
    monitor.detach()
    dev = _device_fields()
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
