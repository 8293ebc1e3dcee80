"""What the ``gpt`` and ``bert`` families share on one chip: the program's
own ``amp.initialize`` -> ``cast_params`` -> ``opt.init`` ->
``amp.make_train_step`` path, compiled once ahead of time so that the same
executable is inspected (``memory_analysis``) and then stepped.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional


@dataclasses.dataclass
class TrainProgram:
    """What ``benchmarks/harness/train.py`` needs of a train cell."""
    state: Any                                   # (params, opt_state, scaler)
    step: Callable[[Any, Any], Any]              # (state, batch) -> (state, loss)
    batches: List[Any]                           # the ring, on the device
    tokens_per_step: int                         # global batch x sequence
    applied_steps: Callable[[Any], int]          # optimizer steps not skipped
    loss_scale: Callable[[Any], float]
    check: Callable[[], dict]                    # vs the plain reference
    memory: dict                                 # compiled bytes per chip
    n_classes: int                               # first loss ~ ln(n_classes)
    flops_per_token: float                       # fwd + bwd, analytic
    attention: dict                              # kernel shapes for the roofline
    chips: int = 1
    layout: Optional[dict] = None
    notes: Optional[dict] = None


def make_optimizer(spec: dict):
    """``{"name": "FusedAdam", "lr": 3e-4, ...}`` -> the program's
    optimizer; every further key is passed on."""
    from apex_tpu import optimizers
    kw = {k: v for k, v in spec.items() if k != "name"}
    return getattr(optimizers, spec["name"])(**kw)


def memory_dict(compiled) -> dict:
    m = compiled.memory_analysis()
    d = dict(temp_bytes=int(m.temp_size_in_bytes),
             argument_bytes=int(m.argument_size_in_bytes),
             output_bytes=int(m.output_size_in_bytes),
             alias_bytes=int(m.alias_size_in_bytes))
    d["total_bytes"] = (d["temp_bytes"] + d["argument_bytes"]
                        + d["output_bytes"] - d["alias_bytes"])
    return d


def rel_err_fn():
    """max|a-b| / max|b| on the device, as one scalar."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rel(a, b):
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
        return jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)), \
            jnp.all(jnp.isfinite(a)) & jnp.all(jnp.isfinite(b))
    return rel


def amp_train_program(*, model, loss_fn, init_args, make_ring, traffic,
                      seed, forward, reference_forward, check_ids, tol,
                      n_classes, flops_per_token, attention) -> TrainProgram:
    """``loss_fn(params, *batch)``; ``init_args`` the example inputs of
    ``model.init``; ``make_ring(key) -> tuple of [ring, batch, ...] arrays``
    (jit-able, made on the device); ``forward(params, ids)`` the program's
    logits and ``reference_forward(params, ids)`` the plain ones."""
    import jax
    from apex_tpu import amp
    from apex_tpu.transformer import parallel_state as ps

    ps.destroy_model_parallel()
    amp_model, opt = amp.initialize(
        model.apply, make_optimizer(traffic["optimizer"]),
        opt_level=traffic["opt_level"], verbosity=0)

    def init_state(key):
        params = amp_model.cast_params(model.init(key, *init_args)["params"])
        return params, opt.init(params), \
            opt._amp_stash.loss_scalers[0].state

    import time
    t = [time.perf_counter()]

    def lap():
        t.append(time.perf_counter())
        return t[-1] - t[-2]

    key = jax.random.PRNGKey(seed)
    k_init, k_ring, k_check = jax.random.split(key, 3)
    state = jax.block_until_ready(jax.jit(init_state)(k_init))
    timings = {"init_s": lap()}
    ring = jax.jit(make_ring)(k_ring)
    n_ring = int(traffic["ring"])
    batches = [tuple(a[i] for a in ring) for i in range(n_ring)]
    timings["ring_s"] = lap()

    step = amp.make_train_step(loss_fn, opt)
    # ONE compile: the executable that is inspected is the one stepped
    lowered = step._jitted.lower(False, *state, *batches[0])
    timings["trace_and_lower_s"] = lap()
    compiled = lowered.compile()
    timings["compile_or_cache_load_s"] = lap()

    def run_step(state, batch):
        p, o, s, loss = compiled(*state, *batch)
        return (p, o, s), loss

    def check():
        ids = check_ids(k_check)
        rel = rel_err_fn()
        err, finite = rel(jax.jit(forward)(state[0], ids),
                          jax.jit(reference_forward)(state[0], ids))
        return {"what": f"forward logits, {ids.shape[0]} x {ids.shape[1]} "
                        f"tokens, program (bf16, kernels) vs plain float32 "
                        f"reference", "rel_err": float(err),
                "finite": bool(finite), "tolerance": tol}

    return TrainProgram(
        state=state, step=run_step, batches=batches,
        tokens_per_step=int(traffic["batch"]) * int(traffic["seq"]),
        applied_steps=lambda st: int(st[1].groups[0].step),
        loss_scale=lambda st: float(st[2].loss_scale), check=check,
        memory=memory_dict(compiled), n_classes=n_classes,
        flops_per_token=flops_per_token, attention=attention,
        notes={"setup_timings": timings})
