"""Minimal amp walkthrough (reference: ``examples/simple/distributed/``).

Trains a tiny MLP regression with every piece of the apex_tpu hot loop —
``amp.initialize`` opt levels, dynamic loss scaling, a fused optimizer,
and data parallelism over whatever devices exist (the `dp` mesh axis
replaces the reference's `torch.distributed.launch` + DDP wrapper;
collectives ride ICI on a real slice and the virtual host mesh on CPU).

Run:  python examples/simple/main_amp.py --opt-level O2
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
          JAX_PLATFORMS=cpu python examples/simple/main_amp.py
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from apex_tpu._compat import shard_map

from apex_tpu import amp
from apex_tpu.models import SimpleMLP
from apex_tpu.optimizers import FusedSGD
from apex_tpu.parallel import allreduce_gradients
from apex_tpu.utils import compile_cache


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--opt-level", default="O2", choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--loss-scale", default=None,
                   help='"dynamic" or a float (opt-level default otherwise)')
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=512)
    p.add_argument("--monitor", default=None, metavar="RUN_JSONL",
                   help="attach an apex_tpu.monitor recorder and dump "
                        "per-step telemetry here (render with "
                        "`python -m apex_tpu.monitor report RUN_JSONL`)")
    args = p.parse_args()
    compile_cache.enable()

    n_dev = jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("data",))
    print(f"devices={n_dev} opt_level={args.opt_level}")

    # activation="none": the fused MLP applies its activation to EVERY
    # layer (apex csrc/mlp.cpp parity), which would clamp a regression head.
    model = SimpleMLP(features=(8, 64, 64, 1), activation="none")
    loss_scale = args.loss_scale
    if loss_scale not in (None, "dynamic"):
        loss_scale = float(loss_scale)
    # lr=0.003: the old default (0.01) diverged at EVERY opt level —
    # momentum 0.9 on a 4-layer *linear* net (activation="none") is
    # unstable there, grad norms grow without bound and the loss hits
    # inf/NaN within ~40 steps (root-caused with monitor.Watchdog:
    # loss_divergence fires by step ~15, then nan — a pure optimization
    # blow-up, not a precision bug; O0 fp32 diverged identically).
    amp_model, optimizer = amp.initialize(
        model.apply, FusedSGD(lr=0.003, momentum=0.9),
        opt_level=args.opt_level, loss_scale=loss_scale)
    scaler = optimizer._amp_stash.loss_scalers[0]

    rng = np.random.RandomState(0)
    w_true = rng.randn(8, 1).astype(np.float32)
    x_all = rng.randn(args.steps, args.batch, 8).astype(np.float32)
    y_all = x_all @ w_true + 0.01 * rng.randn(args.steps, args.batch, 1).astype(np.float32)

    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
    variables = amp_model.cast_params(variables)
    params = variables["params"]
    opt_state = optimizer.init(params)
    sstate = scaler.state

    def loss_fn(params, x, y):
        pred = amp_model({"params": params}, x)
        return jnp.mean((pred.astype(jnp.float32) - y) ** 2)

    # one jitted step: scale -> grad -> dp psum -> unscale -> cond step
    def step(params, opt_state, sstate, x, y):
        from apex_tpu.amp import scaler as scaler_mod
        grads, loss = jax.grad(
            lambda p: (lambda l: (scaler_mod.scale_value(l, sstate), l))(
                loss_fn(p, x, y)), has_aux=True)(params)
        grads = allreduce_gradients(grads, "data")
        grads, found_inf = scaler_mod.unscale(grads, sstate)
        params, opt_state = optimizer.apply(opt_state, params, grads,
                                            skip=found_inf)
        sstate = scaler.update_state(sstate, found_inf)
        return params, opt_state, sstate, jax.lax.pmean(loss, "data")

    sharded_step = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P(), P()), check_vma=False))

    # optional telemetry: attach BEFORE the first (tracing) call so the
    # trace-time hooks — dp collective accounting, loss-scale gauges —
    # land in the recorder (docs/observability.md)
    import contextlib
    from apex_tpu import monitor
    rec = monitor.Recorder(name="simple-amp") if args.monitor else None
    # the watchdog turns the telemetry into diagnoses: divergence/NaN/
    # overflow-storm conditions land as health_event records in the
    # dump and print as they fire (this is what root-caused the old
    # lr=0.01 default blowing up)
    dog = monitor.Watchdog(
        rec, loss_gauges=("train/loss",),
        on_event=lambda ev: print(
            f"[watchdog] {ev['name']}: {ev['diagnosis']}")) if rec else None
    with (monitor.attached(rec) if rec else contextlib.nullcontext()):
        for i in range(args.steps):
            x = jnp.asarray(x_all[i])
            y = jnp.asarray(y_all[i])
            with (rec.step() if rec else contextlib.nullcontext()):
                params, opt_state, sstate, loss = sharded_step(
                    params, opt_state, sstate, x, y)
                if rec is not None:
                    rec.gauge("train/loss", float(loss))
            if i % 50 == 0 or i == args.steps - 1:
                print(f"step {i:4d}  loss {float(loss):.6f}  "
                      f"scale {float(sstate.loss_scale):.0f}")
    if rec is not None:
        rec.dump_jsonl(args.monitor)
        print(f"telemetry: {len(rec.records())} events -> {args.monitor} "
              f"({len(dog.events)} health events)")
    assert float(loss) < 1e-2, f"did not converge: {float(loss)}"
    print("converged ok")


if __name__ == "__main__":
    main()
