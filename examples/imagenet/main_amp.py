"""ImageNet training with amp (reference: ``examples/imagenet/main_amp.py``).

The reference script is the canonical apex demo: ResNet + ``amp.initialize``
with the full flag surface (``--opt-level``, ``--keep-batchnorm-fp32``,
``--loss-scale``, ``--sync_bn``), DDP, a prefetching data loader, top-1/5
validation, and checkpoint save/resume. This is its TPU-native form:

- data parallelism is a `data` mesh axis driven by ``shard_map`` (the DDP
  wrapper + NCCL bucketing is replaced by one grad ``psum`` that XLA
  overlaps with the backward);
- ``--sync-bn`` swaps the norm factory to ``apex_tpu.parallel.SyncBatchNorm``
  (the functional ``convert_syncbn_model``);
- the input pipeline is ``apex_tpu.data.DataLoader`` (C++ threaded prefetch
  when the native extension is built, pure-python fallback otherwise) over
  synthetic or ``.npy`` data — zero-egress stand-in for real ImageNet;
- checkpoints carry model/optimizer/scaler state (the recipe of
  reference ``README.md:57-99``).

Run (single chip):   python examples/imagenet/main_amp.py --steps 30
Run (virtual mesh):  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/imagenet/main_amp.py \
    --arch resnet18 --image-size 32 --batch-size 8 --steps 4 --sync-bn
"""

from __future__ import annotations

import argparse
import functools
import os
import pickle
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from apex_tpu._compat import shard_map

from apex_tpu import amp
from apex_tpu.amp import scaler as scaler_mod
from apex_tpu.models import ResNet18, ResNet50, ResNet101
from apex_tpu.optimizers import FusedSGD
from apex_tpu.ops import softmax_cross_entropy_with_smoothing
from apex_tpu.parallel import SyncBatchNorm, allreduce_gradients
from apex_tpu.utils import compile_cache

ARCHS = {"resnet18": ResNet18, "resnet50": ResNet50, "resnet101": ResNet101}


def parse_args():
    p = argparse.ArgumentParser(description="TPU imagenet + amp")
    p.add_argument("--arch", default="resnet50", choices=sorted(ARCHS))
    p.add_argument("--batch-size", type=int, default=32, help="per device")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--label-smoothing", type=float, default=0.0)
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--steps", type=int, default=20, help="steps per epoch")
    p.add_argument("--opt-level", default="O2", choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--keep-batchnorm-fp32", default=None, type=lambda s: s == "True")
    p.add_argument("--loss-scale", default=None)
    p.add_argument("--sync-bn", action="store_true")
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--save", default=None, help="checkpoint path")
    p.add_argument("--resume", default=None, help="checkpoint path")
    p.add_argument("--validate-steps", type=int, default=2)
    p.add_argument("--dataset-size", type=int, default=512,
                   help="synthetic dataset size")
    return p.parse_args()


def synthetic_batches(args, n_dev, seed=0):
    """Fake-ImageNet through the real input pipeline: a synthetic uint8
    dataset (class-dependent brightness so top-1 actually improves) fed to
    ``apex_tpu.data.DataLoader`` — C++ threaded prefetch/augment/normalize
    when the native lib builds, numpy fallback otherwise (the DALI-stack
    analog of the reference's pipeline, zero-egress)."""
    from apex_tpu.data import DataLoader
    rng = np.random.RandomState(seed)
    b = args.batch_size * n_dev
    n = max(args.dataset_size, b)
    side = args.image_size + args.image_size // 8  # pre-crop margin
    labels = rng.randint(0, args.num_classes, n).astype(np.int32)
    images = rng.randint(0, 64, (n, side, side, 3), dtype=np.uint8)
    offs = np.linspace(0, 191, args.num_classes).astype(np.uint8)
    images += offs[labels][:, None, None, None]
    loader = DataLoader(images, labels, b,
                        crop=(args.image_size, args.image_size),
                        augment=True, shuffle=True, seed=seed,
                        prefetch=4, workers=2)
    while True:
        yield from loader


def main():
    args = parse_args()
    compile_cache.enable()
    n_dev = jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("data",))
    print(f"=> {args.arch} O{args.opt_level[-1]} devices={n_dev} "
          f"global_batch={args.batch_size * n_dev}")

    dtype = jnp.bfloat16 if args.opt_level in ("O2", "O3") else jnp.float32
    norm = (functools.partial(SyncBatchNorm, axis_name="data")
            if args.sync_bn else None)
    kw = {"num_classes": args.num_classes, "dtype": dtype}
    if norm is not None:
        kw["norm"] = norm
    model = ARCHS[args.arch](**kw)

    loss_scale = args.loss_scale
    if loss_scale not in (None, "dynamic"):
        loss_scale = float(loss_scale)
    amp_model, optimizer = amp.initialize(
        lambda v, x: model.apply(v, x, train=True, mutable=["batch_stats"]),
        FusedSGD(lr=args.lr, momentum=args.momentum,
                 weight_decay=args.weight_decay),
        opt_level=args.opt_level, keep_batchnorm_fp32=args.keep_batchnorm_fp32,
        loss_scale=loss_scale)
    scaler = optimizer._amp_stash.loss_scalers[0]

    data = synthetic_batches(args, n_dev)
    x0, _ = next(data)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(x0[:2]), train=True)
    variables = amp_model.cast_params(variables)
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt_state = optimizer.init(params)
    sstate = scaler.state
    start_epoch = 0

    if args.resume and os.path.exists(args.resume):
        with open(args.resume, "rb") as f:
            ckpt = pickle.load(f)
        to_dev = lambda t: jax.tree.map(jnp.asarray, t)  # noqa: E731
        params, batch_stats, opt_state = map(
            to_dev, (ckpt["params"], ckpt["batch_stats"], ckpt["opt_state"]))
        sstate = scaler_mod.ScalerState(*to_dev(tuple(ckpt["scaler"])))
        start_epoch = ckpt["epoch"]
        print(f"=> resumed from {args.resume} (epoch {start_epoch})")

    def loss_fn(params, batch_stats, x, y):
        out, updates = amp_model({"params": params, "batch_stats": batch_stats}, x)
        loss = jnp.mean(softmax_cross_entropy_with_smoothing(
            out, y, args.label_smoothing))
        return loss, (updates["batch_stats"], out)

    def train_step(params, batch_stats, opt_state, sstate, x, y):
        def scaled(p):
            loss, aux = loss_fn(p, batch_stats, x, y)
            return scaler_mod.scale_value(loss, sstate), (loss, aux)
        grads, (loss, (new_stats, _)) = jax.grad(scaled, has_aux=True)(params)
        grads = allreduce_gradients(grads, "data")
        grads, found_inf = scaler_mod.unscale(grads, sstate)
        params, opt_state = optimizer.apply(opt_state, params, grads,
                                            skip=found_inf)
        sstate = scaler.update_state(sstate, found_inf)
        return params, new_stats, opt_state, sstate, jax.lax.pmean(loss, "data")

    def eval_step(params, batch_stats, x, y):
        logits = model.apply({"params": params, "batch_stats": batch_stats},
                             x, train=False)
        top5 = jax.lax.top_k(logits.astype(jnp.float32), 5)[1]
        t1 = jnp.mean((top5[:, 0] == y).astype(jnp.float32))
        t5 = jnp.mean(jnp.any(top5 == y[:, None], axis=1).astype(jnp.float32))
        return jax.lax.pmean(t1, "data"), jax.lax.pmean(t5, "data")

    rep, shard = P(), P("data")
    jit_train = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(rep, rep, rep, rep, shard, shard),
        out_specs=(rep, rep, rep, rep, rep), check_vma=False),
        donate_argnums=(0, 1, 2, 3))
    jit_eval = jax.jit(shard_map(
        eval_step, mesh=mesh, in_specs=(rep, rep, shard, shard),
        out_specs=(rep, rep), check_vma=False))

    global_batch = args.batch_size * n_dev
    for epoch in range(start_epoch, args.epochs):
        t0, imgs = time.perf_counter(), 0
        for i in range(args.steps):
            x, y = next(data)
            params, batch_stats, opt_state, sstate, loss = jit_train(
                params, batch_stats, opt_state, sstate,
                jnp.asarray(x), jnp.asarray(y))
            imgs += global_batch
            if i % args.print_freq == 0:
                jax.block_until_ready(loss)
                dt = time.perf_counter() - t0
                print(f"epoch {epoch} step {i:4d} loss {float(loss):.4f} "
                      f"scale {float(sstate.loss_scale):.0f} "
                      f"{imgs / dt:.1f} img/s")
        acc1 = acc5 = 0.0
        for _ in range(args.validate_steps):
            x, y = next(data)
            t1, t5 = jit_eval(params, batch_stats, jnp.asarray(x), jnp.asarray(y))
            acc1 += float(t1)
            acc5 += float(t5)
        if args.validate_steps:
            print(f"epoch {epoch} done: "
                  f"top1 {acc1 / args.validate_steps * 100:.2f}% "
                  f"top5 {acc5 / args.validate_steps * 100:.2f}%")
        if args.save:
            scaler.state = sstate  # sync functional state back for amp.state_dict
            to_host = lambda t: jax.tree.map(np.asarray, t)  # noqa: E731
            with open(args.save, "wb") as f:
                pickle.dump({"params": to_host(params),
                             "batch_stats": to_host(batch_stats),
                             "opt_state": to_host(opt_state),
                             "scaler": to_host(tuple(sstate)),
                             "epoch": epoch + 1,
                             "amp": amp.state_dict()}, f)
            print(f"=> saved {args.save}")


if __name__ == "__main__":
    main()
