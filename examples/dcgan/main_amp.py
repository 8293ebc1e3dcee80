"""DCGAN with amp (reference: ``examples/dcgan/main_amp.py``).

The reference dcgan example exists to exercise amp's *multiple models,
multiple optimizers, multiple losses* path: ``amp.initialize([netD, netG],
[optD, optG], num_losses=3)`` with a distinct ``loss_id`` (and so a
distinct loss scaler) for errD_real, errD_fake and errG. This script keeps
that exact structure on TPU: three scalers, two FusedAdam optimizers, one
jitted D step + one jitted G step.

Run:  JAX_PLATFORMS=cpu python examples/dcgan/main_amp.py --steps 10
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import amp
from apex_tpu.amp import scaler as scaler_mod
from apex_tpu.models import Discriminator, Generator
from apex_tpu.optimizers import FusedAdam
from apex_tpu.utils import compile_cache


def bce_with_logits(logits, target):
    """binary_cross_entropy_with_logits — the amp-safe form (amp BANS plain
    ``binary_cross_entropy`` under O1, ``apex/amp/lists/functional_overrides.py``)."""
    z = jnp.maximum(logits, 0.0)
    return jnp.mean(z - logits * target + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--nz", type=int, default=100)
    p.add_argument("--ngf", type=int, default=64)
    p.add_argument("--ndf", type=int, default=64)
    p.add_argument("--lr", type=float, default=2e-4)
    p.add_argument("--beta1", type=float, default=0.5)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--opt-level", default="O2", choices=["O0", "O1", "O2", "O3"])
    args = p.parse_args()
    compile_cache.enable()

    dtype = jnp.bfloat16 if args.opt_level in ("O2", "O3") else jnp.float32
    netG = Generator(nz=args.nz, ngf=args.ngf, dtype=dtype)
    netD = Discriminator(ndf=args.ndf, dtype=dtype)

    (ampD, ampG), (optD, optG) = amp.initialize(
        [lambda v, x: netD.apply(v, x, train=True, mutable=["batch_stats"]),
         lambda v, z: netG.apply(v, z, train=True, mutable=["batch_stats"])],
        [FusedAdam(lr=args.lr, betas=(args.beta1, 0.999)),
         FusedAdam(lr=args.lr, betas=(args.beta1, 0.999))],
        opt_level=args.opt_level, num_losses=3)
    scalers = optD._amp_stash.loss_scalers      # 3 scalers, one per loss_id

    key = jax.random.PRNGKey(0)
    z0 = jnp.zeros((2, 1, 1, args.nz))
    x0 = jnp.zeros((2, 64, 64, 3))
    vG = ampG.cast_params(netG.init(key, z0, train=True))
    vD = ampD.cast_params(netD.init(key, x0, train=True))
    pG, sG = vG["params"], vG["batch_stats"]
    pD, sD = vD["params"], vD["batch_stats"]
    optG_state, optD_state = optG.init(pG), optD.init(pD)
    sc_states = [s.state for s in scalers]

    # "real" data: smooth blobs the discriminator can tell from noise
    rng = np.random.RandomState(0)

    def real_batch():
        base = rng.randn(args.batch, 8, 8, 3).astype(np.float32)
        img = np.repeat(np.repeat(base, 8, axis=1), 8, axis=2)
        return np.tanh(img)

    @jax.jit
    def d_step(pD, sD, pG, sG, optD_state, sc_real, sc_fake, real, z):
        fake, _ = ampG({"params": pG, "batch_stats": sG}, z)

        def loss_real(p):
            out, upd = ampD({"params": p, "batch_stats": sD}, real)
            return bce_with_logits(out, 1.0), upd["batch_stats"]

        def loss_fake(p, stats):
            out, upd = ampD({"params": p, "batch_stats": stats},
                            jax.lax.stop_gradient(fake))
            return bce_with_logits(out, 0.0), upd["batch_stats"]

        # loss_id 0: errD_real — its own scaler, like the reference's
        # ``amp.scale_loss(errD_real, optD, loss_id=0)``
        gr, (lr_, sD1) = jax.grad(
            lambda p: (lambda l, s: (scaler_mod.scale_value(l, sc_real), (l, s)))(
                *loss_real(p)), has_aux=True)(pD)
        gr, inf_r = scaler_mod.unscale(gr, sc_real)
        # loss_id 1: errD_fake
        gf, (lf_, sD2) = jax.grad(
            lambda p: (lambda l, s: (scaler_mod.scale_value(l, sc_fake), (l, s)))(
                *loss_fake(p, sD1)), has_aux=True)(pD)
        gf, inf_f = scaler_mod.unscale(gf, sc_fake)

        grads = jax.tree.map(lambda a, b: a + b, gr, gf)
        found_inf = jnp.logical_or(inf_r, inf_f)
        pD, optD_state = optD.apply(optD_state, pD, grads, skip=found_inf)
        sc_real = scalers[0].update_state(sc_real, inf_r)
        sc_fake = scalers[1].update_state(sc_fake, inf_f)
        return pD, sD2, optD_state, sc_real, sc_fake, lr_ + lf_

    @jax.jit
    def g_step(pG, sG, pD, sD, optG_state, sc_g, z):
        def loss_g(p):
            fake, upd = ampG({"params": p, "batch_stats": sG}, z)
            out, _ = ampD({"params": pD, "batch_stats": sD}, fake)
            return bce_with_logits(out, 1.0), upd["batch_stats"]

        g, (lg, sG1) = jax.grad(
            lambda p: (lambda l, s: (scaler_mod.scale_value(l, sc_g), (l, s)))(
                *loss_g(p)), has_aux=True)(pG)
        g, inf_g = scaler_mod.unscale(g, sc_g)
        pG, optG_state = optG.apply(optG_state, pG, g, skip=inf_g)
        sc_g = scalers[2].update_state(sc_g, inf_g)
        return pG, sG1, optG_state, sc_g, lg

    t0 = time.perf_counter()
    for i in range(args.steps):
        real = jnp.asarray(real_batch())
        key, k1, k2 = jax.random.split(key, 3)
        z = jax.random.normal(k1, (args.batch, 1, 1, args.nz))
        pD, sD, optD_state, sc_states[0], sc_states[1], lossD = d_step(
            pD, sD, pG, sG, optD_state, sc_states[0], sc_states[1], real, z)
        z = jax.random.normal(k2, (args.batch, 1, 1, args.nz))
        pG, sG, optG_state, sc_states[2], lossG = g_step(
            pG, sG, pD, sD, optG_state, sc_states[2], z)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"[{i}/{args.steps}] Loss_D {float(lossD):.4f} "
                  f"Loss_G {float(lossG):.4f} "
                  f"scales {[int(float(s.loss_scale)) for s in sc_states]}")
    dt = time.perf_counter() - t0
    print(f"done: {args.steps / dt:.2f} iters/s")
    assert np.isfinite(float(lossD)) and np.isfinite(float(lossG))


if __name__ == "__main__":
    main()
