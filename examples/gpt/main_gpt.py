"""GPT training with the full parallelism stack (reference: the
``apex.transformer`` GPT mpu tests, ``apex/transformer/tensor_parallel/
tests/run_gpt_test.py``, which the reference exposes as its "example" of
the Megatron building blocks — here a real train script).

Demonstrates every transformer-tier capability in one loop:

- dp x tp mesh via ``parallel_state.initialize_model_parallel`` (the
  data axis outermost so it rides DCN on multi-host);
- Megatron tensor parallelism + sequence parallelism (activations
  sequence-sharded between blocks) + Pallas flash attention;
- bf16 compute with fp32 master weights and a dynamic loss scaler
  (amp O2 semantics assembled functionally);
- vocab-parallel cross entropy, tp-partial gradient reduction
  (``allreduce_sequence_parallel_gradients``), dp gradient psum;
- fp32 checkpoint save/resume round trip (``master_state_dict``).

The train step DONATES its state, as ``amp.make_train_step`` does: the
variables, the optimizer state and the scaler state passed in are updated
where they lie and are deleted for the caller, who rebinds the step's
outputs (``variables, opt_state, sstate, loss = step_f(variables, ...)``).
A caller that needs the old state after a step copies it first
(``jax.tree.map(jnp.copy, state)``); the batch is never donated.

Run (8 virtual devices, dp=4 x tp=2):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/gpt/main_gpt.py --tp 2 --steps 30
On a real slice drop the env vars; on multi-host call
``apex_tpu.parallel.init_distributed()`` first (see README).
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from apex_tpu._compat import shard_map

from apex_tpu.amp import scaler as scaler_mod
from apex_tpu.models import GPT, GPTConfig
from apex_tpu.monitor import profile as _prof
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import allreduce_gradients
from apex_tpu.transformer import parallel_state as ps
from apex_tpu.transformer.tensor_parallel import (
    mappings as tp_mappings, vocab_parallel_cross_entropy)
from apex_tpu.utils import compile_cache


def synthetic_batch(rng, batch, seq, vocab):
    ids = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)
    return jnp.asarray(ids), jnp.asarray(labels)


def make_step_fns(mesh, model, opt):
    """``(init_f, step_f)`` for ``model`` on ``mesh``: the jitted
    ``shard_map`` programs of this example (rank-aware init; dp x tp
    train step with Megatron-SP grad reduction, vocab-parallel CE and a
    dynamic loss scaler). ``step_f`` donates its first three arguments
    (module docstring): every output leaf of the state is written over the
    input leaf it replaces. ``chip_smoke.py --chips 4`` runs exactly these
    on real chips."""
    def init_state(ids):
        """Rank-aware init inside shard_map: each tp rank initializes its
        own weight shards (the reference's per-rank RNG offsets)."""
        variables = model.init(jax.random.PRNGKey(0), ids)
        return variables, opt.init(variables), scaler_mod.init_state(2.0 ** 12)

    def train_step(variables, opt_state, sstate, ids, labels):
        def loss_fn(variables):
            logits = model.apply(variables, ids)
            loss = jnp.mean(vocab_parallel_cross_entropy(logits, labels))
            return scaler_mod.scale_value(loss, sstate)

        # the four phase scopes of amp.make_train_step (metadata only):
        # a device trace splits this step as it splits the amp one
        with _prof.scope("amp_grad"):
            scaled, grads = jax.value_and_grad(loss_fn)(variables)
        grads = allreduce_gradients(grads, ps.DATA_AXIS)
        # Megatron-SP contract: LN and post-reduce-scatter bias grads are
        # per-tp-rank partials
        grads = tp_mappings.allreduce_sequence_parallel_gradients(
            grads, GPT.sequence_parallel_grad_filter)
        with _prof.scope("amp_unscale"):
            grads, found_inf = scaler_mod.unscale(grads, sstate)
        # tp ranks see different grad shards and must agree on skip-vs-
        # apply, or replicated state diverges (Megatron's model-parallel
        # found_inf all-reduce)
        found_inf = scaler_mod.sync_found_inf(found_inf, ps.TENSOR_AXIS)
        with _prof.scope("amp_optimizer"):
            new_vars, new_opt = opt.apply(opt_state, variables, grads,
                                          skip=found_inf)
        with _prof.scope("amp_scaler"):
            new_sstate = scaler_mod.update(sstate, found_inf, dynamic=True)
        loss = scaled / sstate.loss_scale
        return (new_vars, new_opt, new_sstate,
                jax.lax.pmean(loss, ps.DATA_AXIS))

    init_f = jax.jit(shard_map(
        init_state, mesh=mesh, in_specs=(P(ps.DATA_AXIS),),
        out_specs=(P(), P(), P()), check_vma=False))
    step_f = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), P(), P(ps.DATA_AXIS), P(ps.DATA_AXIS)),
        out_specs=(P(), P(), P(), P()), check_vma=False),
        # keep_unused: the step never reads the old ``overflow`` flag, and
        # an argument jit drops cannot give its buffer to the new one
        donate_argnums=(0, 1, 2), keep_unused=True)
    return init_f, step_f


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--tp", type=int, default=2)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch", type=int, default=8, help="global batch")
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--vocab", type=int, default=2048)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--no-sp", action="store_true",
                   help="disable Megatron sequence parallelism")
    args = p.parse_args()
    compile_cache.enable()

    n_dev = jax.device_count()
    if n_dev % args.tp:
        raise SystemExit(f"device count {n_dev} not divisible by tp={args.tp}")
    dp = n_dev // args.tp
    if args.batch % dp:
        raise SystemExit(f"global batch {args.batch} not divisible by dp={dp}")

    ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(tensor_model_parallel_size_=args.tp)
    cfg = GPTConfig(vocab_size=args.vocab, max_seq_len=args.seq,
                    hidden_size=args.hidden, num_layers=args.layers,
                    num_heads=args.heads, dtype=jnp.bfloat16,
                    sequence_parallel=not args.no_sp)
    model = GPT(cfg)
    opt = FusedAdam(lr=3e-4, master_weights=True)

    rng = np.random.RandomState(0)
    ids, labels = synthetic_batch(rng, args.batch, args.seq, args.vocab)

    init_f, step_f = make_step_fns(mesh, model, opt)

    variables, opt_state, sstate = init_f(ids)
    first = last = None
    for step in range(args.steps):
        variables, opt_state, sstate, loss = step_f(
            variables, opt_state, sstate, ids, labels)
        if step == 0:
            first = float(loss)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(loss):.4f}  "
                  f"scale {float(sstate.loss_scale):g}")
    last = float(loss)

    # fp32 checkpoint round trip (O2StateDictHook analog): export master,
    # restore, continue bitwise
    fp32 = opt.master_params(opt_state, variables)
    variables2, opt_state2 = opt.restore_master(opt_state, fp32)
    # the step donates its state, and the restored state shares the Adam
    # slots and the scaler with the live one: the resumed call gets a copy
    resumed = jax.tree.map(jnp.copy, (variables2, opt_state2, sstate))
    *_, loss_resumed = step_f(*resumed, ids, labels)
    *_, loss_direct = step_f(variables, opt_state, sstate, ids, labels)
    assert float(loss_resumed) == float(loss_direct), (
        float(loss_resumed), float(loss_direct))
    print(f"loss {first:.4f} -> {last:.4f}; fp32 checkpoint round trip: "
          f"resumed step bitwise-identical")
    ps.destroy_model_parallel()


if __name__ == "__main__":
    main()
