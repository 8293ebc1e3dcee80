"""GPT through the interleaved pipeline: dp x pp x tp with vpp chunks.

The flagship composition as a user script (the dryrun certifies the same
stack; this is the train-loop form): ``PipelinedGPT`` splits the blocks
into ``pp * vpp`` stages (chunk ``c`` of rank ``r`` = global stage
``c*pp + r``, the Megatron interleaved assignment the reference tracks in
``apex/transformer/parallel_state.py:252-322``), the interleaved schedule
moves activations with one ``ppermute`` per tick, remat bounds
activation memory, amp dynamic loss scaling guards bf16, and
DistributedFusedAdam shards optimizer state over the data axis (ZeRO).
Microbatch counts come from a calculator, with optional batch-size
rampup (``--rampup``).

Run (8 virtual devices, dp=2 x pp=2 x tp=2, vpp=2):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/gpt/main_gpt_pipeline.py --steps 10

``--schedule interleaved_1f1b`` (r5) swaps the grad-of-scan interleaved
schedule for Megatron's production interleaved 1F1B: same vpp chunks,
flat activation memory (a [vpp, 2·pp+1]-slot stash instead of one
residual per tick), no per-group bubbles — use it when nmb is large
and memory-bound. Incompatible with --microbatch_group_size (the 1F1B
schedule IS the memory bound) and with MoE/SP configs.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from apex_tpu._compat import shard_map

from apex_tpu.amp import scaler as scaler_mod
from apex_tpu.contrib.optimizers import DistributedFusedAdam
from apex_tpu.models import GPTConfig
from apex_tpu.models.gpt_pipeline import PipelinedGPT
from apex_tpu.transformer import build_num_microbatches_calculator
from apex_tpu.transformer import parallel_state as ps
from apex_tpu.utils import compile_cache


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--tp", type=int, default=2)
    p.add_argument("--pp", type=int, default=2)
    p.add_argument("--schedule", choices=["interleaved",
                                          "interleaved_1f1b"],
                   default="interleaved")
    p.add_argument("--vpp", type=int, default=2)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--micro-batch", type=int, default=2)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--rampup", type=int, nargs=3, metavar=("START", "INCR", "SAMPLES"),
                   help="global-batch-size rampup (Megatron --rampup-batch-size)")
    p.add_argument("--microbatch-group-size", type=int, default=None,
                   help="staged grads: run the schedule G microbatches "
                        "at a time (multiple of pp) — bounds activation "
                        "memory at O(G*mb); see docs/perf.md")
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--vocab", type=int, default=1024)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    args = p.parse_args()
    compile_cache.enable()

    n_dev = jax.device_count()
    if n_dev % (args.tp * args.pp):
        raise SystemExit(f"{n_dev} devices not divisible by tp*pp")
    dp = n_dev // (args.tp * args.pp)

    ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(
        tensor_model_parallel_size_=args.tp,
        pipeline_model_parallel_size_=args.pp,
        virtual_pipeline_model_parallel_size_=args.vpp)
    cfg = GPTConfig(vocab_size=args.vocab, max_seq_len=args.seq,
                    hidden_size=args.hidden, num_layers=args.layers,
                    num_heads=args.heads, dtype=jnp.bfloat16,
                    attention_impl="fused_softmax")
    pgpt = PipelinedGPT(cfg, n_chunks=args.vpp)
    calc = build_num_microbatches_calculator(
        args.global_batch, args.micro_batch, dp,
        rampup_batch_size=args.rampup)
    dopt = DistributedFusedAdam(lr=1e-3, axis_name=ps.DATA_AXIS)

    def init_state(ids_mb):
        params = pgpt.init(jax.random.PRNGKey(0), ids_mb)
        return params, dopt.init(params), scaler_mod.init_state(2.0 ** 12)

    def train_step(params, opt_state, sstate, ids_mb, labels_mb):
        if args.schedule == "interleaved_1f1b":
            if args.microbatch_group_size:
                raise SystemExit("--schedule interleaved_1f1b already has "
                                 "flat memory; drop "
                                 "--microbatch_group_size")
            loss, grads = pgpt.loss_and_grads_1f1b_interleaved(
                params, ids_mb, labels_mb, loss_scale=sstate.loss_scale)
        else:
            loss, grads = pgpt.loss_and_grads(
                params, ids_mb, labels_mb, loss_scale=sstate.loss_scale,
                microbatch_group_size=args.microbatch_group_size)
        # no dp pmean: DistributedFusedAdam's psum_scatter over the data
        # axis already averages (ZeRO); unscale is linear and commutes
        grads, found_inf = scaler_mod.unscale(grads, sstate)
        found_inf = scaler_mod.sync_found_inf(
            found_inf, ps.TENSOR_AXIS, ps.PIPELINE_AXIS, ps.DATA_AXIS)
        params, opt_state = dopt.apply(opt_state, params, grads,
                                       skip=found_inf)
        sstate = scaler_mod.update(sstate, found_inf, dynamic=True)
        return params, opt_state, sstate, loss  # loss_and_grads unscales

    rng = np.random.RandomState(0)
    consumed = 0
    state = None
    step_fns = {}
    for step in range(args.steps):
        calc.update(consumed, consistency_check=True)
        nmb = calc.get()
        if nmb % args.pp:
            raise SystemExit(
                f"microbatch count {nmb} (global batch "
                f"{calc.get_current_global_batch_size()}) must be divisible "
                f"by pp={args.pp} — pick rampup sizes whose nmb is a "
                f"multiple of pp (Megatron interleaved constraint)")
        mb = args.micro_batch
        ids = rng.randint(0, args.vocab, (nmb, dp * mb, args.seq)).astype(np.int32)
        labels = np.roll(ids, -1, axis=2)
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        if state is None:
            # NB the P() out_specs are a device-loop-only contract: the
            # "chunks" params (and their optimizer state) actually differ
            # per pipeline rank (and TP shards per tensor rank), which
            # check_vma=False lets through. The state is only ever fed
            # back into shard_maps with these same specs, so on-device it
            # stays consistent — but materializing it on host (print,
            # checkpoint) would silently read ONE rank's chunk params.
            # For host-side state use P(ps.PIPELINE_AXIS) on the chunks
            # subtree as tests/test_transformer.py's pipeline parity test
            # does, or save via apex_tpu.checkpoint which gathers shards.
            init_f = jax.jit(shard_map(
                init_state, mesh=mesh, in_specs=(P(None, ps.DATA_AXIS),),
                out_specs=(P(), P(), P()), check_vma=False))
            state = init_f(ids)
        if nmb not in step_fns:   # one trace per microbatch count
            step_fns[nmb] = jax.jit(shard_map(
                train_step, mesh=mesh,
                in_specs=(P(), P(), P(), P(None, ps.DATA_AXIS),
                          P(None, ps.DATA_AXIS)),
                out_specs=(P(), P(), P(), P()), check_vma=False))
        params, opt_state, sstate = state
        params, opt_state, sstate, loss = step_fns[nmb](
            params, opt_state, sstate, ids, labels)
        state = (params, opt_state, sstate)
        consumed += calc.get_current_global_batch_size()
        print(f"step {step:3d}  nmb {nmb}  gbs "
              f"{calc.get_current_global_batch_size():3d}  "
              f"loss {float(loss):.4f}  scale {float(sstate.loss_scale):g}")
    ps.destroy_model_parallel()


if __name__ == "__main__":
    main()
