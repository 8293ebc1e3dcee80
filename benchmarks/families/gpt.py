"""The ``gpt`` family: how a GPT-2 configuration becomes a train step, a
serve engine and a reference check. Imports the program under test
(``apex_tpu``) and nothing of the harness's arithmetic.

Train entries (``traffic["entry"]``):

- ``amp``: ``amp.initialize(O2)`` -> ``make_train_step`` on one chip;
- ``example_gpt``: ``examples/gpt/main_gpt.py:make_step_fns`` on the mesh of
  ``parallel_state.initialize_model_parallel`` (dp x tp, Megatron SP).
"""

from __future__ import annotations

import importlib.util
import os

from benchmarks.harness import flops as flops_mod
from benchmarks.harness.manifest import ROOT
from benchmarks.reference import gpt as ref

from . import _amp


def logit_tolerance(config: dict) -> float:
    """The bound on max|program - reference| / max|reference| over the
    logits, program in bf16 with Pallas kernels, reference in float32.

    It is the configuration's own ``logit_tolerance``: TWICE the largest
    error this check read for it on the chip in bf16 (the file says over how
    many runs), because what the bound has to refuse is not far above what
    it has to admit. bf16 keeps 8 significand bits (eps 2**-8 ~ 3.9e-3);
    each pre-LN block adds two bf16-rounded branches to the residual stream,
    so a few eps reach the logits and more at more depth: 1.0-1.3e-2 at 24
    layers, 1.5-1.9e-2 at 36 (PERF.md section 6). An 8-bit KV cache or 8-bit
    block weights (``ServeEngine(fp8_kv=True)``, ``fp8_weights=True``) read
    4-7x and 9-12x the bf16 error at the tests' tiny size and must not pass
    as the same result (``tests/test_reference.py`` holds the check to
    that); chip_smoke.py's 5e-2 lets the fp8 cache through there. A wrong mask, page or
    head gives an error of order 1. A configuration without the key is an
    error: measure it."""
    return float(config["logit_tolerance"])


def model_config(sizes: dict, **kw):
    import jax.numpy as jnp
    from apex_tpu.models import GPTConfig
    assert sizes["dtype"] == "bfloat16", sizes["dtype"]
    return GPTConfig(
        vocab_size=sizes["padded_vocab_size"],
        max_seq_len=sizes["n_positions"], hidden_size=sizes["n_embd"],
        num_layers=sizes["n_layer"], num_heads=sizes["n_head"],
        ffn_hidden_size=sizes.get("n_inner"), dtype=jnp.bfloat16, **kw)


def _token_ring(sizes, traffic):
    import jax
    import jax.numpy as jnp

    def make_ring(key):
        shape = (int(traffic["ring"]), int(traffic["batch"]),
                 int(traffic["seq"]))
        ids = jax.random.randint(key, shape, 0, sizes["vocab_size"],
                                 jnp.int32)
        return ids, jnp.roll(ids, -1, axis=2)       # next-token labels
    return make_ring


def _check_ids(sizes, traffic):
    import jax
    import jax.numpy as jnp
    n, s = traffic.get("check", {}).get("shape", [2, 256])

    def ids(key):
        return jax.random.randint(key, (n, s), 0, sizes["vocab_size"],
                                  jnp.int32)
    return ids


def _attention_shape(sizes, traffic, heads_here: int, batch_here: int):
    return {"kind": "flash", "kernel": r"^apx_flash_attention",
            "batch": batch_here, "heads": heads_here,
            "seq": int(traffic["seq"]),
            "head_dim": sizes["n_embd"] // sizes["n_head"],
            "causal": True, "layers": sizes["n_layer"]}


def build_train(config: dict, traffic: dict, seed: int):
    entry = traffic.get("entry", "amp")
    if entry == "amp":
        return _build_train_amp(config, traffic, seed)
    if entry == "example_gpt":
        return _build_train_example(config, traffic, seed)
    raise ValueError(f"gpt family: unknown train entry {entry!r}")


def _build_train_amp(config, traffic, seed):
    import jax.numpy as jnp
    from apex_tpu.models import GPT

    sizes = config["sizes"]
    model = GPT(model_config(sizes))
    fpt = flops_mod.train_flops_per_token(
        flops_mod.gpt_forward_flops_per_token(sizes, int(traffic["seq"])))
    return _amp.amp_train_program(
        model=model,
        loss_fn=lambda p, i, l: model.loss({"params": p}, i, l),
        init_args=(jnp.zeros((1, int(traffic["seq"])), jnp.int32),),
        make_ring=_token_ring(sizes, traffic), traffic=traffic, seed=seed,
        forward=lambda p, ids: model.apply({"params": p}, ids),
        reference_forward=lambda p, ids: ref.forward(
            p, ids, n_head=sizes["n_head"],
            eps=sizes["layer_norm_epsilon"]),
        check_ids=_check_ids(sizes, traffic), tol=logit_tolerance(config),
        n_classes=sizes["padded_vocab_size"], flops_per_token=fpt,
        attention=_attention_shape(sizes, traffic, sizes["n_head"],
                                   int(traffic["batch"])))


# -- the example's dp x tp step on a mesh -------------------------------------

def _main_gpt():
    """``examples/gpt/main_gpt.py`` as a module (examples/ is no package)."""
    path = os.path.join(ROOT, "examples", "gpt", "main_gpt.py")
    spec = importlib.util.spec_from_file_location("main_gpt", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def global_params(params, mesh, tp: int):
    """The tp=1 parameter tree as global arrays over ``mesh``, made of the
    per-rank shards the example's rank-aware init left on each chip. The
    example declares its state replicated (``out_specs=P()``) although each
    tp rank holds its own shard; ``serve/rules.py:GPT_PARAM_RULES`` says
    along which dimension each leaf is split. No byte is copied."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from apex_tpu.serve import rules

    specs = rules.match_serve_rules(rules.GPT_PARAM_RULES, params, world=tp,
                                    validate=False)

    def one(leaf, spec):
        if all(a is None for a in spec):
            return leaf
        dim = next(i for i, a in enumerate(spec) if a is not None)
        shape = list(leaf.shape)
        shape[dim] *= tp
        return jax.make_array_from_single_device_arrays(
            tuple(shape), NamedSharding(mesh, spec),
            [s.data for s in leaf.addressable_shards])

    return jax.tree.map(one, params, specs,
                        is_leaf=lambda x: isinstance(x, P))


def _build_train_example(config, traffic, seed):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu.models import GPT
    from apex_tpu.transformer import parallel_state as ps

    sizes = config["sizes"]
    lay = traffic["layout"]
    chips, tp = int(lay["chips"]), int(lay["tp"])
    devices = jax.devices()[:chips]
    ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(tensor_model_parallel_size_=tp,
                                        devices=devices)
    dp = chips // tp
    model = GPT(model_config(
        sizes, sequence_parallel=bool(lay["sequence_parallel"])))
    init_f, step_f = _main_gpt().make_step_fns(
        mesh, model, _amp.make_optimizer(traffic["optimizer"]))

    key = jax.random.PRNGKey(seed)
    k_ring, k_check = jax.random.split(key)
    ring_sh = NamedSharding(mesh, P(None, ps.DATA_AXIS))
    ring = jax.jit(_token_ring(sizes, traffic),
                   out_shardings=(ring_sh, ring_sh))(k_ring)
    batches = [tuple(a[i] for a in ring)
               for i in range(int(traffic["ring"]))]
    # the example's init takes its weights from PRNGKey(0), not from --seed
    # (PERF.md, open questions); the token ring is from --seed
    state = init_f(batches[0][0])
    compiled = step_f.lower(*state, *batches[0]).compile()

    def run_step(state, batch):
        v, o, s, loss = compiled(*state, *batch)
        return (v, o, s), loss

    def check():
        ids = _check_ids(sizes, traffic)(k_check)
        fwd = jax.jit(shard_map(
            lambda v, i: model.apply(v, i), mesh=mesh, in_specs=(P(), P()),
            out_specs=P(None, None, ps.TENSOR_AXIS), check_vma=False))
        full = global_params(state[0]["params"], mesh, tp)
        want = jax.jit(lambda p, i: ref.forward(
            p, i, n_head=sizes["n_head"],
            eps=sizes["layer_norm_epsilon"]))(full, ids)
        err, finite = _amp.rel_err_fn()(fwd(state[0], ids), want)
        return {"what": f"forward logits, {ids.shape[0]} x {ids.shape[1]} "
                        f"tokens, dp={dp} x tp={tp} program vs plain "
                        f"float32 reference on the assembled tp=1 weights",
                "rel_err": float(err), "finite": bool(finite),
                "tolerance": logit_tolerance(config)}

    def first_shard(x):
        return x.addressable_shards[0].data

    fpt = flops_mod.train_flops_per_token(
        flops_mod.gpt_forward_flops_per_token(sizes, int(traffic["seq"])))
    return _amp.TrainProgram(
        state=state, step=run_step, batches=batches,
        tokens_per_step=int(traffic["batch"]) * int(traffic["seq"]),
        applied_steps=lambda st: int(first_shard(st[1].groups[0].step)),
        loss_scale=lambda st: float(first_shard(st[2].loss_scale)),
        check=check, memory=_amp.memory_dict(compiled),
        n_classes=sizes["padded_vocab_size"], flops_per_token=fpt,
        attention=_attention_shape(sizes, traffic, sizes["n_head"] // tp,
                                   int(traffic["batch"]) // dp),
        chips=chips,
        layout={"dp": dp, "tp": tp,
                "sequence_parallel": bool(lay["sequence_parallel"]),
                "mesh_device_ids": [int(d.id) for d in mesh.devices.flat]})


# -- serving -----------------------------------------------------------------

def build_serve(config: dict, traffic: dict, seed: int):
    """``ServeEngine`` with default impls over weights made on the device
    from ``--seed`` in the type they are served in."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from apex_tpu import amp, serve
    from apex_tpu.serve import cache as cache_mod
    from apex_tpu.models import GPT
    from apex_tpu.transformer import parallel_state as ps
    from benchmarks.harness import loadgen
    from benchmarks.harness.serve import ServeProgram

    sizes = config["sizes"]
    eng_kw = dict(traffic["engine"])
    ps.destroy_model_parallel()
    cfg = model_config(sizes)
    model = GPT(cfg)
    amp_model = amp.initialize(model.apply, opt_level="O2", verbosity=0)

    def init(key):
        ids = jnp.zeros((1, 8), jnp.int32)
        return amp_model.cast_params(model.init(key, ids)["params"])

    params = jax.jit(init)(jax.random.PRNGKey(seed))
    # the pool holds the mix's worst case: every slot at its longest prompt
    # + longest output, +1 for the null page
    page = cache_mod.resolve_page_size(
        kv_heads=cfg.num_heads, head_dim=cfg.hidden_size // cfg.num_heads,
        context_len=eng_kw["max_seq_len"], dtype=cfg.dtype,
        batch=eng_kw["max_batch"], page_size=eng_kw.get("page_size"))
    longest = (loadgen.longest(traffic["prompt_len"])
               + loadgen.longest(traffic["output_len"]))
    num_pages = eng_kw["max_batch"] * -(-longest // page) + 1
    eng = serve.ServeEngine(cfg, params, num_pages=num_pages, **eng_kw)

    n_check, n_new = traffic.get("check", {}).get("shape", [4, 9])
    pad_to = -(-(eng_kw["max_prompt_len"] + n_new) // 8) * 8

    def check():
        """Prefill then ``n_new - 1`` decode steps through the paged cache
        vs the reference's full forward over the same tokens."""
        rng = np.random.RandomState(seed + 104729)
        prompts = [rng.randint(
            0, sizes["vocab_size"],
            loadgen.draw_length(rng, traffic["prompt_len"])).tolist()
            for _ in range(n_check)]
        eng.record_logits = True
        sids = [eng.add_request(p, n_new) for p in prompts]
        eng.run()
        eng.record_logits = False
        toks = np.zeros((n_check, pad_to), np.int32)
        rows = np.zeros((n_check, n_new), np.int32)
        got = np.zeros((n_check, n_new, sizes["padded_vocab_size"]),
                       np.float32)
        for r, (sid, p) in enumerate(zip(sids, prompts)):
            seq = eng.seqs[sid].tokens
            assert len(seq) == len(p) + n_new, (len(seq), len(p), n_new)
            toks[r, :len(seq)] = seq
            for j in range(n_new):
                rows[r, j] = len(p) + j - 1     # the row that predicts p+j
                got[r, j] = eng.logits_log[sid][len(p) + j]
        eng.logits_log.clear()

        @jax.jit
        def want_rows(p, ids, rows):
            logits = ref.forward(p, ids, n_head=sizes["n_head"],
                                 eps=sizes["layer_norm_epsilon"])
            return jnp.take_along_axis(logits, rows[:, :, None], axis=1)

        want = np.asarray(want_rows(params, jnp.asarray(toks),
                                    jnp.asarray(rows)))
        finite = bool(np.isfinite(got).all() and np.isfinite(want).all())
        err = np.max(np.abs(got - want), axis=(1, 2)) / \
            np.max(np.abs(want), axis=(1, 2))
        return {"what": f"{n_check} requests: prefill + {n_new - 1} decode "
                        f"steps through the paged cache vs the plain "
                        f"float32 reference's full forward",
                "rel_err": float(err.max()),
                "rel_err_by_request": [float(e) for e in err],
                "prompt_lens": [len(p) for p in prompts],
                "finite": finite, "tolerance": logit_tolerance(config)}

    head_dim = cfg.hidden_size // cfg.num_heads
    return ServeProgram(
        engine=eng, vocab=sizes["vocab_size"], check=check,
        attention={"kind": "paged_decode",
                   "kernel": r"^apx_paged_decode_attention",
                   "heads": cfg.num_heads, "head_dim": head_dim,
                   "layers": cfg.num_layers},
        # XLA names a program jit_<function>: serve/engine.py jits ``prefill``
        programs={"prefill": r"^jit_prefill$"},
        info={"paged_impl": eng.paged_impl,
              "attention_impl": eng.attention_impl,
              "page_size": eng.ccfg.page_size,
              "num_pages": eng.ccfg.num_pages,
              "pool_bytes": eng.ccfg.pool_bytes(),
              "max_batch": eng.max_batch,
              "weight_bytes": int(sum(
                  x.size * x.dtype.itemsize
                  for x in jax.tree.leaves(params)))})
