"""Registered traced entrypoints for the collective-consistency check.

Each builder installs a small mesh (sized to whatever devices exist —
the invariant is about axis *names*, which size-1 axes exercise just as
well), returns a function plus tiny arguments, and
``jaxpr_checks.run_entrypoint_checks`` traces it abstractly and asserts
every collective's axis name is one the ambient mesh actually has. These
are the programs apex_tpu ships as its hot paths: the amp-wrapped train
step, the tensor-parallel layers, a pipeline schedule, and the fused
LM-head loss — the places where an axis-name typo would otherwise trace
clean and fail (or silently skip a reduction) on the pod.

Importing this module registers the builders; it does no jax work itself
(APX001 discipline).
"""

from __future__ import annotations

from apex_tpu.lint.jaxpr_checks import register_entrypoint


def _mesh_for(tp: int = 1, pp: int = 1):
    """initialize_model_parallel sized down to the available devices."""
    import jax
    from apex_tpu.transformer import parallel_state as ps

    world = len(jax.devices())
    tp = tp if world % tp == 0 else 1
    pp = pp if world % (tp * pp) == 0 else 1
    ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(
        tensor_model_parallel_size_=tp, pipeline_model_parallel_size_=pp)
    return mesh, tp, pp


def _amp_train_step():
    """amp.make_train_step on a two-matmul model: the whole O1 hot loop
    (scaled grad, unscale+overflow detect, conditional apply, scale
    update) in one jitted program."""
    import jax.numpy as jnp
    from apex_tpu import amp
    from apex_tpu.amp import scaler as scaler_mod
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state as ps

    _mesh_for()

    def loss_fn(params, x, y):
        h = jnp.tanh(x @ params["w1"])
        return jnp.mean((h @ params["w2"] - y) ** 2)

    opt = FusedAdam(lr=1e-3)
    step = amp.make_train_step(loss_fn, opt, donate=False)
    params = {"w1": jnp.zeros((4, 8), jnp.float32),
              "w2": jnp.zeros((8, 2), jnp.float32)}
    opt_state = opt.init(params)
    sstate = scaler_mod.init_state()
    x = jnp.zeros((2, 4), jnp.float32)
    y = jnp.zeros((2, 2), jnp.float32)
    allowed = (ps.DATA_AXIS, ps.PIPELINE_AXIS, ps.TENSOR_AXIS,
               ps.CONTEXT_AXIS, ps.EXPERT_AXIS)
    return step, (params, opt_state, sstate, x, y), allowed


def _tensor_parallel_layers():
    """Column- then Row-parallel linear under shard_map over the tensor
    axis — the f/g collectives of a Megatron block."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu.transformer import parallel_state as ps
    from apex_tpu.transformer.tensor_parallel import (
        ColumnParallelLinear, RowParallelLinear)

    mesh, _, _ = _mesh_for(tp=2)
    col = ColumnParallelLinear(input_size=8, output_size=16,
                               gather_output=False)
    row = RowParallelLinear(input_size=16, output_size=8,
                            input_is_parallel=True)

    def block(x):
        vc = col.init(jax.random.PRNGKey(0), x)
        h = col.apply(vc, x)
        vr = row.init(jax.random.PRNGKey(1), h)
        return row.apply(vr, h)

    fn = shard_map(block, mesh=mesh, in_specs=(P(),), out_specs=P(),
                   check_vma=False)
    x = jnp.zeros((4, 8), jnp.float32)
    return fn, (x,), mesh.axis_names


def _pipeline_schedule():
    """GPipe fill-drain over the pipeline axis (ppermute-based p2p)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu.transformer.pipeline_parallel import pipeline_apply

    mesh, _, _ = _mesh_for(pp=2)

    def stage_fn(params, h):
        return jnp.tanh(h * params)

    def run(x, w):
        return pipeline_apply(stage_fn, w, x, n_microbatches=2, remat=False)

    fn = shard_map(run, mesh=mesh,
                   in_specs=(P(), P("pipeline") if "pipeline" in
                             mesh.axis_names and mesh.shape["pipeline"] > 1
                             else P()),
                   out_specs=P("pipeline"), check_vma=False)
    x = jnp.zeros((2, 4, 4), jnp.float32)          # [n_micro, mb, d]
    w = jnp.zeros((mesh.shape["pipeline"], 1), jnp.float32)[:, 0]
    return fn, (x, w), mesh.axis_names


def _amp_train_step_monitored():
    """The amp train step with a monitor recorder attached: the
    instrumented variant of ``_amp_train_step``. Attaching happens at
    trace time (inside the returned fn), so the traced program carries
    the debug-callback telemetry — this is the gate that keeps the
    instrumentation itself APX001/APX005-clean and its collectives on
    canonical axes."""
    from apex_tpu import monitor

    step, args, allowed = _amp_train_step()
    rec = monitor.Recorder(name="lint-entrypoint")

    def monitored(*a):
        with monitor.attached(rec):
            return step(*a)

    return monitored, args, allowed


def _tp_overlap_layers():
    """Sequence-parallel Column→Row pair, forward AND backward: the
    ring collective-matmul path (``parallel/overlap.py``, what
    ``sequence_parallel=True`` means at tp > 1) whose ppermutes must ride
    the tensor axis — a wrong axis here would silently exchange shards
    with the wrong neighbours and trace clean."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu.transformer.tensor_parallel import (
        ColumnParallelLinear, RowParallelLinear)

    mesh, _, _ = _mesh_for(tp=2)
    col = ColumnParallelLinear(input_size=8, output_size=16,
                               gather_output=False, sequence_parallel=True)
    row = RowParallelLinear(input_size=16, output_size=8,
                            input_is_parallel=True, sequence_parallel=True)

    def block(x):
        vc = col.init(jax.random.PRNGKey(0), x)
        h = col.apply(vc, x)
        vr = row.init(jax.random.PRNGKey(1), h)
        return row.apply(vr, h)

    def loss_and_grad(x):
        def loss(x):
            return jnp.sum(block(x) ** 2)
        # sequence-parallel layers psum_scatter, so the local loss and
        # grad are per-rank PARTIALS: psum both over the tensor axis so
        # the P() out_specs are honest (APXJ101 — this entrypoint used
        # to return rank 0's partial, the exact bug class it now gates)
        from apex_tpu.transformer import parallel_state as ps
        l, g = loss(x), jax.grad(loss)(x)
        return (jax.lax.psum(l, ps.TENSOR_AXIS),
                jax.lax.psum(g, ps.TENSOR_AXIS))

    fn = shard_map(loss_and_grad, mesh=mesh, in_specs=(P(),),
                   out_specs=(P(), P()), check_vma=False)
    x = jnp.zeros((4, 8), jnp.float32)
    return fn, (x,), mesh.axis_names


def _ddp_bucketed_step():
    """Bucketed-DDP gradient accumulation (``overlap.accumulate_gradients``):
    per-microbatch message_size-bucket psums over the data axis,
    interleaved with the next microbatch's compute."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu.parallel.overlap import accumulate_gradients
    from apex_tpu.transformer import parallel_state as ps

    mesh, _, _ = _mesh_for()

    def grad_fn(p, mb):
        def loss(p):
            return jnp.mean((jnp.tanh(mb @ p["w1"]) @ p["w2"]) ** 2)
        return jax.grad(loss)(p)

    def run(p, mb0, mb1):
        return accumulate_gradients(grad_fn, p, (mb0, mb1),
                                    axis_name=ps.DATA_AXIS,
                                    message_size=100, overlap_comm=True)

    fn = shard_map(run, mesh=mesh, in_specs=(P(), P(), P()),
                   out_specs=P(), check_vma=False)
    params = {"w1": jnp.zeros((4, 8), jnp.float32),
              "w2": jnp.zeros((8, 2), jnp.float32)}
    mb = jnp.zeros((2, 4), jnp.float32)
    return fn, (params, mb, mb), mesh.axis_names


def _pp_zero_bubble_step():
    """Zero-bubble pipeline step (split backward, deferred wgrad) over
    the pipeline axis: forward + dgrad rings in the tick scan, dense
    wgrad flush after — the collectives (two ppermute rings + the
    external loss/grad psum) must all ride canonical axes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu.transformer import parallel_state as ps
    from apex_tpu.transformer.pipeline_parallel import (
        forward_backward_pipelining_zb)

    mesh, _, _ = _mesh_for(pp=2)

    def stage_fn(params, h):
        return h + jnp.tanh(h * params)

    def run(x, w):
        loss, g = forward_backward_pipelining_zb(
            stage_fn, lambda o: jnp.sum(o ** 2), w, x, n_microbatches=4)
        return jax.lax.psum(loss, ps.PIPELINE_AXIS), g

    inner = shard_map(
        run, mesh=mesh,
        in_specs=(P(), P("pipeline") if mesh.shape.get("pipeline", 1) > 1
                  else P()),
        out_specs=(P(), P("pipeline") if mesh.shape.get("pipeline", 1) > 1
                   else P()), check_vma=False)
    # the step is jitted with an explicit donation opt-out: this
    # entrypoint is only ever traced abstractly by the lint gate, and
    # the toy stage weights double as the check's returned grads —
    # donating would alias an input the caller still reads (APX007's
    # conscious-opt-out form)
    fn = jax.jit(inner, donate_argnums=())
    x = jnp.zeros((4, 2, 4), jnp.float32)           # [n_micro, mb, d]
    w = jnp.zeros((mesh.shape["pipeline"],), jnp.float32)
    return fn, (x, w), mesh.axis_names


def _pp_zero_bubble_interleaved_step():
    """Interleaved (vpp) zero-bubble step: the wrapped forward/backward
    rings of the interleaved enumeration plus the deferred-wgrad flush,
    chunk params stacked [V, ...]."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu.transformer import parallel_state as ps
    from apex_tpu.transformer.pipeline_parallel import (
        forward_backward_pipelining_zb_interleaved)

    mesh, _, _ = _mesh_for(pp=2)
    V = 2

    def stage_fn(params, h):
        return h + jnp.tanh(h * params)

    def run(x, w):
        loss, g = forward_backward_pipelining_zb_interleaved(
            stage_fn, lambda o: jnp.sum(o ** 2), w, x,
            n_microbatches=4, n_chunks=V)
        return jax.lax.psum(loss, ps.PIPELINE_AXIS), g

    pp_spec = P(None, "pipeline") if mesh.shape.get("pipeline", 1) > 1 \
        else P()
    inner = shard_map(run, mesh=mesh, in_specs=(P(), pp_spec),
                      out_specs=(P(), pp_spec), check_vma=False)
    # same abstract-trace-only donation opt-out as _pp_zero_bubble_step
    fn = jax.jit(inner, donate_argnums=())
    x = jnp.zeros((4, 2, 4), jnp.float32)
    w = jnp.zeros((V, mesh.shape["pipeline"]), jnp.float32)
    return fn, (x, w), mesh.axis_names


def _zero3_train_step():
    """ZeRO-3 sharded train step under amp O2 over the data axis: shard
    -> gather-behind-forward -> reduce-scatter-behind-backward ->
    found_inf psum -> sharded update. Every collective (all_gather,
    psum_scatter, the overflow-flag psum) must ride the canonical data
    axis — a typo'd axis here would trace clean and silently skip the
    gradient reduction on the pod."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu import amp, zero
    from apex_tpu.amp import scaler as scaler_mod
    from apex_tpu.transformer import parallel_state as ps

    mesh, _, _ = _mesh_for()

    def apply_fn(p, x):
        return jnp.tanh(x @ p["w1"]) @ p["w2"]

    opt = zero.ZeroOptimizer(lr=1e-3, shard_params=True)
    model, opt = amp.initialize(apply_fn, opt, opt_level="O2",
                                half_dtype=jnp.bfloat16,
                                loss_scale="dynamic", verbosity=0,
                                zero=dict(axis_name=ps.DATA_AXIS,
                                          min_shard_size=8))

    def loss_fn(full, x, y):
        # model.apply_fn is the AmpModel: the O2 cast (bf16 inputs,
        # fp32 output recast) contributes its eqns to the gated jaxpr
        return jnp.mean((model.apply_fn(full, x) - y) ** 2)

    step = zero.make_train_step(loss_fn, model, opt, donate=False)

    def run(params, x, y):
        shards = model.shard(params)
        state = opt.init(shards, model.spec)
        sstate = scaler_mod.init_state()
        out = step(shards, state, sstate, x, y)
        # the step's outputs are per-rank SHARDS — returning them under
        # out_specs=P() would record rank 0's partition only (APXJ101,
        # the bug class this gate exists for). The gate only needs the
        # collectives in the jaxpr, so reduce to a cross-rank-invariant
        # fingerprint instead of gathering the whole state.
        fp = sum(jnp.sum(leaf.astype(jnp.float32))
                 for leaf in jax.tree_util.tree_leaves(out))
        return jax.lax.psum(fp, ps.DATA_AXIS)

    inner = shard_map(run, mesh=mesh, in_specs=(P(), P(), P()),
                      out_specs=P(), check_vma=False)
    # donate_argnums=() is the APX007 conscious opt-out: this entrypoint
    # is traced abstractly by the lint gate only, and run's inputs are
    # the template params the builder still holds — the donation
    # convention lives inside zero.make_train_step(donate=True), whose
    # caller owns the whole (shards, opt_state, scaler) tuple
    fn = jax.jit(inner, donate_argnums=())
    params = {"w1": jnp.zeros((8, 16), jnp.float32),
              "w2": jnp.zeros((16, 4), jnp.float32)}
    x = jnp.zeros((4, 8), jnp.float32)
    y = jnp.zeros((4, 4), jnp.float32)
    return fn, (params, x, y), mesh.axis_names


def _fp8_train_step():
    """The O4 hot loop (``amp.make_train_step(fp8=True)``): fp8 matmuls
    through the delayed-scaling codec, amax recorded as meta cotangents,
    grad unscale + overflow skip + delayed-scaling update + scale update
    in one jitted program — plus the fp8-compressed bucketed gradient
    all-reduce (``compress="fp8"``), whose per-bucket amax pmax and fp8
    psum must ride the canonical data axis."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from apex_tpu import amp
    from apex_tpu._compat import shard_map
    from apex_tpu.amp import fp8 as fp8_mod
    from apex_tpu.amp import scaler as scaler_mod
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel.overlap import bucketed_allreduce
    from apex_tpu.transformer import parallel_state as ps

    mesh, _, _ = _mesh_for()

    def loss_fn(params, fstate, x, y):
        h = jnp.tanh(fp8_mod.fp8_matmul(x, params["w1"], fstate["l1"]))
        o = fp8_mod.fp8_matmul(h, params["w2"], fstate["l2"])
        return jnp.mean((o - y) ** 2)

    opt = FusedAdam(lr=1e-3)
    step = amp.make_train_step(loss_fn, opt, fp8=True, donate=False)

    def run(params, fstate, x, y):
        opt_state = opt.init(params)
        sstate = scaler_mod.init_state()
        out = step(params, opt_state, sstate, fstate, x, y)
        new_params = out[0]
        # the O4 comm path: the fresh params stand in for a grad tree
        # so the fp8 bucket collectives enter the gated jaxpr
        reduced = bucketed_allreduce(new_params, ps.DATA_AXIS,
                                     message_size=256, compress="fp8")
        return reduced, out[3]

    fn = shard_map(run, mesh=mesh, in_specs=(P(), P(), P(), P()),
                   out_specs=(P(), P()), check_vma=False)
    params = {"w1": jnp.zeros((4, 8), jnp.float32),
              "w2": jnp.zeros((8, 2), jnp.float32)}
    fstate = fp8_mod.init_state(["l1", "l2"], history_len=4)
    x = jnp.zeros((2, 4), jnp.float32)
    y = jnp.zeros((2, 2), jnp.float32)
    return fn, (params, fstate, x, y), mesh.axis_names


def _flash_attention_tuned_step():
    """A cache-resolved flash-attention fwd+bwd step: the builder
    writes tuned block entries (both phases) into a throwaway autotune
    cache and the step resolves its tiling from it at trace time —
    keeping the ``autotune="cache"`` resolution path (host-side lookup,
    monitor events, tuned grids) inside the zero-findings gate. The
    resolved blocks differ from the heuristic defaults on purpose, so a
    silently-dead lookup would be caught by the builder's assert."""
    import tempfile

    import jax
    import jax.numpy as jnp
    from apex_tpu.ops.flash_attention import flash_attention
    from apex_tpu.tune import TuneCache, cache_key
    from apex_tpu.tune import runtime as tune_rt

    mesh, _, _ = _mesh_for()
    b, h, s, d = 1, 2, 128, 8
    tmp = tempfile.mkdtemp(prefix="apexlint_tune_")
    cache = TuneCache(tmp)
    shape = {"b": b, "h": h, "sq": s, "sk": s, "d": d, "itemsize": 4}
    flags = {"causal": True, "bias": False, "dropout": False,
             "segments": False}
    for kern in ("flash_attention_fwd", "flash_attention_bwd"):
        cache.put(cache_key(kern, shape, "float32", flags),
                  {"block_q": 64, "block_k": 64})

    def run(q, k, v):
        # block resolution is trace-time host work: point the lookup at
        # the builder's cache for the duration of the trace, restore
        # after (the gate runs inside the user's process)
        with tune_rt.override_cache_dir(tmp):
            cfg = tune_rt.resolve("flash_attention_fwd", shape,
                                  "float32", flags, policy="cache")
            assert cfg == {"block_q": 64, "block_k": 64}, \
                f"lint entrypoint cache did not resolve: {cfg}"

            def loss(q, k, v):
                return jnp.sum(flash_attention(
                    q, k, v, causal=True, interpret=True) ** 2)

            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    # abstract-trace-only entrypoint; the toy q/k/v double as the
    # returned grads, so donation would alias inputs the checker still
    # reads (APX007's conscious-opt-out form)
    fn = jax.jit(run, donate_argnums=())
    q = jnp.zeros((b, h, s, d), jnp.float32)
    k = jnp.zeros((b, h, s, d), jnp.float32)
    v = jnp.zeros((b, h, s, d), jnp.float32)
    return fn, (q, k, v), mesh.axis_names


def _profiled_train_step():
    """The amp train step traced with the profile-scope vocabulary live
    (``monitor.profile.scope`` threads ``jax.named_scope`` tags through
    amp/TP/pipeline/ops): keeps the scope plumbing itself inside the
    zero-findings gate — a scope that imported jax at module level, did
    jax work at import (APX001), or inserted side effects under jit
    (APX005) would be caught here. The step is jitted with the explicit
    APX007 opt-out: this entrypoint is only traced abstractly and its
    toy inputs double as the checker's returned values."""
    import jax
    from apex_tpu import monitor
    from apex_tpu.monitor import profile as profile_mod

    step, args, allowed = _amp_train_step()
    rec = monitor.Recorder(name="lint-profile-entrypoint")

    def profiled(*a):
        with monitor.attached(rec), profile_mod.scope("lint_step"):
            return step._jitted(True, *a)

    fn = jax.jit(profiled, donate_argnums=())
    return fn, args, allowed


def _memory_profiled_step():
    """The amp train step traced while the FULL memory instrumentation
    is armed: recorder attached, a live :class:`MemorySampler` thread
    polling, and the analytic high-water walk running over the very
    step being gated. Keeps the memory layer's purity contract inside
    the zero-findings gate — a sampler that inserted ops, a snapshot
    that did jax work at import (APX001), or a walk that left side
    effects under jit (APX005) would be caught here. Jitted with the
    explicit APX007 opt-out: this entrypoint is only traced abstractly
    and its toy inputs double as the checker's returned values."""
    import jax
    from apex_tpu import monitor
    from apex_tpu.monitor import memory as memory_mod

    step, args, allowed = _amp_train_step()
    rec = monitor.Recorder(name="lint-memory-entrypoint")
    sampler = memory_mod.MemorySampler(0.05, recorder=rec)

    def sampled(*a):
        with monitor.attached(rec), sampler:
            memory_mod.analytic_high_water(
                lambda *aa: step._jitted(True, *aa), *a)
            return step._jitted(True, *a)

    fn = jax.jit(sampled, donate_argnums=())
    return fn, args, allowed


def _serve_decode_step():
    """The serve decode step under tp=2: one token per batch slot
    through the TP layers with the paged KV cache sharded along heads
    over the tensor axis (``serve.rules.CACHE_RULES``). The collectives
    — the row-parallel psums behind proj/fc2 and the full-vocab logits
    gather — must ride the canonical tensor axis: a typo'd axis in the
    serve path would trace clean and deadlock (or silently drop the
    reduction) on the pod."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.serve import cache as cache_mod
    from apex_tpu.serve import model as serve_model
    from apex_tpu.serve import rules as serve_rules

    cfg = GPTConfig(vocab_size=32, max_seq_len=32, hidden_size=16,
                    num_layers=1, num_heads=2, dtype=jnp.float32)
    # init at tp=1 (full layout) BEFORE installing the tp=2 mesh: the
    # serve convention is a full param tree split by the in_specs
    from apex_tpu.transformer import parallel_state as ps
    ps.destroy_model_parallel()
    params = GPT(cfg).init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))["params"]
    mesh, tp, _ = _mesh_for(tp=2)
    ccfg = cache_mod.CacheConfig(num_layers=1, kv_heads=2, head_dim=8,
                                 num_pages=4, page_size=8)
    state = cache_mod.init_cache(ccfg)

    def decode(params, state, bt, pos, tok, act):
        logits, state = serve_model.decode_forward(
            cfg, ccfg, params, state, bt, pos, tok, act,
            paged_impl="reference")
        return logits, state

    pspec = serve_rules.match_serve_rules(serve_rules.GPT_PARAM_RULES,
                                          params, world=tp)
    cspec = serve_rules.match_serve_rules(serve_rules.CACHE_RULES,
                                          state, world=tp)
    inner = shard_map(decode, mesh=mesh,
                      in_specs=(pspec, cspec, P(), P(), P(), P()),
                      out_specs=(P(), cspec), check_vma=False)
    # donate_argnums=() is the APX007 conscious opt-out: this entrypoint
    # is traced abstractly by the lint gate only — the REAL serve step
    # (ServeEngine._build_steps) donates the cache pytree
    fn = jax.jit(inner, donate_argnums=())
    bt = jnp.zeros((2, 2), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    tok = jnp.zeros((2,), jnp.int32)
    act = jnp.ones((2,), bool)
    return fn, (params, state, bt, pos, tok, act), mesh.axis_names


def _serve_prefill_step():
    """The serve prefill step under tp=2 — the OTHER compiled serve
    program (PR 11 gated only decode): one padded prompt through full
    causal attention with every position's K/V scattered into the
    rules-sharded paged cache. Same axis hazards as decode (row-parallel
    psums, the full-vocab logits gather) plus the prompt-scatter path,
    which must stay rank-local to each rank's heads shard."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.serve import cache as cache_mod
    from apex_tpu.serve import model as serve_model
    from apex_tpu.serve import rules as serve_rules

    cfg = GPTConfig(vocab_size=32, max_seq_len=32, hidden_size=16,
                    num_layers=1, num_heads=2, dtype=jnp.float32)
    # same convention as _serve_decode_step: init the FULL tp=1 tree
    # before installing the tp=2 mesh; shard_map in_specs split it
    from apex_tpu.transformer import parallel_state as ps
    ps.destroy_model_parallel()
    params = GPT(cfg).init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))["params"]
    mesh, tp, _ = _mesh_for(tp=2)
    ccfg = cache_mod.CacheConfig(num_layers=1, kv_heads=2, head_dim=8,
                                 num_pages=4, page_size=8)
    state = cache_mod.init_cache(ccfg)

    def prefill(params, state, bt, length, ids):
        logits, state = serve_model.prefill_forward(
            cfg, ccfg, params, state, bt, length, ids,
            attention_impl="reference")
        return logits, state

    pspec = serve_rules.match_serve_rules(serve_rules.GPT_PARAM_RULES,
                                          params, world=tp)
    cspec = serve_rules.match_serve_rules(serve_rules.CACHE_RULES,
                                          state, world=tp)
    inner = shard_map(prefill, mesh=mesh,
                      in_specs=(pspec, cspec, P(), P(), P()),
                      out_specs=(P(), cspec), check_vma=False)
    # donate_argnums=() is the APX007 conscious opt-out: traced
    # abstractly only — the REAL prefill (ServeEngine._build_steps)
    # donates the cache pytree
    fn = jax.jit(inner, donate_argnums=())
    bt = jnp.zeros((2,), jnp.int32)
    length = jnp.asarray(4, jnp.int32)
    ids = jnp.zeros((16,), jnp.int32)
    return fn, (params, state, bt, length, ids), mesh.axis_names


def _serve_verify_step():
    """The speculative VERIFY invocation of the serve decode program
    under tp=2 (ISSUE 20): rows ``0..k`` of the fixed-capacity batch
    carry ``k+1`` CONSECUTIVE positions of ONE sequence — the last
    committed token plus the draft tokens, each row writing its K/V
    before any row attends, per-row ``seq_lens`` masking causality.
    The compiled program is the decode program (that identity is the
    greedy-parity theorem), but the usage pattern exercises the
    repeated-block-table gather and multi-row write path, and the same
    axis hazards as decode apply (row-parallel psums, the full-vocab
    logits gather) — so the window shape gets its own gate."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.serve import cache as cache_mod
    from apex_tpu.serve import model as serve_model
    from apex_tpu.serve import rules as serve_rules

    cfg = GPTConfig(vocab_size=32, max_seq_len=32, hidden_size=16,
                    num_layers=1, num_heads=2, dtype=jnp.float32)
    # init at tp=1 (full layout) BEFORE installing the tp=2 mesh, like
    # the decode/prefill serve entrypoints
    from apex_tpu.transformer import parallel_state as ps
    ps.destroy_model_parallel()
    params = GPT(cfg).init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))["params"]
    mesh, tp, _ = _mesh_for(tp=2)
    ccfg = cache_mod.CacheConfig(num_layers=1, kv_heads=2, head_dim=8,
                                 num_pages=4, page_size=8)
    state = cache_mod.init_cache(ccfg)

    def verify(params, state, bt, pos, tok, act):
        logits, state = serve_model.decode_forward(
            cfg, ccfg, params, state, bt, pos, tok, act,
            paged_impl="reference")
        return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32), \
            state

    pspec = serve_rules.match_serve_rules(serve_rules.GPT_PARAM_RULES,
                                          params, world=tp)
    cspec = serve_rules.match_serve_rules(serve_rules.CACHE_RULES,
                                          state, world=tp)
    inner = shard_map(verify, mesh=mesh,
                      in_specs=(pspec, cspec, P(), P(), P(), P()),
                      out_specs=(P(), P(), cspec), check_vma=False)
    # donate_argnums=() is the APX007 conscious opt-out: traced
    # abstractly only — the REAL verify call (ServeEngine._spec_round)
    # goes through the donated decode program
    fn = jax.jit(inner, donate_argnums=())
    # a k=2 verify window: rows 0..2 at positions 5..7 of one
    # sequence, the SAME block table repeated per row, row 3 inactive
    bt = jnp.tile(jnp.asarray([[1, 2]], jnp.int32), (4, 1))
    pos = jnp.asarray([5, 6, 7, 0], jnp.int32)
    tok = jnp.asarray([3, 9, 4, 0], jnp.int32)
    act = jnp.asarray([True, True, True, False])
    return fn, (params, state, bt, pos, tok, act), mesh.axis_names


def _fp8_weight_decode_step():
    """The serve decode step with fp8 WEIGHT-streaming engaged
    (ISSUE 20): the block linear kernels quantized once to e4m3 with
    per-tensor scales (``serve.model.quantize_gpt_weights``) and read
    back through the fused dequant-matmul, whose blocks resolve from a
    builder-seeded tuned cache at trace time — so the Pallas
    ``fp8_matmul`` kernel (not the pure-XLA dequant reference the
    ineligible-shape path keeps) is what the zero-findings gate traces.
    The geometry is chosen 128-aligned on purpose: every linear is
    kernel-eligible, and a silently-dead lookup fails the builder's
    assert."""
    import tempfile

    import jax
    import jax.numpy as jnp
    from apex_tpu.models.gpt import GPT, GPTConfig
    from apex_tpu.serve import cache as cache_mod
    from apex_tpu.serve import model as serve_model
    from apex_tpu.tune import TuneCache, cache_key
    from apex_tpu.tune import runtime as tune_rt
    from apex_tpu.transformer import parallel_state as ps

    mesh, _, _ = _mesh_for()
    ps.destroy_model_parallel()
    cfg = GPTConfig(vocab_size=32, max_seq_len=32, hidden_size=128,
                    num_layers=1, num_heads=2, dtype=jnp.float32)
    params = GPT(cfg).init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 4), jnp.int32))["params"]
    qparams = serve_model.quantize_gpt_weights(cfg, params)
    ccfg = cache_mod.CacheConfig(num_layers=1, kv_heads=2, head_dim=64,
                                 num_pages=4, page_size=8)
    state = cache_mod.init_cache(ccfg)
    B = 2
    tmp = tempfile.mkdtemp(prefix="apexlint_tune_fp8mm_")
    cache = TuneCache(tmp)
    qkv_shape = None
    # one tuned entry per block-linear geometry (qkv/proj/fc1/fc2); the
    # decode batch is the m extent
    for k_dim, n_dim in ((128, 3 * 128), (128, 128), (128, cfg.ffn),
                         (cfg.ffn, 128)):
        shape = {"m": B, "k": k_dim, "n": n_dim, "itemsize": 4}
        if qkv_shape is None:
            qkv_shape = shape
        cache.put(cache_key("fp8_matmul", shape, "float32", {}),
                  {"block_k": 128, "block_n": 128})

    def run(params, state, bt, pos, tok, act):
        # block resolution is trace-time host work: point the lookup
        # at the builder's cache for the duration of the trace
        with tune_rt.override_cache_dir(tmp):
            got = tune_rt.resolve("fp8_matmul", qkv_shape, "float32",
                                  {}, policy="cache")
            assert got == {"block_k": 128, "block_n": 128}, \
                f"lint entrypoint fp8mm cache did not resolve: {got}"
            logits, state = serve_model.decode_forward(
                cfg, ccfg, params, state, bt, pos, tok, act,
                paged_impl="reference", interpret=True,
                autotune="cache")
        return logits, state

    # donate_argnums=() is the APX007 conscious opt-out: traced
    # abstractly only — the REAL step (ServeEngine._build_steps)
    # donates the cache pytree
    fn = jax.jit(run, donate_argnums=())
    bt = jnp.zeros((B, 4), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    tok = jnp.zeros((B,), jnp.int32)
    act = jnp.ones((B,), bool)
    return fn, (qparams, state, bt, pos, tok, act), mesh.axis_names


def _fused_layer_norm_step():
    """A cache-resolved fused-LayerNorm fwd+bwd step (ISSUE 13): the
    builder writes a tuned ``fused_layer_norm`` block into a throwaway
    autotune cache and the step resolves it at trace time, so the
    Pallas LN kernel pair (not the jnp shim the default path keeps) is
    what the zero-findings gate traces. The resolved block differs from
    any heuristic on purpose — a silently-dead lookup fails the
    builder's assert."""
    import tempfile

    import jax
    import jax.numpy as jnp
    from apex_tpu.ops.layer_norm import fused_layer_norm_affine
    from apex_tpu.tune import TuneCache, cache_key
    from apex_tpu.tune import runtime as tune_rt

    mesh, _, _ = _mesh_for()
    n, h = 32, 128
    tmp = tempfile.mkdtemp(prefix="apexlint_tune_ln_")
    shape = {"n": n, "h": h, "itemsize": 4}
    TuneCache(tmp).put(cache_key("fused_layer_norm", shape, "float32", {}),
                       {"block_r": 16})

    def run(x, w, b):
        # block resolution is trace-time host work: point the lookup at
        # the builder's cache for the duration of the trace
        with tune_rt.override_cache_dir(tmp):
            cfg = tune_rt.resolve("fused_layer_norm", shape, "float32",
                                  {}, policy="cache")
            assert cfg == {"block_r": 16}, \
                f"lint entrypoint LN cache did not resolve: {cfg}"

            def loss(x, w, b):
                y = fused_layer_norm_affine(x, w, b, (h,), block_r=16,
                                            interpret=True)
                return jnp.sum(y ** 2)

            return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)

    # abstract-trace-only entrypoint; the toy x/w/b double as the
    # returned grads, so donation would alias inputs the checker still
    # reads (APX007's conscious-opt-out form)
    fn = jax.jit(run, donate_argnums=())
    x = jnp.zeros((n, h), jnp.float32)
    w = jnp.ones((h,), jnp.float32)
    b = jnp.zeros((h,), jnp.float32)
    return fn, (x, w, b), mesh.axis_names


def _zero_fused_update_step():
    """A ZeRO tier-1/2 step with the fused multi-tensor update engaged
    (ISSUE 13 tentpole c): reduce-scatter of the flat grads, ONE Pallas
    sweep of the shard, all_gather of the fresh params — over the
    canonical data axis. The builder seeds the tuned cache so the
    kernel (not the flat-jnp twin) is in the gated jaxpr; like the
    zero3 entrypoint, the output is a cross-rank-invariant psummed
    fingerprint (APXJ101: shards under P() would record rank 0 only)."""
    import tempfile

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu.tune import TuneCache, cache_key
    from apex_tpu.tune import runtime as tune_rt
    from apex_tpu.transformer import parallel_state as ps
    from apex_tpu.zero.optimizer import ZeroOptimizer

    mesh, _, _ = _mesh_for()
    world = mesh.shape.get(ps.DATA_AXIS, 1)
    params = {"w1": jnp.zeros((8, 16), jnp.float32),
              "w2": jnp.zeros((16, 4), jnp.float32)}
    total = sum(x.size for x in jax.tree_util.tree_leaves(params))
    per = (-(-total // world) * world) // world   # padded flat / world
    tmp = tempfile.mkdtemp(prefix="apexlint_tune_mtu_")
    TuneCache(tmp).put(
        cache_key("multi_tensor_update", {"n": int(per), "itemsize": 4},
                  "float32", {"lamb": False}), {"block_n": 1024})

    def run(p, g):
        with tune_rt.override_cache_dir(tmp):
            opt = ZeroOptimizer(lr=1e-3, kind="adam", shard_params=False)
            cfg = opt._fused_cfg(per)
            assert cfg == {"block_n": 1024}, \
                f"lint entrypoint mtu cache did not resolve: {cfg}"
            state = opt.init(p)
            new_p, new_state = opt.apply(state, p, g)
        fp = sum(jnp.sum(leaf.astype(jnp.float32))
                 for leaf in jax.tree_util.tree_leaves((new_p, new_state)))
        return jax.lax.psum(fp, ps.DATA_AXIS)

    inner = shard_map(run, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                      check_vma=False)
    # donate_argnums=() is the APX007 conscious opt-out: traced
    # abstractly only — the REAL step donates through
    # zero.make_train_step(donate=True), whose caller owns the state
    fn = jax.jit(inner, donate_argnums=())
    grads = jax.tree.map(lambda x: x, params)
    return fn, (params, grads), mesh.axis_names


def _fused_lm_head_ce():
    """Vocab-parallel fused LM-head CE: the pmax/psum trio over the
    tensor axis, plus the Pallas kernels in interpret mode."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu.ops.lm_head_ce import fused_lm_head_cross_entropy
    from apex_tpu.transformer import parallel_state as ps

    mesh, tp, _ = _mesh_for(tp=2)
    v, h, n = 256, 32, 8

    def loss(x, emb, tgt):
        return jnp.sum(fused_lm_head_cross_entropy(
            x, emb, tgt, axis_name=ps.TENSOR_AXIS, interpret=True))

    fn = shard_map(loss, mesh=mesh,
                   in_specs=(P(), P("tensor"), P()), out_specs=P(),
                   check_vma=False)
    x = jnp.zeros((n, h), jnp.float32)
    emb = jnp.zeros((v, h), jnp.float32)
    tgt = jnp.zeros((n,), jnp.int32)
    return fn, (x, emb, tgt), mesh.axis_names


def _amp_o2_master_step():
    """The O2 master-weight hot loop (``amp.initialize(opt_level="O2")``
    + FusedAdam): bf16 model casts with fp32 output recast, fp32
    masters inside the optimizer, dynamic loss scaling with the
    overflow-skip cond — the program whose contracts the APXP30x
    precision analyzers gate (fp32 accumulation of the loss reduction,
    unscale-before-apply, skip=found_inf guarding the master write)."""
    import jax.numpy as jnp
    from apex_tpu import amp
    from apex_tpu.amp import scaler as scaler_mod
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state as ps

    _mesh_for()

    def apply_fn(p, x):
        return jnp.tanh(x @ p["w1"]) @ p["w2"]

    opt = FusedAdam(lr=1e-3)
    model, opt = amp.initialize(apply_fn, opt, opt_level="O2",
                                half_dtype=jnp.bfloat16,
                                loss_scale="dynamic", verbosity=0)

    def loss_fn(params, x, y):
        # AmpModel O2: params/inputs cast to bf16, outputs recast to
        # fp32 BEFORE this mean — the APXP301 contract by construction
        return jnp.mean((model.apply_fn(params, x) - y) ** 2)

    step = amp.make_train_step(loss_fn, opt, donate=False)
    params = {"w1": jnp.zeros((4, 8), jnp.float32),
              "w2": jnp.zeros((8, 2), jnp.float32)}
    opt_state = opt.init(params)
    sstate = scaler_mod.init_state()
    x = jnp.zeros((2, 4), jnp.float32)
    y = jnp.zeros((2, 2), jnp.float32)
    allowed = (ps.DATA_AXIS, ps.PIPELINE_AXIS, ps.TENSOR_AXIS,
               ps.CONTEXT_AXIS, ps.EXPERT_AXIS)
    return step, (params, opt_state, sstate, x, y), allowed


def _pp_1f1b_model_step():
    """The model-aware 1F1B schedule with its single-rank embed/head
    conds: embed_fn and loss_fn run under ``lax.cond`` branches taken
    by exactly one pipeline rank (predicates from ``axis_index`` over
    the pipeline axis), and the loss head performs a TENSOR-axis psum
    *inside* its cond — the vocab-parallel loss idiom and the
    known-hard APXJ106 true negative: the predicate is uniform over the
    tensor axis, so the tensor group is complete inside the branch,
    while a pipeline-axis collective in there would deadlock (which is
    exactly what APXJ106 + the runtime debug_axis_probe reject)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu.transformer import parallel_state as ps
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        forward_backward_pipelining_1f1b_model)

    mesh, _, _ = _mesh_for(tp=2, pp=2)
    nmb = 4

    def embed_fn(ep, mb):
        return mb * 1.0

    def stage_fn(w, h):
        return jnp.tanh(h * w["s"])

    def loss_fn(hp, h, mb):
        # tensor-axis reduction inside the single-rank head cond; the
        # microbatch keeps the reduced value loop-variant and the
        # square keeps its BACKWARD loop-variant too (a loss linear in
        # the psum would transpose to a collective over the constant
        # cotangent seed — a true APXJ102 on the toy, unlike any real
        # nonlinear loss head)
        r = jax.lax.psum((h * mb).astype(jnp.float32), ps.TENSOR_AXIS)
        return jnp.sum(r * r)

    def run(x, w):
        loss, grads = forward_backward_pipelining_1f1b_model(
            embed_fn, stage_fn, loss_fn,
            {"embed": {}, "stage": {"s": w}, "head": {}}, x, nmb)
        fp = loss + sum(jnp.sum(leaf.astype(jnp.float32))
                        for leaf in jax.tree_util.tree_leaves(grads))
        # per-rank loss/grads -> cross-rank-invariant fingerprint
        # (APXJ101: P() outputs must not still vary over manual axes)
        return jax.lax.psum(jax.lax.psum(fp, ps.PIPELINE_AXIS),
                            ps.TENSOR_AXIS)

    fn = shard_map(run, mesh=mesh, in_specs=(P(), P("pipeline")),
                   out_specs=P(), check_vma=False)
    x = jnp.ones((nmb, 2, 4), jnp.float32)
    w = jnp.ones((mesh.shape[ps.PIPELINE_AXIS],), jnp.float32)
    return fn, (x, w), mesh.axis_names


register_entrypoint("amp_train_step", _amp_train_step)
register_entrypoint("amp_train_step_monitored", _amp_train_step_monitored)
register_entrypoint("tensor_parallel_layers", _tensor_parallel_layers)
register_entrypoint("tp_overlap_layers", _tp_overlap_layers)
register_entrypoint("ddp_bucketed_step", _ddp_bucketed_step)
register_entrypoint("pipeline_schedule", _pipeline_schedule)
register_entrypoint("pp_zero_bubble_step", _pp_zero_bubble_step)
register_entrypoint("pp_zero_bubble_interleaved_step",
                    _pp_zero_bubble_interleaved_step)
register_entrypoint("zero3_train_step", _zero3_train_step)
register_entrypoint("fp8_train_step", _fp8_train_step)
register_entrypoint("flash_attention_tuned_step", _flash_attention_tuned_step)
register_entrypoint("fused_layer_norm_step", _fused_layer_norm_step)
register_entrypoint("zero_fused_update_step", _zero_fused_update_step)
register_entrypoint("profiled_train_step", _profiled_train_step)
register_entrypoint("memory_profiled_step", _memory_profiled_step)
register_entrypoint("serve_decode_step", _serve_decode_step)
register_entrypoint("serve_prefill_step", _serve_prefill_step)
register_entrypoint("serve_verify_step", _serve_verify_step)
register_entrypoint("fp8_weight_decode_step", _fp8_weight_decode_step)
register_entrypoint("fused_lm_head_ce", _fused_lm_head_ce)
register_entrypoint("amp_o2_master_step", _amp_o2_master_step)
register_entrypoint("pp_1f1b_model_step", _pp_1f1b_model_step)
