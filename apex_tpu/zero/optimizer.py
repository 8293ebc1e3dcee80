"""ZeroOptimizer: every ZeRO tier behind one switchboard.

Tier map (Rajbhandari et al. SC'20, apex ``contrib.optimizers``):

===========================  ==========================================
``shard_params=False``       ZeRO-1/2 — optimizer state (master fp32,
(tier 1/2, the                m, v) lives as ONE flat ``[total/world]``
``DistributedFusedAdam`` /    shard per rank; params and grads are
``DistributedFusedLAMB``      full: grads arrive whole and are
configuration)                ``psum_scatter``-ed, fresh params are
                              ``all_gather``-ed back every step
                              (optionally e5m2-quantized on the wire).
``shard_params=True``        ZeRO-3 — parameters are ALSO sharded
(tier 3, FSDP semantics)      (per-leaf, ``apex_tpu.zero.core``); the
                              backward hands this optimizer its summed
                              gradient SHARDS (the ``zero_gather``
                              conjugate), the update runs on the local
                              partition only, and no gather happens
                              here at all — the next forward's
                              transient materialization is the only
                              full-param traffic.
===========================  ==========================================

Both tiers run the SAME element math (``zero/update.py``) and the same
accounted collectives (``zero/comm.py``); ``contrib.optimizers``'
``DistributedFusedAdam``/``DistributedFusedLAMB`` are subclasses
pinning ``shard_params=False`` — one implementation, no drift.

Memory per chip (P params, world N, fp32 master+m+v, bf16/fp32 model
dtype d): dense DDP ``(d+12)P``; tier 2 ``dP + 12P/N``; tier 3
``(d+12)P/N`` (+ the transient gathered tree during a step). The
``zero_sharded_step`` bench records the measured version of this table.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from apex_tpu import _compat
from apex_tpu.zero import comm as _comm
from apex_tpu.zero.core import ZeroSpec, pad_to_multiple
from apex_tpu.zero.update import (ShardedAdamState, ShardedLambState,
                                  Zero3State, adam_shard_step,
                                  lamb_shard_term, lamb_trust_ratio)
from apex_tpu.utils.flat import FlatBuffer

__all__ = ["ZeroOptimizer", "ShardedAdamState", "ShardedLambState",
           "Zero3State"]


def _cast_fresh(x, dtype):
    """astype that never aliases (master and model params must stay
    distinct buffers — see ``optimizers/base.py``)."""
    if x.dtype == dtype:
        return jnp.array(x, copy=True)
    return x.astype(dtype)


class ZeroOptimizer:
    """Sharded fused Adam(W)/LAMB over the ``axis_name`` mesh axis.

    Run ``init``/``apply`` inside ``shard_map`` with the axis bound
    (world=1 degrades to a plain fused update). ``kind`` selects the
    update ("adam" or "lamb"); ``shard_params`` selects the tier (see
    the module table). Tier 3 additionally needs the
    :class:`~apex_tpu.zero.core.ZeroSpec` of the resident tree —
    pass it to ``init``/``apply`` or construct with ``spec=``.
    """

    def __init__(self, lr=1e-3, *, kind: str = "adam",
                 shard_params: bool = True,
                 bias_correction: bool = True, betas=(0.9, 0.999),
                 eps: float = 1e-8, weight_decay: float = 0.0,
                 adam_w_mode: bool = True, gradient_average: bool = True,
                 max_grad_norm: float | None = None,
                 use_nvlamb: bool = False,
                 axis_name: str = "data", overlap_comm: bool = False,
                 compress_allgather: bool | str = False,
                 spec: ZeroSpec | None = None,
                 autotune: str | None = None):
        if kind not in ("adam", "lamb"):
            raise ValueError(f"kind must be 'adam' or 'lamb', got {kind!r}")
        self.kind = kind
        self.shard_params = shard_params
        self.lr = lr
        self.bias_correction = bias_correction
        self.betas = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.adam_w_mode = adam_w_mode
        self.gradient_average = gradient_average
        self.max_grad_norm = max_grad_norm
        self.use_nvlamb = use_nvlamb
        self.axis_name = axis_name
        self.overlap_comm = overlap_comm
        # True = the reference's raw e5m2 cast (bitwise-documented);
        # "scaled" = the amp O4 codec (amax-scaled before the cast —
        # survives values outside e5m2's range; zero/comm.py)
        if compress_allgather not in (False, True, "scaled"):
            raise ValueError(
                f"compress_allgather must be False, True or 'scaled', "
                f"got {compress_allgather!r}")
        self.compress_allgather = compress_allgather
        # fused multi-tensor update resolution (zero/fused_update.py):
        # explicit policy > $APEX_TPU_AUTOTUNE > "cache"; no tuned entry
        # (or "off") keeps the historical tree-map/flat-jnp update
        # bit-for-bit. Validated eagerly so a typo fails at construction.
        if autotune is not None:
            from apex_tpu.tune import runtime as _tune_rt
            _tune_rt.resolve_policy(autotune)
        self.autotune = autotune
        self._zspec = spec
        self._spec: FlatBuffer | None = None   # tier-1/2 flat layout

    # -- shared plumbing ----------------------------------------------------
    def _world(self):
        return _comm._world_of(self.axis_name)

    def _hyper(self):
        return dict(betas=self.betas, eps=self.eps,
                    weight_decay=self.weight_decay,
                    adam_w_mode=self.adam_w_mode,
                    bias_correction=self.bias_correction)

    def configure_amp(self, properties, scaler):
        """amp.initialize hook: the fp32 master shard IS the O2 master-
        weight store, so there is nothing to switch on — just keep the
        scaler for the stateful conveniences."""
        self._scaler = scaler

    def _fused_cfg(self, n: int):
        """Tuned ``multi_tensor_update`` chunk config for an ``n``-element
        fp32 sweep, or ``None`` (use the tree-map/flat-jnp path). Runs at
        trace time; resolution order and telemetry are the shared
        ``tune.runtime`` contract the flash/LN/CE kernels use."""
        from apex_tpu.tune import runtime as _tune_rt
        policy = _tune_rt.resolve_policy(self.autotune)
        if policy == "off" or n <= 0:
            return None
        return _tune_rt.resolve(
            "multi_tensor_update", {"n": int(n), "itemsize": 4},
            "float32", {"lamb": self.kind == "lamb"}, policy=policy,
            interpret=_compat.resolve_interpret(None))

    # -- dispatch -----------------------------------------------------------
    def init(self, params, spec: ZeroSpec | None = None):
        """Tier 1/2: ``params`` is the full tree. Tier 3: ``params`` is
        the RESIDENT tree from ``zero_shard`` (fp32 — master precision
        is set here) and ``spec`` its ZeroSpec."""
        if self.shard_params:
            return self._init3(params, spec)
        return self._init_flat(params)

    def apply(self, state, params, grads, skip=None, lr=None,
              spec: ZeroSpec | None = None):
        """One sharded step; returns ``(new_params, new_state)``.

        Tier 1/2: full ``params``/``grads`` in, full params out (the
        gather lives here). Tier 3: resident shards and gradient shards
        in, fresh resident shards out (no gather — the update never
        leaves the partition)."""
        if self.shard_params:
            return self._apply3(state, params, grads, skip=skip, lr=lr,
                                spec=spec)
        return self._apply_flat(state, params, grads, skip=skip, lr=lr)

    # ======================================================================
    # tier 1/2: flat [total/world] shard, full params at the boundary
    # ======================================================================
    def _init_flat(self, params):
        self._spec = FlatBuffer.from_tree(params)
        world = self._world()
        flat = pad_to_multiple(
            self._spec.pack(params, dtype=jnp.float32), world)
        per = flat.shape[0] // world
        if world > 1:
            rank = jax.lax.axis_index(self.axis_name)
            shard = jax.lax.dynamic_slice_in_dim(flat, rank * per, per)
        else:
            shard = flat
        cls = ShardedAdamState if self.kind == "adam" else ShardedLambState
        return cls(step=jnp.asarray(0, jnp.int32), master_shard=shard,
                   m_shard=jnp.zeros_like(shard),
                   v_shard=jnp.zeros_like(shard))

    # per-leaf ranges of the flat buffer intersected with the dynamic
    # per-rank shard window — the LAMB trust-ratio machinery
    # (see ``DistributedFusedLAMB``'s docstring for the design notes)
    def _leaf_starts_in_shard(self, base, per):
        """Per-leaf clipped start positions in shard coordinates (the
        piecewise trust-ratio ramp's scatter indices)."""
        offs = jnp.asarray(self._spec.offsets, jnp.int32)
        return jnp.clip(offs - base, 0, per)

    def _range_sums(self, x, base, per):
        """Per-leaf sums of the leaf∩shard ranges, computed EXACTLY.

        Each leaf intersects the shard in a contiguous range of length
        ≤ min(leaf_size, per) — a *static* bound, so a dynamic-start
        static-length window plus an in-window mask gives a plain masked
        reduction per leaf. (A cumsum-difference formulation cancels
        catastrophically in f32: a 256-element leaf after a 2M-element
        prefix summed to exactly 0.)
        """
        sums = []
        for off, size in zip(self._spec.offsets, self._spec.sizes):
            L = min(size, per)
            s = jnp.clip(off - base, 0, per)          # dynamic, in-shard
            e = jnp.clip(off + size - base, 0, per)
            w = jnp.clip(s, 0, per - L)               # window fits: static L
            win = jax.lax.dynamic_slice_in_dim(x, w, L)
            q = w + jnp.arange(L, dtype=jnp.int32)
            mask = (q >= s) & (q < e)
            sums.append(jnp.sum(jnp.where(mask, win, 0.0)))
        return jnp.stack(sums)

    @staticmethod
    def _piecewise(values, starts, per):
        """[per] vector equal to values[i] on leaf i's shard range —
        a delta scatter (n tiny adds) + cumsum; positions past the last
        leaf (alignment padding) carry the last value, harmless because
        pad slots of p/update are zero."""
        deltas = jnp.diff(values, prepend=jnp.zeros((1,), values.dtype))
        d = jnp.zeros((per + 1,), values.dtype).at[starts].add(deltas)
        return jnp.cumsum(d[:per])

    def _apply_flat(self, state, params, grads, skip=None, lr=None):
        if self._spec is None:
            self._spec = FlatBuffer.from_tree(params)
        spec = self._spec
        world = self._world()
        lr = jnp.asarray(self.lr if lr is None else lr, jnp.float32)
        if skip is None:
            skip = jnp.asarray(False)

        flat_g = pad_to_multiple(spec.pack(grads, dtype=jnp.float32), world)
        per = flat_g.shape[0] // world
        # reduce_scatter: each rank receives the summed shard it owns
        # (distributed_fused_adam.py:409 _pipeline_block_reductions)
        g_shard = _comm.reduce_scatter_flat(flat_g, self.axis_name,
                                            overlap_comm=self.overlap_comm)
        if self.gradient_average and world > 1:
            g_shard = g_shard / world
        if world > 1:
            rank = jax.lax.axis_index(self.axis_name)
        else:
            rank = 0
        base = rank * per if world > 1 else 0

        if self.kind == "lamb":
            starts = self._leaf_starts_in_shard(base, per)
            # global grad norm + clip (distributed_fused_lamb.py:665-699)
            gsq = _comm.psum_flat(jnp.sum(g_shard * g_shard), self.axis_name)
            gnorm = jnp.sqrt(gsq)
            if self.max_grad_norm and self.max_grad_norm > 0:
                g_shard = g_shard / jnp.maximum(
                    1.0, gnorm / self.max_grad_norm)

        fused = self._fused_cfg(per)

        def _do(state=state, g=g_shard, lr=lr):
            step = state.step + 1
            p = state.master_shard
            if self.kind == "adam":
                if fused is not None:
                    from apex_tpu.zero.fused_update import fused_shard_update
                    new_p, m, v = fused_shard_update(
                        p, g, state.m_shard, state.v_shard, step,
                        kind="adam", lr=lr, block_n=fused["block_n"],
                        **self._hyper())
                else:
                    new_p, m, v = adam_shard_step(
                        p, g, state.m_shard, state.v_shard, step, lr=lr,
                        **self._hyper())
                return type(state)(step, new_p, m, v)
            if fused is not None:
                from apex_tpu.zero.fused_update import fused_shard_update
                upd, m, v = fused_shard_update(
                    p, g, state.m_shard, state.v_shard, step,
                    kind="lamb", lr=lr,
                    grad_averaging=self.gradient_average,
                    block_n=fused["block_n"], **self._hyper())
            else:
                upd, m, v = lamb_shard_term(
                    p, g, state.m_shard, state.v_shard, step,
                    grad_averaging=self.gradient_average, **self._hyper())
            # per-tensor norms: shard-local contiguous-range sums +
            # cross-shard psum (the allgather of update norms, :722-778)
            w_sq = _comm.psum_flat(self._range_sums(p * p, base, per),
                                   self.axis_name)
            u_sq = _comm.psum_flat(self._range_sums(upd * upd, base, per),
                                   self.axis_name)
            ratio = lamb_trust_ratio(jnp.sqrt(w_sq), jnp.sqrt(u_sq),
                                     use_nvlamb=self.use_nvlamb,
                                     weight_decay=self.weight_decay)
            new_p = p - lr * self._piecewise(ratio, starts, per) * upd
            return type(state)(step, new_p, m, v)

        new_state = jax.lax.cond(skip, lambda: state, _do)

        # all_gather the fresh params (distributed_fused_adam.py:477),
        # optionally through the e5m2 quantized-broadcast helper
        if self.compress_allgather:
            flat_new = _comm.quantized_all_gather(
                new_state.master_shard, self.axis_name,
                out_dtype=jnp.float32, overlap_comm=self.overlap_comm,
                scaled=(self.compress_allgather == "scaled"))
        else:
            flat_new = _comm.all_gather_flat(
                new_state.master_shard, self.axis_name,
                overlap_comm=self.overlap_comm).astype(jnp.float32)
        return spec.unpack(flat_new[:spec.total]), new_state

    # tier-1/2 elastic checkpointing (contrib.optimizers.zero_state)
    def gather_state(self, state):
        """Topology-independent full state for checkpointing (inside
        ``shard_map``); see ``apex_tpu.contrib.optimizers.zero_state``."""
        from apex_tpu.contrib.optimizers.zero_state import gather_zero_state
        return gather_zero_state(self, state)

    def shard_state(self, full_state, params=None):
        """Local shard of a gathered state under the CURRENT mesh — the
        dp=8 -> dp=4 resume path (``distributed_fused_lamb.py:139``)."""
        from apex_tpu.contrib.optimizers.zero_state import shard_zero_state
        return shard_zero_state(self, full_state, params)

    # ======================================================================
    # tier 3: per-leaf resident shards, no gather anywhere in the step
    # ======================================================================
    def _spec3(self, spec: ZeroSpec | None) -> ZeroSpec:
        if spec is not None:
            self._zspec = spec
        if self._zspec is None:
            raise ValueError(
                "ZeroOptimizer(shard_params=True) needs the ZeroSpec of "
                "the resident tree — pass spec= here or at construction "
                "(ZeroShardedModel.shard builds it)")
        return self._zspec

    @staticmethod
    def _is_float(x) -> bool:
        return jnp.issubdtype(x.dtype, jnp.floating)

    def _init3(self, shards, spec: ZeroSpec | None = None):
        spec = self._spec3(spec)

        def master(x):
            return _cast_fresh(x, jnp.float32) if self._is_float(x) else x

        def slot(x):
            return jnp.zeros(x.shape, jnp.float32) if self._is_float(x) \
                else jnp.zeros((0,), jnp.float32)

        return Zero3State(
            step=jnp.asarray(0, jnp.int32),
            master=jax.tree.map(master, shards),
            m=jax.tree.map(slot, shards),
            v=jax.tree.map(slot, shards),
        )

    def _masked_psum_merge(self, partials: list, spec: ZeroSpec):
        """Exact cross-rank per-leaf reductions in ONE psum: sharded
        leaves' partial sums need the cross-shard psum, replicated
        leaves' are already whole (every rank computed the identical
        value) and must be counted ONCE — merge by the static mask."""
        stacked = jnp.stack(partials)
        summed = _comm.psum_flat(stacked, self.axis_name)
        mask = jnp.asarray(np.asarray(spec.sharded, bool))
        return jnp.where(mask, summed, stacked)

    def _apply3(self, state: Zero3State, shards, grads, skip=None, lr=None,
                spec: ZeroSpec | None = None):
        spec = self._spec3(spec)
        world = self._world()
        lr = jnp.asarray(self.lr if lr is None else lr, jnp.float32)
        if skip is None:
            skip = jnp.asarray(False)

        p_leaves = jax.tree.leaves(shards)
        g_leaves = [g.astype(jnp.float32) if self._is_float(g) else g
                    for g in jax.tree.leaves(grads)]
        if self.gradient_average and world > 1:
            g_leaves = [g / world if self._is_float(g) else g
                        for g in g_leaves]
        m_leaves = jax.tree.leaves(state.m)
        v_leaves = jax.tree.leaves(state.v)
        mast_leaves = jax.tree.leaves(state.master)
        is_float = [self._is_float(g) for g in g_leaves]
        floats = [i for i, f in enumerate(is_float) if f]

        if self.kind == "lamb":
            gsq = self._masked_psum_merge(
                [jnp.sum(g_leaves[i] * g_leaves[i]) if is_float[i]
                 else jnp.zeros((), jnp.float32)
                 for i in range(len(g_leaves))], spec)
            gnorm = jnp.sqrt(jnp.sum(gsq))
            if self.max_grad_norm and self.max_grad_norm > 0:
                clip = jnp.maximum(1.0, gnorm / self.max_grad_norm)
                g_leaves = [g_leaves[i] / clip if is_float[i]
                            else g_leaves[i] for i in range(len(g_leaves))]

        # the fused multi-tensor path sweeps ALL float leaves as one
        # concatenated flat buffer — one kernel instead of a tree-map of
        # per-leaf op chains (elementwise, so concatenation preserves
        # bit-parity with the per-leaf form under compilation)
        fused = self._fused_cfg(sum(mast_leaves[i].size for i in floats)) \
            if floats else None

        def _fused_leaves(kind, step, lr):
            from apex_tpu.zero.fused_update import fused_shard_update
            def cat(ls):
                return jnp.concatenate([ls[i].reshape(-1) for i in floats])
            fo, fm, fv = fused_shard_update(
                cat(mast_leaves), cat(g_leaves), cat(m_leaves),
                cat(v_leaves), step, kind=kind, lr=lr,
                grad_averaging=self.gradient_average,
                block_n=fused["block_n"], **self._hyper())
            out, off = {}, 0
            for i in floats:
                sz = mast_leaves[i].size
                shp = mast_leaves[i].shape
                out[i] = (fo[off:off + sz].reshape(shp),
                          fm[off:off + sz].reshape(shp),
                          fv[off:off + sz].reshape(shp))
                off += sz
            return out

        def _do():
            step = state.step + 1
            new_master = list(mast_leaves)
            new_m, new_v = list(m_leaves), list(v_leaves)
            if self.kind == "adam":
                if fused is not None:
                    for i, (o, nm, nv) in _fused_leaves("adam", step,
                                                        lr).items():
                        new_master[i], new_m[i], new_v[i] = o, nm, nv
                else:
                    for i in floats:
                        new_master[i], new_m[i], new_v[i] = adam_shard_step(
                            mast_leaves[i], g_leaves[i], m_leaves[i],
                            v_leaves[i], step, lr=lr, **self._hyper())
            else:
                upds = {}
                if fused is not None:
                    for i, (o, nm, nv) in _fused_leaves("lamb", step,
                                                        lr).items():
                        upds[i], new_m[i], new_v[i] = o, nm, nv
                else:
                    for i in floats:
                        upds[i], new_m[i], new_v[i] = lamb_shard_term(
                            mast_leaves[i], g_leaves[i], m_leaves[i],
                            v_leaves[i], step,
                            grad_averaging=self.gradient_average,
                            **self._hyper())
                # whole-logical-tensor norms from shard partials
                zero = jnp.zeros((), jnp.float32)
                w_sq = self._masked_psum_merge(
                    [jnp.sum(mast_leaves[i] ** 2) if is_float[i] else zero
                     for i in range(len(g_leaves))], spec)
                u_sq = self._masked_psum_merge(
                    [jnp.sum(upds[i] ** 2) if is_float[i] else zero
                     for i in range(len(g_leaves))], spec)
                ratio = lamb_trust_ratio(jnp.sqrt(w_sq), jnp.sqrt(u_sq),
                                         use_nvlamb=self.use_nvlamb,
                                         weight_decay=self.weight_decay)
                for i in floats:
                    new_master[i] = mast_leaves[i] - lr * ratio[i] * upds[i]
            t = spec.treedef
            return Zero3State(step,
                              jax.tree.unflatten(t, new_master),
                              jax.tree.unflatten(t, new_m),
                              jax.tree.unflatten(t, new_v))

        new_state = jax.lax.cond(skip, lambda: state, _do)

        # fresh resident shards in the MODEL dtypes (fp32 master ->
        # bf16/fp16 under amp O2) — the tier-3 analog of the param
        # all_gather is: nothing. The next forward's transient
        # zero_gather is the only full-param traffic.
        new_shards = jax.tree.unflatten(spec.treedef, [
            _cast_fresh(nm, p.dtype) if self._is_float(p) else p
            for nm, p in zip(jax.tree.leaves(new_state.master), p_leaves)])
        return new_shards, new_state
