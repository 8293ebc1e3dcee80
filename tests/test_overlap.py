"""Explicit comms/compute overlap (``apex_tpu/parallel/overlap.py``) on
the 8-device virtual mesh.

Three contracts, per the PR-4 acceptance bar:

1. **Parity**: the collective-matmul primitives and the bucketed
   gradient all-reduce compute the same values as the blocking forms
   they replace — fwd and bwd, fp32 and bf16 (``all_gather_matmul``
   forward and the bucketed psums bitwise; the reduce-scatter ring, which
   is also the gather form's backward, reassociates the cross-rank sum,
   so dtype tolerance there).
2. **Structure**: the sequence-parallel layers at ``world > 1`` trace
   the decomposed form — no ``reduce_scatter``, the expected number of
   ``ppermute``s, ONE ``all_gather`` a collective (the device's: a gather
   ring lost on the chip) — and everywhere else (tp=1, no SP) the
   program the layers traced before they took the ring from what they
   see; one fused ``psum`` per bucket for DDP, and with its
   ``overlap_comm`` off (the default) the program is byte-identical to
   the pre-overlap path (asserted as str(jaxpr) equality against the
   hand-written loop).
3. **Accounting**: trace-time ``ppermute`` bytes/counts land in the
   monitor's collective table (which previously only ever saw
   psum/all_gather/psum_scatter).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu._compat import shard_map
from apex_tpu.lint.jaxpr_checks import iter_eqns
from apex_tpu.parallel import (
    DistributedDataParallel, accumulate_gradients, allreduce_gradients,
    bucketed_allreduce)
from apex_tpu.parallel import overlap as overlap_mod
from apex_tpu.parallel.overlap import (
    all_gather_matmul, bucket_partition, matmul_reduce_scatter)
from apex_tpu.transformer import parallel_state as ps
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear, RowParallelLinear, mappings)

TP = 4


@pytest.fixture
def tp_mesh():
    ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(tensor_model_parallel_size_=TP)
    yield mesh
    ps.destroy_model_parallel()


def _data_mesh():
    return Mesh(np.array(jax.devices()), ("data",))


def _eqn_count(jaxpr, name):
    return sum(1 for e in iter_eqns(jaxpr) if e.primitive.name == name)


def _normalized(jaxpr_str):
    """jaxpr text with memory addresses scrubbed: custom_vjp eqn params
    embed bound-function reprs whose id changes per trace."""
    import re
    return re.sub(r"0x[0-9a-f]+", "0xADDR", jaxpr_str)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# collective-matmul primitives vs the blocking mappings path
# ---------------------------------------------------------------------------


def _ring_case(tp, dtype, form, dim, batch):
    """fwd + bwd of one collective matmul and of the blocking mappings
    path it replaces, on a tensor axis of ``tp`` devices."""
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tensor",))
    rng = np.random.RandomState(tp * 10 + dim)
    s, h, n = 8, 16, 12          # s is the FULL sequence
    shape = (s, batch, h) if dim == 0 else (batch, s, h)
    x = jnp.asarray(rng.randn(*shape), dtype)
    w = jnp.asarray(rng.randn(h, n) * 0.3, dtype)
    seq = P("tensor") if dim == 0 else P(None, "tensor")
    gather = form == "gather"

    def dot(a, w):
        return jnp.dot(a, w, preferred_element_type=jnp.float32).astype(
            a.dtype)

    if gather:
        def plain(x, w):
            return dot(mappings.gather_from_sequence_parallel_region(
                x, "tensor", dim), w)

        def fused(x, w):
            return all_gather_matmul(x, w, "tensor", dim)
    else:
        def plain(x, w):
            return mappings.reduce_scatter_to_sequence_parallel_region(
                dot(x, w), "tensor", dim)

        def fused(x, w):
            return matmul_reduce_scatter(x, w, "tensor", dim)

    def both(x, w):
        def run(fn):
            def loss(x, w):
                l = jnp.sum(fn(x, w).astype(jnp.float32) ** 2)
                return l if gather else jax.lax.psum(l, "tensor")
            l, grads = jax.value_and_grad(loss, argnums=(0, 1))(x, w)
            return l, *grads
        return run(plain), run(fused)

    x_spec = seq if gather else P()
    out = (P(), x_spec, P())
    return shard_map(both, mesh=mesh, in_specs=(x_spec, P()),
                     out_specs=(out, out), check_vma=False)(x, w)


# every combination with an even batch (the payload's halves travel
# opposite ways), and a batch of 1 (no dimension to cut: whole payloads,
# one way) for both forms and both layouts
_RING_CASES = [(tp, dt, form, dim, 4)
               for tp in (2, 4) for dt in (jnp.float32, jnp.bfloat16)
               for form in ("gather", "scatter") for dim in (0, 1)]
_RING_CASES += [(4, jnp.float32, form, dim, 1)
                for form in ("gather", "scatter") for dim in (0, 1)]


@pytest.mark.parametrize(
    "tp,dtype,form,dim,batch", _RING_CASES,
    ids=[f"tp{c[0]}-{jnp.dtype(c[1]).name}-{c[2]}-dim{c[3]}-b{c[4]}"
         for c in _RING_CASES])
def test_two_way_ring_matches_blocking(tp, dtype, form, dim, batch):
    """The collective matmuls against the blocking form, forward and
    gradients: fp32 tight, bf16 to the dtype's tolerance. The gather form
    is the device's all-gather and the same contraction (exact forward),
    with the two-way scatter ring in its backward; the scatter form is
    that ring forward, and reassociates the cross-rank sum."""
    lanes = overlap_mod._lanes((8, batch, 16) if dim == 0
                               else (batch, 8, 16), dim)
    assert len(lanes) == (2 if batch % 2 == 0 else 1)
    (l0, dx0, dw0), (l1, dx1, dw1) = _ring_case(tp, dtype, form, dim, batch)
    if form == "gather":
        np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
    else:
        np.testing.assert_allclose(float(l0), float(l1), **_tol(dtype))
    for want, got in ((dx0, dx1), (dw0, dw1)):
        want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
        if dtype == jnp.bfloat16:
            # a sum of bf16-rounded terms, reassociated: a few eps of the
            # largest entry, whatever the entry
            assert np.max(np.abs(want - got)) <= 2e-2 * np.max(np.abs(want))
        else:
            np.testing.assert_allclose(want, got, **_tol(dtype))


def test_primitive_validation():
    with pytest.raises(ValueError, match="weight must be 2D"):
        all_gather_matmul(jnp.ones((4, 8)), jnp.ones((8, 2, 1)), "tensor", 0)
    with pytest.raises(ValueError, match="contraction mismatch"):
        all_gather_matmul(jnp.ones((4, 8)), jnp.ones((7, 2)), "tensor", 0)
    with pytest.raises(ValueError, match="non-contraction axis"):
        matmul_reduce_scatter(jnp.ones((4, 8)), jnp.ones((8, 2)), "tensor", 1)


# ---------------------------------------------------------------------------
# layer wiring: ``sequence_parallel=True`` at world > 1 IS the scatter ring
# ---------------------------------------------------------------------------


def _sp_layers(h=16, n=32, dim=0):
    col = ColumnParallelLinear(input_size=h, output_size=n,
                               gather_output=False, sequence_parallel=True,
                               sequence_dim=dim)
    row = RowParallelLinear(input_size=n, output_size=h,
                            input_is_parallel=True, sequence_parallel=True,
                            sequence_dim=dim)
    return col, row


def _init_in(mesh, layer, spec, x):
    """The layer's variables, initialised under ``mesh`` ahead of the
    program that is counted or compared (an init is a forward of its own)."""
    return shard_map(lambda xs: layer.init(jax.random.PRNGKey(0), xs),
                     mesh=mesh, in_specs=(spec,), out_specs=P(),
                     check_vma=False)(x)


def _blocking_block(vc, vr, xs, dim=0):
    """The column -> row sandwich on the blocking mappings called
    directly: what the layers ran before they took the ring."""
    def dot(a, k):
        return jnp.dot(a, k.astype(a.dtype),
                       preferred_element_type=jnp.float32).astype(a.dtype)

    vc, vr = vc["params"], vr["params"]
    hid = dot(mappings.gather_from_sequence_parallel_region(
        xs, "tensor", dim), vc["kernel"]) + vc["bias"]
    return mappings.reduce_scatter_to_sequence_parallel_region(
        dot(hid, vr["kernel"]), "tensor", dim) + vr["bias"]


@pytest.mark.slow
def test_sp_layers_overlap_matches_plain(tp_mesh):
    """Column→Row sequence-parallel sandwich: the layers agree with the
    blocking mappings called directly on loss and grads (the only
    reassociation is in the Row reduce).

    Slow tier (52 s of tp=4 compile on CPU): tier-1 keeps the same
    fwd+bwd numerics covered at the primitive level
    (test_two_way_ring_matches_blocking) and the layer wiring covered
    structurally (test_sp_layers_trace_the_ring)."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(8, 16), jnp.float32)
    col, row = _sp_layers()
    vc = _init_in(tp_mesh, col, P("tensor"), x)
    vr = _init_in(tp_mesh, row, P(), jnp.zeros((8, 32 // TP), jnp.float32))

    def run(block):
        def inner(xs):
            def loss(xs):
                return jnp.sum(block(vc, vr, xs) ** 2)
            return loss(xs), jax.grad(loss)(xs)

        return shard_map(inner, mesh=tp_mesh, in_specs=(P("tensor"),),
                         out_specs=(P(), P("tensor")), check_vma=False)(x)

    l0, g0 = run(_blocking_block)
    l1, g1 = run(lambda vc, vr, xs: row.apply(vr, col.apply(vc, xs)))
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                               rtol=1e-5, atol=1e-6)


def _collectives(fn, mesh, in_specs, args, out_specs):
    jx = jax.make_jaxpr(shard_map(fn, mesh=mesh, in_specs=in_specs,
                                  out_specs=out_specs, check_vma=False))(*args)
    return {name: _eqn_count(jx.jaxpr, name)
            for name in ("all_gather", "reduce_scatter", "ppermute")}


# (input shape, sequence_dim, lanes): a 2-D [s, h] payload has no dimension
# to cut and travels whole, one way; an even batch travels in two halves
_LAYOUTS = {"s_h": ((8, 16), 0, 1), "b_s_h": ((2, 8, 16), 1, 2)}


@pytest.mark.parametrize("layout", list(_LAYOUTS))
def test_sp_layers_trace_the_ring(tp_mesh, layout):
    """SP layers at world > 1, forward AND backward: no blocking
    reduce-scatter of an activation — the row layer's forward and the
    column layer's backward (``dx``) ring, (tp - 1) hops a lane each — and
    ONE all-gather a collective, the device's: the column layer's forward
    (its gathered x is kept for dw) and the row layer's backward (its
    gathered cotangent feeds dx and dw)."""
    shape, dim, lanes = _LAYOUTS[layout]
    x = jnp.asarray(np.random.RandomState(4).randn(*shape), jnp.float32)
    spec = P("tensor") if dim == 0 else P(None, "tensor")
    col, row = _sp_layers(dim=dim)
    vc = _init_in(tp_mesh, col, spec, x)
    hid = jnp.zeros(shape[:-1] + (32 // TP,), jnp.float32)
    vr = _init_in(tp_mesh, row, P(), hid)

    def step(vc, vr, xs):
        return jax.value_and_grad(lambda vc, vr, xs: jnp.sum(
            row.apply(vr, col.apply(vc, xs)) ** 2), argnums=(0, 1, 2))(
                vc, vr, xs)

    got = _collectives(step, tp_mesh, (P(), P(), spec), (vc, vr, x),
                       (P(), (P(), P(), spec)))
    hops = (TP - 1) * lanes
    assert got == {"all_gather": 2, "reduce_scatter": 0,
                   "ppermute": 2 * hops}
    # before: the blocking pair forward and its conjugates backward
    before = _collectives(
        lambda vc, vr, xs: jax.grad(lambda xs: jnp.sum(
            _blocking_block(vc, vr, xs, dim) ** 2))(xs),
        tp_mesh, (P(), P(), spec), (vc, vr, x), spec)
    assert before == {"all_gather": 2, "reduce_scatter": 2, "ppermute": 0}


def test_row_backward_gathers_its_cotangent_once(tp_mesh):
    """The row layer's backward needs the gathered cotangent twice
    (``dx = g @ w^T``, ``dw = x^T @ g``) and gathers it ONCE."""
    x = jnp.asarray(np.random.RandomState(5).randn(2, 8, 32 // TP),
                    jnp.float32)
    _, row = _sp_layers(dim=1)
    v = _init_in(tp_mesh, row, P(), x)

    def both(v, x):
        return jax.value_and_grad(
            lambda v, x: jnp.sum(row.apply(v, x) ** 2), argnums=(0, 1))(v, x)

    n_fwd = _collectives(row.apply, tp_mesh, (P(), P()), (v, x),
                         P(None, "tensor"))
    n_both = _collectives(both, tp_mesh, (P(), P()), (v, x), P())
    assert n_fwd == {"all_gather": 0, "reduce_scatter": 0,
                     "ppermute": 2 * (TP - 1)}
    assert n_both == {"all_gather": 1, "reduce_scatter": 0,
                      "ppermute": 2 * (TP - 1)}


def test_sp_linears_count_themselves(tp_mesh):
    """Each SP linear call at world > 1 counts its two collectives at
    trace time by the form they take: the scatter that rings
    (``tp/sp_linear_ring``) and the gather the device runs whole
    (``tp/sp_linear_blocking``): what ``sp_ring_share`` of the benchmark
    reads. Nothing is counted without SP."""
    from apex_tpu import monitor

    x = jnp.asarray(np.random.RandomState(6).randn(8, 16), jnp.float32)
    col, row = _sp_layers()

    def block(xs):
        hid = col.apply(col.init(jax.random.PRNGKey(0), xs), xs)
        return row.apply(row.init(jax.random.PRNGKey(1), hid), hid)

    rec = monitor.Recorder(name="sp-count")
    with monitor.attached(rec):
        jax.make_jaxpr(shard_map(block, mesh=tp_mesh, in_specs=(P("tensor"),),
                                 out_specs=P("tensor"), check_vma=False))(x)
        # init + apply of two layers: four calls, a ring and a gather each
        four = {"tp/sp_linear_ring": 4, "tp/sp_linear_blocking": 4}
        assert rec.counters() == four
        plain = ColumnParallelLinear(input_size=16, output_size=32)
        jax.make_jaxpr(shard_map(
            lambda xs: plain.apply(plain.init(jax.random.PRNGKey(0), xs), xs),
            mesh=tp_mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False))(x)
        assert rec.counters() == four


# ---------------------------------------------------------------------------
# the bypass: everywhere but SP at world > 1 the layers are the program
# they were
# ---------------------------------------------------------------------------


def _parent_column(p, x, world):
    """``ColumnParallelLinear.__call__`` of the commit before the layers
    took the ring (2d6d991), sequence parallelism inactive."""
    if world > 1:
        x = mappings.copy_to_tensor_model_parallel_region(x, "tensor")
    y = jnp.dot(x, p["kernel"].astype(x.dtype),
                preferred_element_type=jnp.float32).astype(x.dtype)
    y = y + p["bias"].astype(y.dtype)
    if world > 1:
        y = mappings.gather_from_tensor_model_parallel_region(y, "tensor")
    return y


def _parent_row(p, x, world):
    """``RowParallelLinear.__call__`` of that commit, likewise."""
    if world > 1:
        x = mappings.scatter_to_tensor_model_parallel_region(x, "tensor")
    y = jnp.dot(x, p["kernel"].astype(x.dtype),
                preferred_element_type=jnp.float32).astype(x.dtype)
    if world > 1:
        y = mappings.reduce_from_tensor_model_parallel_region(y, "tensor")
    return y + p["bias"].astype(y.dtype)


@pytest.mark.parametrize("world,sp", [(1, True), (1, False), (4, False)])
@pytest.mark.parametrize("kind", ["column", "row"])
def test_layers_bypass_is_the_parents_program(kind, world, sp):
    """At world == 1 (the one-chip train cells, every serve cell) with
    ``sequence_parallel`` on or off, and at world == 4 without it, the
    layers trace the jaxpr the parent's layers trace, forward and
    backward (checked against the parent's own files when this test was
    written: ``git archive 2d6d991``)."""
    ps.destroy_model_parallel()
    try:
        mesh = ps.initialize_model_parallel(
            tensor_model_parallel_size_=world,
            devices=jax.devices()[:world])
        cls, ref = ((ColumnParallelLinear, _parent_column)
                    if kind == "column" else (RowParallelLinear, _parent_row))
        layer = cls(input_size=16, output_size=16, sequence_parallel=sp,
                    sequence_dim=1)
        x = jnp.asarray(np.random.RandomState(7).randn(2, 8, 16),
                        jnp.bfloat16)

        def trace(fn):
            def step(x):
                v = layer.init(jax.random.PRNGKey(0), x)["params"]
                return jax.value_and_grad(lambda p, x: jnp.sum(
                    fn(p, x).astype(jnp.float32) ** 2), argnums=(0, 1))(v, x)
            return _normalized(str(jax.make_jaxpr(shard_map(
                step, mesh=mesh, in_specs=(P(),), out_specs=P(),
                check_vma=False))(x)))

        assert trace(lambda p, x: layer.apply({"params": p}, x)) \
            == trace(lambda p, x: ref(p, x, world))
    finally:
        ps.destroy_model_parallel()


# ---------------------------------------------------------------------------
# the ring is a ring on the chip: mesh order from the devices' coords
# ---------------------------------------------------------------------------


class _Chip:
    """A stand-in device: hashable, with the ``coords`` a TPU device has."""

    def __init__(self, id, coords):
        self.id, self.coords = id, coords

    def __repr__(self):
        return f"chip{self.id}{self.coords}"


def _one_hop(a, b):
    return sorted(abs(p - q) for p, q in zip(a.coords, b.coords))[-2:] \
        == [0, 1]


@pytest.mark.parametrize("tp", [4, 2])
def test_tensor_ranks_are_ici_neighbours_on_a_2x2(tp):
    """A v5e 2x2 enumerates row-major over ``coords``: ranks 1 -> 2 and
    3 -> 0 of that order are diagonal. Consecutive tensor ranks of the
    mesh, and the last with the first, differ in exactly one coordinate
    by one, for tp=4 and for dp=2 x tp=2."""
    devs = [_Chip(i, c) for i, c in enumerate(
        [(0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0)])]
    try:
        mesh = ps.initialize_model_parallel(tensor_model_parallel_size_=tp,
                                            devices=devs)
    finally:
        ps.destroy_model_parallel()
    assert dict(mesh.shape)["tensor"] == tp
    groups = mesh.devices.reshape(-1, tp)
    assert sorted(d.id for d in groups.flat) == [0, 1, 2, 3]
    for group in groups:
        for r in range(tp):
            assert _one_hop(group[r], group[(r + 1) % tp]), list(group)
    # the groups keep their members and their first device
    assert [g[0].id for g in groups] == list(range(0, 4, tp))


def test_devices_without_coords_keep_their_order():
    """CPU devices carry no ``coords``: the mesh is the enumeration
    order, as before."""
    assert not hasattr(jax.devices()[0], "coords")
    try:
        mesh = ps.initialize_model_parallel(tensor_model_parallel_size_=4)
    finally:
        ps.destroy_model_parallel()
    assert [d.id for d in mesh.devices.flat] == [d.id for d in jax.devices()]


# ---------------------------------------------------------------------------
# bucketed gradient all-reduce
# ---------------------------------------------------------------------------


def _grad_tree(rng, dtype=jnp.float32):
    return {
        "w1": jnp.asarray(rng.randn(6, 5), dtype),        # 120 B fp32
        "b1": jnp.asarray(rng.randn(5), dtype),           # 20 B
        "step": jnp.asarray(7, jnp.int32),                # non-floating
        "w2": jnp.asarray(rng.randn(100), dtype),         # 400 B — straddler
        "b2": jnp.asarray(rng.randn(3), dtype),           # 12 B
    }


def test_bucket_partition_semantics():
    leaves, _ = jax.tree.flatten(_grad_tree(np.random.RandomState(0)))
    # tree order: b1(20B), b2(12B), step(int), w1(120B), w2(400B)
    # message_size=32: b1 fills past 32 only with b2 → [b1,b2], then w1
    # alone (120 ≥ 32), then w2 alone. Straddling leaves stay whole.
    buckets = bucket_partition(leaves, 32)
    sizes = [[int(leaves[i].size) for i in b] for b in buckets]
    assert sizes == [[5, 3], [30], [100]]
    # non-floating leaves are in no bucket
    bucketed = {i for b in buckets for i in b}
    int_idx = [i for i, g in enumerate(leaves)
               if not jnp.issubdtype(g.dtype, jnp.floating)]
    assert not (bucketed & set(int_idx))
    # one-bucket case: everything fits
    assert len(bucket_partition(leaves, 1 << 30)) == 1
    # minimum size: every float leaf its own bucket
    assert len(bucket_partition(leaves, 1)) == 4
    # fp32-upcast sizing doubles bf16 wire bytes: the same tree splits
    # into twice the buckets once the upcast is priced in
    half = [jnp.ones((4,), jnp.bfloat16)] * 4        # 8 B each, 16 B on wire
    assert len(bucket_partition(half, 32)) == 1
    assert len(bucket_partition(half, 32, allreduce_always_fp32=True)) == 2
    assert len(bucket_partition(half, 33, allreduce_always_fp32=True)) == 2
    assert len(bucket_partition(half, 33)) == 1
    with pytest.raises(ValueError):
        bucket_partition(half, 0)


@pytest.mark.parametrize("message_size", [1, 64, 1 << 30])
def test_bucketed_allreduce_matches_per_leaf(message_size):
    """Bucketing changes grouping, not any leaf's reduction: bitwise
    parity with allreduce_gradients across bucket counts (4-bucket,
    straddling, one-bucket)."""
    mesh = _data_mesh()
    grads = _grad_tree(np.random.RandomState(6))

    def both(g):
        return (allreduce_gradients(g, "data"),
                bucketed_allreduce(g, "data", message_size=message_size))

    r1, r2 = shard_map(both, mesh=mesh, in_specs=(P(),),
                       out_specs=(P(), P()), check_vma=False)(grads)
    for k in grads:
        np.testing.assert_array_equal(np.asarray(r1[k]), np.asarray(r2[k]))


def test_bucketed_allreduce_scaling_options():
    """predivide / no-average / fp32-upcast combinations match the
    per-leaf path bitwise (same per-leaf math, different grouping)."""
    mesh = _data_mesh()
    n = len(jax.devices())
    grads = {"a": jnp.full((4,), 1.5, jnp.bfloat16),
             "b": jnp.asarray(np.random.RandomState(7).randn(9), jnp.float32)}
    for kw in (dict(gradient_predivide_factor=float(n)),
               dict(gradient_average=False),
               dict(allreduce_always_fp32=True),
               dict(allreduce_always_fp32=True, gradient_average=False,
                    gradient_predivide_factor=2.0)):
        def both(g):
            return (allreduce_gradients(g, "data", **kw),
                    bucketed_allreduce(g, "data", message_size=8, **kw))
        r1, r2 = shard_map(both, mesh=mesh, in_specs=(P(),),
                           out_specs=(P(), P()), check_vma=False)(grads)
        for k in grads:
            np.testing.assert_array_equal(np.asarray(r1[k]),
                                          np.asarray(r2[k]))
            assert r1[k].dtype == r2[k].dtype == grads[k].dtype


def test_bucketed_allreduce_one_psum_per_bucket():
    mesh = _data_mesh()
    grads = _grad_tree(np.random.RandomState(8))
    leaves, _ = jax.tree.flatten(grads)
    for message_size in (1, 32, 1 << 30):
        n_buckets = len(bucket_partition(leaves, message_size))
        jx = jax.make_jaxpr(shard_map(
            lambda g: bucketed_allreduce(g, "data",
                                         message_size=message_size),
            mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False))(grads)
        assert _eqn_count(jx.jaxpr, "psum") == n_buckets


# ---------------------------------------------------------------------------
# gradient accumulation: streamed bucket psums vs the delayed flush
# ---------------------------------------------------------------------------


def _acc_setup(n_micro=3, seed=9):
    rng = np.random.RandomState(seed)
    params = {"w1": jnp.asarray(rng.randn(4, 8) * 0.3, jnp.float32),
              "w2": jnp.asarray(rng.randn(8, 2) * 0.3, jnp.float32)}
    mbs = tuple(jnp.asarray(rng.randn(2, 4), jnp.float32)
                for _ in range(n_micro))

    def grad_fn(p, mb):
        def loss(p):
            return jnp.mean((jnp.tanh(mb @ p["w1"]) @ p["w2"]) ** 2)
        return jax.grad(loss)(p)

    return params, mbs, grad_fn


def test_accumulate_modes_agree():
    mesh = _data_mesh()
    params, mbs, grad_fn = _acc_setup()

    def run(**kw):
        def inner(p, *mbs):
            return accumulate_gradients(grad_fn, p, mbs, axis_name="data",
                                        message_size=64, **kw)
        return shard_map(inner, mesh=mesh, in_specs=(P(),) * (1 + len(mbs)),
                         out_specs=P(), check_vma=False)(params, *mbs)

    base = run(overlap_comm=False)
    streamed = run(overlap_comm=True, delay_allreduce=False)
    delayed = run(overlap_comm=True, delay_allreduce=True)
    for k in params:
        # delayed bucketing reduces the same accumulated leaves: bitwise
        np.testing.assert_array_equal(np.asarray(base[k]),
                                      np.asarray(delayed[k]))
        # streamed reassociates (psum per microbatch): fp tolerance
        np.testing.assert_allclose(np.asarray(base[k]),
                                   np.asarray(streamed[k]),
                                   rtol=1e-6, atol=1e-7)


def test_accumulate_off_is_byte_identical_to_manual_loop():
    """overlap_comm=False is the hand-written accumulate-then-allreduce
    program, byte for byte — the DDP half of the `off == today` bar."""
    mesh = _data_mesh()
    params, mbs, grad_fn = _acc_setup()

    def helper(p, *mbs):
        return accumulate_gradients(grad_fn, p, mbs, axis_name="data",
                                    overlap_comm=False)

    def manual(p, *mbs):
        acc = None
        for mb in mbs:
            g = grad_fn(p, mb)
            acc = g if acc is None else jax.tree.map(jnp.add, acc, g)
        return allreduce_gradients(acc, "data")

    specs = (P(),) * (1 + len(mbs))
    j1 = jax.make_jaxpr(shard_map(helper, mesh=mesh, in_specs=specs,
                                  out_specs=P(), check_vma=False))(
        params, *mbs)
    j2 = jax.make_jaxpr(shard_map(manual, mesh=mesh, in_specs=specs,
                                  out_specs=P(), check_vma=False))(
        params, *mbs)
    assert str(j1) == str(j2)


def test_accumulate_streamed_psum_counts():
    """Streamed: one psum per bucket per microbatch, each issued in the
    program before the next microbatch's compute (the overlap window);
    delayed: one per bucket total."""
    mesh = _data_mesh()
    params, mbs, grad_fn = _acc_setup()
    leaves, _ = jax.tree.flatten(params)
    n_buckets = len(bucket_partition(leaves, 64))
    assert n_buckets == 2   # w1 128 B ≥ 64 closes; w2 64 B

    def trace(**kw):
        def inner(p, *mbs):
            return accumulate_gradients(grad_fn, p, mbs, axis_name="data",
                                        message_size=64, **kw)
        return jax.make_jaxpr(shard_map(
            inner, mesh=mesh, in_specs=(P(),) * (1 + len(mbs)),
            out_specs=P(), check_vma=False))(params, *mbs)

    streamed = trace(overlap_comm=True, delay_allreduce=False)
    assert _eqn_count(streamed.jaxpr, "psum") == n_buckets * len(mbs)
    delayed = trace(overlap_comm=True, delay_allreduce=True)
    assert _eqn_count(delayed.jaxpr, "psum") == n_buckets
    off = trace(overlap_comm=False)
    n_float = sum(1 for g in leaves
                  if jnp.issubdtype(g.dtype, jnp.floating))
    assert _eqn_count(off.jaxpr, "psum") == n_float   # today's per-leaf form


def test_ddp_wrapper_bucketed_flush_and_accumulate():
    mesh = _data_mesh()
    params, mbs, grad_fn = _acc_setup()
    ddp_off = DistributedDataParallel(lambda p, x: x)
    ddp_on = DistributedDataParallel(lambda p, x: x, overlap_comm=True,
                                     message_size=64)
    grads = grad_fn(params, mbs[0])

    def inner(g):
        return ddp_off.sync(g), ddp_on.sync(g)

    r_off, r_on = shard_map(inner, mesh=mesh, in_specs=(P(),),
                            out_specs=(P(), P()), check_vma=False)(grads)
    for k in grads:
        np.testing.assert_array_equal(np.asarray(r_off[k]),
                                      np.asarray(r_on[k]))

    def acc(p, *mbs):
        return ddp_on.accumulate(grad_fn, p, mbs)

    got = shard_map(acc, mesh=mesh, in_specs=(P(),) * (1 + len(mbs)),
                    out_specs=P(), check_vma=False)(params, *mbs)
    want = shard_map(lambda p, *m: accumulate_gradients(
        grad_fn, p, m, axis_name="data", message_size=64,
        overlap_comm=True), mesh=mesh, in_specs=(P(),) * (1 + len(mbs)),
        out_specs=P(), check_vma=False)(params, *mbs)
    for k in params:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))


@pytest.mark.slow
@pytest.mark.parametrize("message_size", [1, 16, 48, 128, 512, 1 << 20])
@pytest.mark.parametrize("n_micro", [1, 2, 5])
def test_accumulate_exhaustive_sweep(message_size, n_micro):
    """Exhaustive bucket-size × microbatch-count sweep (slow tier; the
    representative cases above run in tier-1)."""
    mesh = _data_mesh()
    params, mbs, grad_fn = _acc_setup(n_micro=n_micro, seed=message_size % 97)

    def run(**kw):
        def inner(p, *mbs):
            return accumulate_gradients(grad_fn, p, mbs, axis_name="data",
                                        message_size=message_size, **kw)
        return shard_map(inner, mesh=mesh, in_specs=(P(),) * (1 + len(mbs)),
                         out_specs=P(), check_vma=False)(params, *mbs)

    base = run(overlap_comm=False)
    for kw in (dict(overlap_comm=True),
               dict(overlap_comm=True, delay_allreduce=True)):
        got = run(**kw)
        for k in params:
            np.testing.assert_allclose(np.asarray(base[k]),
                                       np.asarray(got[k]),
                                       rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# monitor: trace-time ppermute accounting
# ---------------------------------------------------------------------------


def test_monitor_counts_ppermute_bytes(tp_mesh):
    from apex_tpu import monitor

    x = jnp.asarray(np.random.RandomState(10).randn(8, 16), jnp.float32)
    w = jnp.asarray(np.random.RandomState(11).randn(16, 12), jnp.float32)
    rec = monitor.Recorder(name="overlap-test")
    with monitor.attached(rec):
        jax.make_jaxpr(shard_map(
            lambda x, w: matmul_reduce_scatter(x, w, "tensor", 0),
            mesh=tp_mesh, in_specs=(P(), P()), out_specs=P("tensor"),
            check_vma=False))(x, w)
    table = rec.collectives()
    assert "ppermute@tensor" in table, table
    entry = table["ppermute@tensor"]
    # tp-1 hops, each carrying the [s/tp, n] fp32 partial sum
    assert entry["count"] == TP - 1
    assert entry["bytes"] == (TP - 1) * (8 // TP) * 12 * 4


def test_monitor_counts_bucket_psums():
    from apex_tpu import monitor

    mesh = _data_mesh()
    grads = _grad_tree(np.random.RandomState(12))
    leaves, _ = jax.tree.flatten(grads)
    n_buckets = len(bucket_partition(leaves, 32))
    rec = monitor.Recorder(name="overlap-test")
    with monitor.attached(rec):
        jax.make_jaxpr(shard_map(
            lambda g: bucketed_allreduce(g, "data", message_size=32),
            mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False))(grads)
    table = rec.collectives()
    assert table["psum@data"]["count"] == n_buckets
    float_bytes = sum(g.size * g.dtype.itemsize for g in leaves
                      if jnp.issubdtype(g.dtype, jnp.floating))
    assert table["psum@data"]["bytes"] == float_bytes


@pytest.mark.parametrize("form", ["gather", "scatter"])
def test_overlap_disabled_monitor_adds_no_ops(tp_mesh, form):
    """The accounting is trace-time host bookkeeping: attaching a
    recorder must not change the traced program (jaxpr purity, the
    disabled-mode contract of docs/observability.md)."""
    from apex_tpu import monitor

    x = jnp.asarray(np.random.RandomState(13).randn(8, 16), jnp.float32)
    w = jnp.asarray(np.random.RandomState(14).randn(16, 12), jnp.float32)

    fn, x_spec, out = ((all_gather_matmul, P("tensor"), P())
                       if form == "gather"
                       else (matmul_reduce_scatter, P(), P("tensor")))

    def trace():
        return _normalized(str(jax.make_jaxpr(shard_map(
            lambda x, w: fn(x, w, "tensor", 0),
            mesh=tp_mesh, in_specs=(x_spec, P()), out_specs=out,
            check_vma=False))(x, w)))

    bare = trace()
    with monitor.attached(monitor.Recorder(name="purity")):
        instrumented = trace()
    assert bare == instrumented
