"""Contrib tests: transducer, group BN, ASP sparsity, spatial bottleneck.

Mirrors ``apex/contrib/test/transducer/*`` (joint + loss vs reference DP),
``apex/contrib/sparsity/test/*`` (mask validity + persistence through
steps), and the spatial-parallel bottleneck correctness.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from apex_tpu._compat import shard_map

from apex_tpu.contrib.transducer import transducer_joint, transducer_loss
from apex_tpu.contrib.sparsity import ASP, create_mask
from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC
from apex_tpu.contrib.bottleneck import halo_exchange, SpatialBottleneck


# ---------------------------------------------------------------- transducer

def _rnnt_loss_ref(lp, labels, T, U_y, blank=0):
    """Sequential numpy alpha recursion (transducer_ref.py analog)."""
    U = U_y + 1
    alpha = np.full((T, U), -np.inf)
    alpha[0, 0] = 0.0
    for t in range(T):
        for u in range(U):
            if t == 0 and u == 0:
                continue
            cands = []
            if t > 0:
                cands.append(alpha[t - 1, u] + lp[t - 1, u, blank])
            if u > 0:
                cands.append(alpha[t, u - 1] + lp[t, u - 1, labels[u - 1]])
            alpha[t, u] = np.logaddexp.reduce(cands)
    return -(alpha[T - 1, U - 1] + lp[T - 1, U - 1, blank])


def test_transducer_joint():
    rng = np.random.RandomState(0)
    f = jnp.asarray(rng.randn(2, 4, 8), jnp.float32)
    g = jnp.asarray(rng.randn(2, 3, 8), jnp.float32)
    out = transducer_joint(f, g)
    assert out.shape == (2, 4, 3, 8)
    np.testing.assert_allclose(
        np.asarray(out[1, 2, 1]), np.asarray(f[1, 2]) + np.asarray(g[1, 1]), rtol=1e-6)
    out_relu = transducer_joint(f, g, relu=True)
    assert float(jnp.min(out_relu)) >= 0.0


def test_transducer_loss_matches_reference_dp():
    rng = np.random.RandomState(1)
    B, T, U, V = 2, 5, 4, 6      # U = y_len+1 max
    logits = rng.randn(B, T, U, V).astype(np.float32)
    lp = jax.nn.log_softmax(jnp.asarray(logits), -1)
    labels = jnp.asarray(rng.randint(1, V, (B, U - 1)))
    f_len = jnp.asarray([5, 4])
    y_len = jnp.asarray([3, 2])
    loss = transducer_loss(lp, labels, f_len, y_len)
    for b in range(B):
        ref = _rnnt_loss_ref(np.asarray(lp[b]), np.asarray(labels[b]),
                             int(f_len[b]), int(y_len[b]))
        np.testing.assert_allclose(float(loss[b]), ref, rtol=1e-4, atol=1e-4)


def test_transducer_loss_grad_finite():
    rng = np.random.RandomState(2)
    lp = jax.nn.log_softmax(jnp.asarray(rng.randn(1, 4, 3, 5), jnp.float32), -1)
    labels = jnp.asarray([[1, 2]])
    g = jax.grad(lambda lp: jnp.sum(transducer_loss(
        lp, labels, jnp.asarray([4]), jnp.asarray([2]))))(lp)
    assert np.isfinite(np.asarray(g)).all()
    # grads flow only into reachable lattice cells' used entries
    assert float(jnp.sum(jnp.abs(g))) > 0


# ---------------------------------------------------------------- sparsity

def test_create_mask_2of4():
    rng = np.random.RandomState(3)
    w = jnp.asarray(rng.randn(16, 8), jnp.float32)   # [in, out] kernel
    m = create_mask(w)                                # 2:4 along in (axis -2)
    mm = np.asarray(m).T.reshape(8, 4, 4)
    assert (mm.sum(-1) == 2).all()
    # kept entries are the two largest |w| per group of 4 input weights
    wa = np.abs(np.asarray(w)).T.reshape(8, 4, 4)
    for i in range(8):
        for gidx in range(4):
            kept = set(np.where(mm[i, gidx])[0])
            top2 = set(np.argsort(wa[i, gidx])[-2:])
            assert kept == top2


def test_asp_masks_persist_through_optimizer():
    from apex_tpu.optimizers import FusedSGD
    rng = np.random.RandomState(4)
    params = {"dense": {"kernel": jnp.asarray(rng.randn(16, 8), jnp.float32),
                        "bias": jnp.zeros((8,), jnp.float32)}}
    ASP.init_model_for_pruning(params)
    masks = ASP.compute_sparse_masks(params)
    params = ASP.apply_masks(params)
    kmask = np.asarray(masks["dense"]["kernel"])
    assert (np.asarray(params["dense"]["kernel"])[~kmask] == 0).all()
    assert np.asarray(masks["dense"]["bias"]).all()  # bias not pruned

    opt = FusedSGD(params, lr=0.1)
    ASP.init_optimizer_for_pruning(opt)
    state = opt.init()
    g = jax.tree.map(jnp.ones_like, params)
    new_p, _ = opt.apply(state, params, g)
    assert (np.asarray(new_p["dense"]["kernel"])[~kmask] == 0).all()
    ASP.restore_pruned_weights(params)


# ---------------------------------------------------------------- group BN

def test_groupbn_nhwc_with_add_relu():
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(4, 6, 6, 8), jnp.float32)
    z = jnp.asarray(rng.randn(4, 6, 6, 8), jnp.float32)
    bn = BatchNorm2d_NHWC(num_features=8, fuse_relu=True, bn_group=1,
                          axis_name=None)
    v = bn.init(jax.random.PRNGKey(0), x)
    y, _ = bn.apply(v, x, z=z, mutable=["batch_stats"])
    mean = np.asarray(x).reshape(-1, 8).mean(0)
    var = np.asarray(x).reshape(-1, 8).var(0)
    ref = np.maximum((np.asarray(x) - mean) / np.sqrt(var + 1e-5) + np.asarray(z), 0)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- spatial bottleneck

def test_halo_exchange():
    mesh = Mesh(np.array(jax.devices()), ("data",))
    n = len(jax.devices())
    H = n * 2
    x = jnp.arange(H * 3, dtype=jnp.float32).reshape(1, H, 3, 1)

    f = shard_map(lambda x: halo_exchange(x, "data", 1),
                  mesh=mesh, in_specs=(P(None, "data"),),
                  out_specs=P(None, "data"), check_vma=False)
    y = f(x)  # [1, n*(2+2), 3, 1]
    y = np.asarray(y).reshape(n, 4, 3)
    xs = np.asarray(x).reshape(n, 2, 3)
    for r in range(n):
        np.testing.assert_array_equal(y[r, 1:3], xs[r])          # own rows
        if r > 0:
            np.testing.assert_array_equal(y[r, 0], xs[r - 1, -1])  # upper halo
        else:
            assert (y[r, 0] == 0).all()
        if r < n - 1:
            np.testing.assert_array_equal(y[r, 3], xs[r + 1, 0])   # lower halo
        else:
            assert (y[r, 3] == 0).all()


@pytest.mark.slow
def test_spatial_bottleneck_matches_unsharded():
    mesh = Mesh(np.array(jax.devices()), ("data",))
    n = len(jax.devices())
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(1, n * 2, 4, 8), jnp.float32)
    blk = SpatialBottleneck(filters=4, axis_name="data")

    # init once on the full input with a single-device axis context
    def init_and_run_full(x):
        # full-volume reference: same weights, halo exchange degenerates
        mesh1 = Mesh(np.array(jax.devices()[:1]), ("data",))
        v = shard_map(lambda x: blk.init(jax.random.PRNGKey(0), x),
                      mesh=mesh1, in_specs=(P(),), out_specs=P(),
                      check_vma=False)(x)
        y = shard_map(lambda x: blk.apply(v, x, mutable=["batch_stats"])[0],
                      mesh=mesh1, in_specs=(P(),), out_specs=P(),
                      check_vma=False)(x)
        return v, y

    v, y_full = init_and_run_full(x)
    v = jax.tree.map(np.asarray, v)  # device-neutral params for the 8-dev mesh
    y_sharded = shard_map(lambda x: blk.apply(v, x, mutable=["batch_stats"])[0],
                          mesh=mesh,
                          in_specs=(P(None, "data"),),
                          out_specs=P(None, "data"), check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(y_sharded), np.asarray(y_full),
                               rtol=1e-4, atol=1e-4)


def test_contrib_fast_layer_norm_parity_surface():
    """apex.contrib.layer_norm API shim: FastLayerNorm(hidden, eps) ==
    the one fused LN (apex's second LN is folded into it on purpose: one
    implementation serves both, ``contrib/layer_norm/__init__.py``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from apex_tpu.contrib.layer_norm import FastLayerNorm, ln_fwd

    x = jnp.asarray(np.random.RandomState(0).randn(4, 32), jnp.float32)
    m = FastLayerNorm(32)
    v = m.init(jax.random.PRNGKey(0), x)
    y = m.apply(v, x)
    ref = (x - x.mean(-1, keepdims=True)) / jnp.sqrt(
        x.var(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    y2 = ln_fwd(x, jnp.ones((32,)), jnp.zeros((32,)))
    np.testing.assert_allclose(np.asarray(y2), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_transducer_pack_output_warns_inert():
    import pytest
    from apex_tpu.contrib.transducer.transducer import TransducerJoint
    """pack_output is a CUDA packed-varlen knob; on TPU it is accepted
    for parity and warns once."""
    from apex_tpu.utils import parity
    parity._seen.clear()
    with pytest.warns(UserWarning, match="pack_output"):
        TransducerJoint(pack_output=True)
