"""amp O4 / fp8 delayed-scaling tests (PR 7).

Coverage map (ISSUE 7 satellites):

- codec round-trip properties: amax saturation, e4m3 vs e5m2 ranges,
  subnormal flush, the hardcoded format maxima vs ml_dtypes' finfo;
- ``fp8_matmul`` custom_vjp: forward equals the quantize/dequantize
  reference, backward records amax for x/w/g as meta cotangents;
- delayed scaling: ring shift, history max, margin, non-finite guard;
- ``make_train_step(fp8=True)``: convergence next to bf16, overflow
  skip leaves the amax history BITWISE untouched (the O2 master-weight
  skip contract), state donated/threaded;
- checkpoint.py round trip of the fp8 state tree;
- ``initialize(enabled=False)`` keeps the O4 surface inert-but-present
  (the PR 6 ``zero=`` wrapper-drop class of bug);
- comm: ``bucketed_allreduce(compress="fp8")`` bytes <= 0.55x bf16 at
  matched config (trace-time monitor accounting — the acceptance
  bound), reduction parity within the e5m2 envelope, knob validation,
  ``zero.comm.quantized_all_gather(scaled=...)`` unification;
- slow: a tiny-GPT convergence run, O4 final loss within documented
  tolerance (rtol 0.2 over the tail mean — docs/amp.md) of bf16.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, Mesh, PartitionSpec as P

from apex_tpu import amp, checkpoint, monitor
from apex_tpu._compat import shard_map
from apex_tpu.amp import fp8
from apex_tpu.amp import scaler as scaler_mod
from apex_tpu.optimizers import FusedAdam


# ---------------------------------------------------------------------------
# codec properties
# ---------------------------------------------------------------------------


def test_format_maxima_match_ml_dtypes():
    import ml_dtypes
    assert fp8.E4M3_MAX == float(ml_dtypes.finfo(ml_dtypes.float8_e4m3fn).max)
    assert fp8.E5M2_MAX == float(ml_dtypes.finfo(ml_dtypes.float8_e5m2).max)
    assert fp8.fp8_max(fp8.E4M3) == 448.0
    assert fp8.fp8_max(fp8.E5M2) == 57344.0
    with pytest.raises(ValueError):
        fp8.fp8_max(jnp.bfloat16)


def test_quantize_saturates_not_nan():
    """e4m3fn has no inf encoding: an unclipped out-of-range cast
    produces NaN. The codec must clip instead."""
    x = jnp.asarray([1e6, -1e6, 2.0], jnp.float32)
    q = fp8.quantize(x, jnp.float32(1.0), fp8.E4M3)
    back = q.astype(jnp.float32)
    assert bool(jnp.all(jnp.isfinite(back)))
    assert float(back[0]) == fp8.E4M3_MAX
    assert float(back[1]) == -fp8.E4M3_MAX
    # and the naive cast really is the trap the clip defends against
    naive = x.astype(fp8.E4M3).astype(jnp.float32)
    assert bool(jnp.any(~jnp.isfinite(naive))) or \
        float(jnp.max(jnp.abs(naive))) >= fp8.E4M3_MAX


def test_round_trip_error_envelope():
    """Relative round-trip error with a well-chosen scale is bounded by
    the format's mantissa width: 2^-3 for e4m3 (3 bits), 2^-2 for e5m2
    (2 bits) — one half-ULP each."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(512) * 7.0, jnp.float32)
    for fmt, fmt_max, bound in ((fp8.E4M3, fp8.E4M3_MAX, 2.0 ** -3),
                                (fp8.E5M2, fp8.E5M2_MAX, 2.0 ** -2)):
        s = fp8.compute_scale(fp8.amax(x), fmt_max)
        r = fp8.dequantize(fp8.quantize(x, s, fmt), s)
        rel = float(jnp.max(jnp.abs(r - x) / (jnp.abs(x) + 1e-9)))
        assert rel <= bound * 0.5 + 1e-6, (fmt, rel)


def test_subnormal_flush():
    """Values far below amax land in (or under) the format's subnormal
    range and flush toward zero — quantization loses them, dequantize
    must not resurrect garbage."""
    x = jnp.asarray([100.0, 1e-7], jnp.float32)
    s = fp8.compute_scale(fp8.amax(x), fp8.E4M3_MAX)   # scale anchored at 100
    r = fp8.dequantize(fp8.quantize(x, s, fp8.E4M3), s)
    assert float(r[0]) == pytest.approx(100.0, rel=2 ** -3)
    assert abs(float(r[1])) < 1e-3    # flushed, not amplified


def test_compute_scale_guards():
    # untrained history (amax 0) and non-finite fall back to 1.0
    assert float(fp8.compute_scale(0.0, fp8.E4M3_MAX)) == 1.0
    assert float(fp8.compute_scale(np.inf, fp8.E4M3_MAX)) == 1.0
    # margin: each unit halves the scale
    s0 = float(fp8.compute_scale(1.0, fp8.E4M3_MAX, margin=0.0))
    s1 = float(fp8.compute_scale(1.0, fp8.E4M3_MAX, margin=1.0))
    assert s0 == pytest.approx(448.0) and s1 == pytest.approx(224.0)


def test_update_meta_ring_and_history_max():
    meta = fp8.init_meta(history_len=3)
    m1 = fp8.update_meta(meta, 4.0, fp8.E4M3_MAX)
    m2 = fp8.update_meta(m1, 1.0, fp8.E4M3_MAX)
    np.testing.assert_allclose(np.asarray(m2.amax_history), [1.0, 4.0, 0.0])
    # scale derives from the HISTORY max (4.0), not the newest obs
    assert float(m2.scale) == pytest.approx(448.0 / 4.0)
    # the ring forgets: after 3 more pushes the 4.0 falls off
    m = m2
    for _ in range(3):
        m = fp8.update_meta(m, 1.0, fp8.E4M3_MAX)
    assert float(m.scale) == pytest.approx(448.0)
    # a non-finite observation records as 0 and cannot zero the scale
    mbad = fp8.update_meta(meta, np.nan, fp8.E4M3_MAX)
    assert float(mbad.amax_history[0]) == 0.0
    assert np.isfinite(float(mbad.scale))


# ---------------------------------------------------------------------------
# fp8_matmul custom_vjp
# ---------------------------------------------------------------------------


def _rand(shape, seed, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape) * scale,
                       jnp.float32)


def test_fp8_matmul_forward_matches_reference():
    x, w = _rand((4, 8), 0), _rand((8, 3), 1)
    meta = fp8.init_dot_meta()
    got = fp8.fp8_matmul(x, w, meta)
    qx = fp8.dequantize(fp8.quantize(x, meta.x.scale, fp8.E4M3), meta.x.scale)
    qw = fp8.dequantize(fp8.quantize(w, meta.w.scale, fp8.E4M3), meta.w.scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(qx @ qw),
                               rtol=1e-5, atol=1e-5)
    # scale-aware path: a trained scale reduces quantization error on a
    # tensor far outside the format at scale 1.0 (amax >> 448 — every
    # value saturates unscaled; the trained scale maps amax back to the
    # format max)
    xs = x * 1e4
    s = float(fp8.compute_scale(fp8.amax(xs), fp8.E4M3_MAX))
    meta2 = meta._replace(x=meta.x._replace(scale=jnp.float32(s)))
    err_default = float(jnp.max(jnp.abs(fp8.fp8_matmul(xs, w, meta) -
                                        xs @ w)))
    err_trained = float(jnp.max(jnp.abs(fp8.fp8_matmul(xs, w, meta2) -
                                        xs @ w)))
    assert np.isfinite(err_default)   # saturates, never NaN
    assert err_trained < err_default


def test_fp8_matmul_shape_validation():
    meta = fp8.init_dot_meta()
    with pytest.raises(ValueError):
        fp8.fp8_matmul(jnp.zeros((2, 3)), jnp.zeros((4, 5)), meta)
    with pytest.raises(ValueError):
        fp8.fp8_matmul(jnp.zeros((2, 3)), jnp.zeros((3, 4, 5)), meta)


def test_fp8_matmul_records_amax_as_meta_cotangent():
    """jax.grad over (params, fp8_state) must return the recorded amax
    of x and w (measured in the fwd) and of the cotangent (measured in
    the bwd) in the meta cotangent's ``scale`` slots."""
    x, w = _rand((4, 8), 2, scale=3.0), _rand((8, 3), 3, scale=0.5)
    meta = fp8.init_dot_meta()

    def loss(w, meta):
        return jnp.sum(fp8.fp8_matmul(x, w, meta))

    gw, gmeta = jax.grad(loss, argnums=(0, 1))(w, meta)
    assert float(gmeta.x.scale) == pytest.approx(float(fp8.amax(x)), rel=1e-6)
    assert float(gmeta.w.scale) == pytest.approx(float(fp8.amax(w)), rel=1e-6)
    # cotangent of a sum() is all-ones: amax_g == 1
    assert float(gmeta.g.scale) == pytest.approx(1.0)
    # history slots of the recorded tree are zeros (pure observation)
    assert float(jnp.max(jnp.abs(gmeta.x.amax_history))) == 0.0
    # and the weight grad approximates x^T @ ones within the e5m2+e4m3
    # envelope
    ref = x.T @ jnp.ones((4, 3), jnp.float32)
    rel = float(jnp.max(jnp.abs(gw - ref)) / (jnp.max(jnp.abs(ref)) + 1e-9))
    assert rel < 0.2


def test_fp8_matmul_batched_leading_dims():
    x = _rand((2, 5, 8), 4)
    w = _rand((8, 3), 5)
    meta = fp8.init_dot_meta()
    y = fp8.fp8_matmul(x, w, meta)
    assert y.shape == (2, 5, 3)
    # grads flow and keep shapes
    g = jax.grad(lambda w: jnp.sum(fp8.fp8_matmul(x, w, meta) ** 2))(w)
    assert g.shape == w.shape


def test_update_state_applies_recorded_amax():
    state = fp8.init_state(["a"], history_len=4)
    recorded = {"a": fp8.Fp8DotMeta(
        x=fp8.Fp8Meta(jnp.zeros(4), jnp.float32(2.0)),
        w=fp8.Fp8Meta(jnp.zeros(4), jnp.float32(4.0)),
        g=fp8.Fp8Meta(jnp.zeros(4), jnp.float32(8.0)))}
    new = fp8.update_state(state, recorded)
    assert float(new["a"].x.scale) == pytest.approx(448.0 / 2.0)
    assert float(new["a"].w.scale) == pytest.approx(448.0 / 4.0)
    assert float(new["a"].g.scale) == pytest.approx(57344.0 / 8.0)
    # margin flows through
    new_m = fp8.update_state(state, recorded, margin=1.0)
    assert float(new_m["a"].x.scale) == pytest.approx(448.0 / 4.0)


# ---------------------------------------------------------------------------
# O4 opt level + train step
# ---------------------------------------------------------------------------


def _mlp_apply(params, x):
    h = jnp.tanh(x @ params["w1"])
    return h @ params["w2"]


def test_o4_properties_defaults():
    m = amp.initialize(_mlp_apply, opt_level="O4")
    p = m.properties
    assert p.opt_level == "O4"
    assert p.cast_model_type == jnp.bfloat16
    assert p.master_weights is True
    assert p.keep_batchnorm_fp32 is True
    # bf16 shares fp32's exponent range: the global loss scale exists
    # only for NON-fp8 leaves and needs no dynamics
    assert p.loss_scale == 1.0
    assert p.fp8_history_len == 16 and p.fp8_margin == 0.0
    # fp16 half dtype: dynamic scaling for the non-fp8 leaves, exactly
    # like O2 (the fp8-consumed grads are governed by their own e5m2
    # delayed scale either way)
    m16 = amp.initialize(_mlp_apply, opt_level="O4", half_dtype=jnp.float16)
    assert m16.properties.loss_scale == "dynamic"


def test_o4_init_fp8_state_uses_history_len():
    m = amp.initialize(_mlp_apply, opt_level="O4", fp8_history_len=5)
    st = m.init_fp8_state(["l1", "l2"])
    assert set(st) == {"l1", "l2"}
    assert st["l1"].x.amax_history.shape == (5,)


def _fp8_mlp_loss(params, fstate, x, y):
    h = jnp.tanh(fp8.fp8_matmul(x, params["w1"], fstate["l1"]))
    return jnp.mean((fp8.fp8_matmul(h, params["w2"], fstate["l2"]) - y) ** 2)


def _mk_fp8_setup(seed=0, lr=5e-2, history_len=4, **step_kw):
    params = {"w1": _rand((4, 8), seed, 0.4),
              "w2": _rand((8, 2), seed + 1, 0.4)}
    opt = FusedAdam(lr=lr)
    step = amp.make_train_step(_fp8_mlp_loss, opt, fp8=True, donate=False,
                               **step_kw)
    return (params, opt.init(params), scaler_mod.init_state(),
            fp8.init_state(["l1", "l2"], history_len=history_len), step)


def test_fp8_train_step_converges_and_updates_state():
    params, opt_state, sstate, fstate, step = _mk_fp8_setup()
    x = jnp.ones((8, 4), jnp.float32) * 1.5
    y = jnp.zeros((8, 2), jnp.float32)
    losses = []
    for _ in range(25):
        params, opt_state, sstate, fstate, loss = step(
            params, opt_state, sstate, fstate, x, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.2
    # delayed scaling engaged: the x-meta saw amax 1.5 and moved its
    # scale off the init value
    assert float(fstate["l1"].x.amax_history[0]) == pytest.approx(1.5)
    assert float(fstate["l1"].x.scale) == pytest.approx(448.0 / 1.5, rel=1e-5)


def test_fp8_vs_bf16_mlp_convergence_parity():
    """The non-slow convergence gate: same tiny MLP regression, O4 fp8
    matmuls vs bf16 matmuls, final-loss tail within rtol 0.2 (the
    documented O4 tolerance, docs/amp.md)."""
    rng = np.random.RandomState(42)
    x = jnp.asarray(rng.randn(32, 4), jnp.float32)
    wt = rng.randn(4, 2)
    y = jnp.asarray(np.tanh(np.asarray(x) @ wt) * 0.7, jnp.float32)

    def run(fp8_on, steps=80):
        params = {"w1": _rand((4, 8), 7, 0.4), "w2": _rand((8, 2), 8, 0.4)}
        opt = FusedAdam(lr=3e-2)
        if fp8_on:
            p, o, s, f, step = params, opt.init(params), \
                scaler_mod.init_state(), fp8.init_state(["l1", "l2"]), \
                amp.make_train_step(_fp8_mlp_loss, opt, fp8=True,
                                    donate=False)
            for _ in range(steps):
                p, o, s, f, loss = step(p, o, s, f, x, y)
            return float(loss)

        def bf16_loss(p, xb, yb):
            h = jnp.tanh(jnp.dot(xb.astype(jnp.bfloat16),
                                 p["w1"].astype(jnp.bfloat16),
                                 preferred_element_type=jnp.float32))
            return jnp.mean((jnp.dot(h.astype(jnp.bfloat16),
                                     p["w2"].astype(jnp.bfloat16),
                                     preferred_element_type=jnp.float32)
                             - yb) ** 2)

        p, o, s = params, opt.init(params), scaler_mod.init_state()
        step = amp.make_train_step(bf16_loss, opt, donate=False)
        for _ in range(steps):
            p, o, s, loss = step(p, o, s, x, y)
        return float(loss)

    l_fp8, l_bf16 = run(True), run(False)
    assert l_fp8 == pytest.approx(l_bf16, rel=0.2, abs=5e-3), \
        (l_fp8, l_bf16)


def test_overflow_skip_leaves_amax_history_untouched():
    """The O2 master-weight-skip contract, ported to the amax history:
    a poisoned (NaN) batch must skip the parameter update AND leave the
    whole fp8 state tree bitwise unchanged — an inf/nan backward pass
    must never enter the delayed-scaling statistics."""
    params, opt_state, sstate, fstate, step = _mk_fp8_setup()
    x = jnp.ones((8, 4), jnp.float32)
    y = jnp.zeros((8, 2), jnp.float32)
    # one clean step so the state is mid-training, not all-init
    params, opt_state, sstate, fstate, _ = step(
        params, opt_state, sstate, fstate, x, y)
    before_f = jax.tree.map(np.asarray, fstate)
    before_p = jax.tree.map(np.asarray, params)
    bad_x = x.at[0, 0].set(jnp.nan)
    params, opt_state, sstate, fstate, loss = step(
        params, opt_state, sstate, fstate, bad_x, y)
    for a, b in zip(jax.tree.leaves(before_f), jax.tree.leaves(fstate)):
        np.testing.assert_array_equal(a, np.asarray(b))
    for a, b in zip(jax.tree.leaves(before_p), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, np.asarray(b))
    # and a clean step afterwards resumes updating the statistics
    params, opt_state, sstate, fstate, _ = step(
        params, opt_state, sstate, fstate, x, y)
    changed = any(
        not np.array_equal(a, np.asarray(b))
        for a, b in zip(jax.tree.leaves(before_f), jax.tree.leaves(fstate)))
    assert changed


def test_fp8_margin_flows_from_properties():
    """make_train_step(fp8=True) pulls fp8_margin off the optimizer's
    amp properties when not given explicitly."""
    params = {"w1": _rand((4, 8), 0, 0.4), "w2": _rand((8, 2), 1, 0.4)}
    opt = FusedAdam(lr=1e-2)
    _, opt = amp.initialize(_mlp_apply, opt, opt_level="O4", fp8_margin=2.0)
    step = amp.make_train_step(_fp8_mlp_loss, opt, fp8=True, donate=False)
    fstate = fp8.init_state(["l1", "l2"], history_len=4)
    x = jnp.ones((8, 4), jnp.float32)
    p, o, s, f, _ = step(params, opt.init(params), scaler_mod.init_state(),
                         fstate, x, jnp.zeros((8, 2), jnp.float32))
    # margin=2 parks amax 4x below the format max: scale = 448/(1*4)
    assert float(f["l1"].x.scale) == pytest.approx(448.0 / 4.0, rel=1e-5)
    # and the knob cannot be silently dropped: without fp8=True an
    # explicit margin is a contradiction, not a no-op
    with pytest.raises(ValueError, match="fp8_margin"):
        amp.make_train_step(_fp8_mlp_loss, opt, fp8_margin=2.0)


# ---------------------------------------------------------------------------
# checkpoint round trip
# ---------------------------------------------------------------------------


def test_fp8_state_checkpoint_round_trip(tmp_path):
    params, opt_state, sstate, fstate, step = _mk_fp8_setup(history_len=6)
    x = jnp.ones((8, 4), jnp.float32) * 2.0
    y = jnp.zeros((8, 2), jnp.float32)
    for _ in range(3):
        params, opt_state, sstate, fstate, _ = step(
            params, opt_state, sstate, fstate, x, y)
    path = str(tmp_path / "fp8_ckpt.npz")
    checkpoint.save_train_state(path, params=params, opt_state=opt_state,
                                scaler_state=sstate, extra={"fp8": fstate})
    template = fp8.init_state(["l1", "l2"], history_len=6)
    p2, o2, s2, extra = checkpoint.load_train_state(
        path, params=jax.tree.map(jnp.zeros_like, params),
        opt_state=jax.tree.map(jnp.zeros_like, opt_state),
        scaler_state=jax.tree.map(jnp.zeros_like, sstate),
        extra={"fp8": template})
    for a, b in zip(jax.tree.leaves(fstate), jax.tree.leaves(extra["fp8"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # NamedTuple structure restored through its own constructor
    assert isinstance(extra["fp8"]["l1"], fp8.Fp8DotMeta)
    # wrong history length fails loudly, never silently reshapes
    with pytest.raises(ValueError):
        checkpoint.load_train_state(
            path, params=jax.tree.map(jnp.zeros_like, params),
            opt_state=jax.tree.map(jnp.zeros_like, opt_state),
            scaler_state=jax.tree.map(jnp.zeros_like, sstate),
            extra={"fp8": fp8.init_state(["l1", "l2"], history_len=3)})


# ---------------------------------------------------------------------------
# enabled=False: inert-but-present (the PR 6 wrapper-drop bug class)
# ---------------------------------------------------------------------------


def test_initialize_enabled_false_keeps_fp8_surface():
    try:
        model = amp.initialize(_mlp_apply, opt_level="O4", enabled=False,
                               fp8_history_len=4)
        assert not fp8.is_enabled()
        # the documented O4 entry point survives: the returned model
        # still carries init_fp8_state (NOT the bare apply function —
        # the PR 6 wrapper-drop bug class) and still applies
        st0 = model.init_fp8_state(["l1"])
        assert st0["l1"].x.amax_history.shape == (4,)
        pp = {"w1": _rand((4, 8), 11), "w2": _rand((8, 2), 12)}
        xs = jnp.ones((2, 4), jnp.float32)
        np.testing.assert_allclose(np.asarray(model(pp, xs)),
                                   np.asarray(_mlp_apply(pp, xs)))
        x, w = _rand((4, 8), 0), _rand((8, 3), 1)
        meta = fp8.init_dot_meta()
        # fp8_matmul degrades to the plain fp32-accumulated matmul
        got = fp8.fp8_matmul(x, w, meta)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                                   rtol=1e-6)
        # update_state is the identity
        st = fp8.init_state(["l1"])
        assert fp8.update_state(st, st) is st
        # the O4-written train step runs at full precision with the
        # SAME signatures: params update, fp8 state threads through
        params, opt_state, sstate, fstate, step = _mk_fp8_setup()
        xb = jnp.ones((8, 4), jnp.float32)
        yb = jnp.zeros((8, 2), jnp.float32)
        p2, o2, s2, f2, loss = step(params, opt_state, sstate, fstate,
                                    xb, yb)
        assert np.isfinite(float(loss))
        assert not np.array_equal(np.asarray(p2["w1"]),
                                  np.asarray(params["w1"]))
    finally:
        fp8.set_enabled(True)
    # re-initializing re-arms the codec
    amp.initialize(_mlp_apply, opt_level="O4")
    assert fp8.is_enabled()


# ---------------------------------------------------------------------------
# comm: fp8 buckets + scaled gather (the ONE codec)
# ---------------------------------------------------------------------------


def _bucket_bytes(grads, compress, message_size=2048):
    from apex_tpu.parallel.overlap import bucketed_allreduce
    rec = monitor.Recorder(name="fp8-bytes", capacity=256)
    am = AbstractMesh((8,), ("data",))
    fn = shard_map(
        lambda g: bucketed_allreduce(g, "data", message_size=message_size,
                                     compress=compress),
        mesh=am, in_specs=(P(),), out_specs=P(), check_vma=False)
    with monitor.attached(rec):
        jax.make_jaxpr(fn)(grads)
    table = rec.collectives()
    return sum(v["bytes"] for k, v in table.items() if k.endswith("@data"))


def test_fp8_bucket_bytes_leq_055x_bf16():
    """THE acceptance bound: fp8-compressed bucketed allreduce moves
    <= 0.55x the bytes of the bf16 path at matched config (1-byte wire
    vs 2, plus the per-bucket amax pmax scalars), per the monitor's
    trace-time accounting."""
    rng = np.random.RandomState(5)
    grads = {"w1": jnp.asarray(rng.randn(32, 64), jnp.bfloat16),
             "w2": jnp.asarray(rng.randn(64, 8), jnp.bfloat16)}
    b_bf16 = _bucket_bytes(grads, None)
    b_fp8 = _bucket_bytes(grads, "fp8")
    assert b_bf16 > 0
    ratio = b_fp8 / b_bf16
    assert ratio <= 0.55, f"fp8/bf16 wire bytes {ratio:.4f} > 0.55"
    # vs fp32 grads the wire shrinks ~4x
    fgrads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    assert _bucket_bytes(grads, "fp8") / _bucket_bytes(fgrads, None) <= 0.3


def test_fp8_bucket_reduce_parity_within_e5m2_envelope():
    from apex_tpu.parallel.overlap import bucketed_allreduce
    rng = np.random.RandomState(6)
    grads = {"w1": jnp.asarray(rng.randn(16, 32), jnp.float32),
             "w2": jnp.asarray(rng.randn(32, 4), jnp.float32)}
    mesh = Mesh(np.array(jax.devices()), ("data",))

    def run(compress):
        return shard_map(
            lambda g: bucketed_allreduce(g, "data", message_size=1024,
                                         compress=compress),
            mesh=mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False)(grads)

    exact, lossy = run(None), run("fp8")
    for k in exact:
        rel = float(jnp.max(jnp.abs(lossy[k] - exact[k])
                            / (jnp.abs(exact[k]) + 1e-6)))
        # e5m2: 2 mantissa bits -> half-ULP 2^-3 = 0.125; the world-
        # predivide and the sum add a little reassociation slack
        assert rel <= 0.2, (k, rel)


def test_fp8_compress_knob_validation():
    from apex_tpu.parallel.overlap import (accumulate_gradients,
                                           bucketed_allreduce)
    from apex_tpu.parallel.distributed import DistributedDataParallel
    g = {"w": jnp.ones((4,), jnp.float32)}
    with pytest.raises(ValueError, match="compress"):
        bucketed_allreduce(g, "data", compress="int8")
    with pytest.raises(ValueError, match="contradicts"):
        bucketed_allreduce(g, "data", compress="fp8",
                           allreduce_always_fp32=True)
    with pytest.raises(ValueError, match="overlap_comm"):
        accumulate_gradients(lambda p, mb: p, g, (g,), compress="fp8",
                             overlap_comm=False)
    with pytest.raises(ValueError, match="overlap_comm"):
        DistributedDataParallel(_mlp_apply, compress="fp8")
    with pytest.raises(ValueError, match="compress"):
        DistributedDataParallel(_mlp_apply, compress="int8",
                                overlap_comm=True)
    with pytest.raises(ValueError, match="contradicts"):
        DistributedDataParallel(_mlp_apply, compress="fp8",
                                overlap_comm=True,
                                allreduce_always_fp32=True)
    # the valid spelling threads through to flush()
    ddp = DistributedDataParallel(_mlp_apply, compress="fp8",
                                  overlap_comm=True)
    assert ddp.compress == "fp8"


def test_ddp_fp8_flush_end_to_end():
    from apex_tpu.parallel.distributed import DistributedDataParallel
    mesh = Mesh(np.array(jax.devices()), ("data",))
    rng = np.random.RandomState(8)
    grads = {"w": jnp.asarray(rng.randn(64) * 0.1, jnp.float32)}
    ddp = DistributedDataParallel(_mlp_apply, overlap_comm=True,
                                  message_size=64, compress="fp8")
    out = shard_map(ddp.flush, mesh=mesh, in_specs=(P(),), out_specs=P(),
                    check_vma=False)(grads)
    # replicated input: the mean-reduced output equals the input up to
    # the e5m2 wire
    rel = float(jnp.max(jnp.abs(out["w"] - grads["w"])
                        / (jnp.abs(grads["w"]) + 1e-6)))
    assert rel <= 0.2


def test_quantized_all_gather_scaled_unification():
    """Satellite: zero.comm.quantized_all_gather rides the shared codec
    when scaled=True, and scaled=False keeps the bitwise-documented raw
    cast so existing callers/tests see identical wire bytes."""
    from apex_tpu.zero import comm as zcomm
    mesh = Mesh(np.array(jax.devices()), ("data",))
    rng = np.random.RandomState(9)

    def gather(shard, **kw):
        return shard_map(
            lambda t: zcomm.quantized_all_gather(t, "data", **kw),
            mesh=mesh, in_specs=(P("data"),), out_specs=P(),
            check_vma=False)(shard)

    world = len(jax.devices())
    shard = jnp.asarray(rng.randn(8 * world), jnp.float32)
    # default: bitwise the raw e5m2 cast (the documented behavior)
    raw = gather(shard, scaled=False)
    ref = shard.astype(jnp.float8_e5m2).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(raw), np.asarray(ref))
    # scaled: out-of-range values survive (raw would inf out)
    big = shard * 1e5   # beyond e5m2's 57344 max
    raw_big = gather(big, scaled=False)
    scaled_big = gather(big, scaled=True)
    assert bool(jnp.any(~jnp.isfinite(raw_big)))
    assert bool(jnp.all(jnp.isfinite(scaled_big)))
    rel = float(jnp.max(jnp.abs(scaled_big - big) / (jnp.abs(big) + 1e-6)))
    assert rel <= 0.2


def test_zero_optimizer_compress_allgather_scaled_knob():
    from apex_tpu.zero import ZeroOptimizer
    assert ZeroOptimizer(compress_allgather="scaled").compress_allgather \
        == "scaled"
    with pytest.raises(ValueError, match="compress_allgather"):
        ZeroOptimizer(compress_allgather="fp8")


# ---------------------------------------------------------------------------
# monitor purity: the fp8 accounting must vanish when detached
# ---------------------------------------------------------------------------


def test_fp8_bucket_jaxpr_pure_when_detached():
    from apex_tpu.parallel.overlap import bucketed_allreduce
    g = {"w": jnp.ones((32,), jnp.float32)}
    am = AbstractMesh((8,), ("data",))

    def trace():
        return str(jax.make_jaxpr(shard_map(
            lambda g: bucketed_allreduce(g, "data", message_size=64,
                                         compress="fp8"),
            mesh=am, in_specs=(P(),), out_specs=P(), check_vma=False))(g))

    detached = trace()
    rec = monitor.Recorder(name="purity", capacity=64)
    with monitor.attached(rec):
        attached = trace()
    # accounting is host-side bookkeeping only: byte-identical jaxprs
    assert detached == attached


# ---------------------------------------------------------------------------
# GPT convergence (slow): O4 vs bf16, the behavioral parity gate
# ---------------------------------------------------------------------------


def _tiny_gpt_setup(fp8_on, vocab=32, d=32, heads=2, layers=2, seq=16):
    """A real (if tiny) GPT: learned token+position embeddings, causal
    self-attention, MLP blocks — with every projection matmul routed
    through fp8_matmul when fp8_on (the O4 recipe: e4m3 fwd weights/
    activations, e5m2 cotangents) and through bf16 storage otherwise
    (the O2 shape)."""
    rng = np.random.RandomState(0)

    def init_w(*shape, s=0.08):
        return jnp.asarray(rng.randn(*shape) * s, jnp.float32)

    params = {"emb": init_w(vocab, d), "pos": init_w(seq, d)}
    sites = []
    for i in range(layers):
        params[f"qkv{i}"] = init_w(d, 3 * d)
        params[f"o{i}"] = init_w(d, d)
        params[f"m1_{i}"] = init_w(d, 4 * d)
        params[f"m2_{i}"] = init_w(4 * d, d)
        sites += [f"qkv{i}", f"o{i}", f"m1_{i}", f"m2_{i}"]
    params["head"] = init_w(d, vocab)
    sites.append("head")

    def mm(x, w, fstate, site):
        if fp8_on:
            return fp8.fp8_matmul(x, w, fstate[site])
        return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)

    def ln(h):
        mu = jnp.mean(h, -1, keepdims=True)
        var = jnp.var(h, -1, keepdims=True)
        return (h - mu) / jnp.sqrt(var + 1e-5)

    def forward(p, fstate, ids):
        b, s = ids.shape
        h = p["emb"][ids] + p["pos"][None, :s]
        mask = jnp.tril(jnp.ones((s, s), bool))
        for i in range(layers):
            x = ln(h)
            qkv = mm(x.reshape(b * s, d), p[f"qkv{i}"], fstate,
                     f"qkv{i}").reshape(b, s, 3, heads, d // heads)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            att = jnp.einsum("bqhc,bkhc->bhqk", q, k) / np.sqrt(d // heads)
            att = jnp.where(mask[None, None], att, -1e9)
            att = jax.nn.softmax(att, -1)
            o = jnp.einsum("bhqk,bkhc->bqhc", att, v).reshape(b * s, d)
            h = h + mm(o, p[f"o{i}"], fstate, f"o{i}").reshape(b, s, d)
            x = ln(h).reshape(b * s, d)
            m = jax.nn.gelu(mm(x, p[f"m1_{i}"], fstate, f"m1_{i}"))
            h = h + mm(m, p[f"m2_{i}"], fstate, f"m2_{i}").reshape(b, s, d)
        logits = mm(ln(h).reshape(b * s, d), p["head"], fstate, "head")
        return logits.reshape(b, s, vocab)

    def loss_fn_fp8(p, fstate, ids, labels):
        logits = forward(p, fstate, ids)
        lp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(lp, labels[..., None],
                                             -1))

    def loss_fn_plain(p, ids, labels):
        return loss_fn_fp8(p, None, ids, labels)

    return params, sites, (loss_fn_fp8 if fp8_on else loss_fn_plain)


@pytest.mark.slow
def test_gpt_convergence_o4_within_tolerance_of_bf16():
    """The behavioral parity gate (ISSUE 7 acceptance): a tiny GPT
    trained on a deterministic next-token task, O4 (every projection
    through the fp8 delayed-scaling codec) vs the bf16 O2 shape at
    IDENTICAL config/init/data — the mean loss over the last 10 steps
    must agree within rtol 0.2 (the documented O4 tolerance,
    docs/amp.md), and both runs must actually converge."""
    vocab, seq, batch, steps = 32, 16, 16, 150
    rng = np.random.RandomState(3)
    # first-order structure the model can learn: t+1 = 5*t + 3 mod V,
    # with 20% uniform noise so the optimum has nonzero entropy (a
    # near-zero floor would make any relative comparison degenerate)
    starts = rng.randint(0, vocab, (batch,))
    seqs = np.zeros((batch, seq + 1), np.int64)
    seqs[:, 0] = starts
    for t in range(seq):
        nxt = (5 * seqs[:, t] + 3) % vocab
        noise = rng.randint(0, vocab, (batch,))
        take_noise = rng.rand(batch) < 0.2
        seqs[:, t + 1] = np.where(take_noise, noise, nxt)
    ids = jnp.asarray(seqs[:, :-1], jnp.int32)
    labels = jnp.asarray(seqs[:, 1:], jnp.int32)

    def run(fp8_on):
        params, sites, loss_fn = _tiny_gpt_setup(fp8_on, vocab=vocab,
                                                 seq=seq)
        opt = FusedAdam(lr=2e-3)
        tail = []
        if fp8_on:
            step = amp.make_train_step(loss_fn, opt, fp8=True,
                                       donate=False)
            p, o, s = params, opt.init(params), scaler_mod.init_state()
            f = fp8.init_state(sites, history_len=8)
            for i in range(steps):
                p, o, s, f, loss = step(p, o, s, f, ids, labels)
                if i >= steps - 10:
                    tail.append(float(loss))
        else:
            step = amp.make_train_step(loss_fn, opt, donate=False)
            p, o, s = params, opt.init(params), scaler_mod.init_state()
            for i in range(steps):
                p, o, s, loss = step(p, o, s, ids, labels)
                if i >= steps - 10:
                    tail.append(float(loss))
        return float(np.mean(tail))

    l_o4, l_bf16 = run(True), run(False)
    ceiling = float(np.log(vocab))          # uniform-prediction loss
    assert l_bf16 < 0.75 * ceiling          # the baseline really learned
    assert l_o4 < 0.75 * ceiling            # and so did O4
    assert l_o4 == pytest.approx(l_bf16, rel=0.2), (l_o4, l_bf16)
