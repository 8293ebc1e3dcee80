"""Per-module cost attribution (``apex_tpu.monitor.profile`` for the scopes,
``apex_tpu.monitor.attribution`` for the tools that read them).

Covers the tentpole contract: scope nesting (host path + name-stack
tagging), analytic vs measured attribution on a tiny model, scan
trip-count multipliers, collective-byte accounting, disabled-mode
jaxpr byte-identity, the threaded-scope coverage acceptance bound on a
tiny GPT amp train step (>= 90% of analytic step FLOPs under named
scopes), and the ``report.aggregate()["profile"]`` round trip.
"""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import monitor
from apex_tpu.monitor import attribution as attr
from apex_tpu.monitor import profile as prof
from apex_tpu.monitor.report import aggregate, load_jsonl


def _two_layer(x, w1, w2):
    with prof.scope("layer1"):
        h = jnp.tanh(x @ w1)
    with prof.scope("head"):
        return jnp.sum(h @ w2)


def _args():
    return (jnp.ones((8, 16)), jnp.ones((16, 32)), jnp.ones((32, 4)))


# ---------------------------------------------------------------------------
# scope mechanics
# ---------------------------------------------------------------------------

def test_scope_nesting_builds_paths():
    seen = []
    with prof.scope("outer"):
        seen.append(prof.current_scope())
        with prof.scope("inner"):
            seen.append(prof.current_scope())
        with prof.scope("sibling/with/slashes"):
            seen.append(prof.current_scope())
    assert prof.current_scope() == ""
    assert seen == ["outer", "outer/inner", "outer/sibling_with_slashes"]


def test_scope_unwinds_on_exception():
    with pytest.raises(RuntimeError):
        with prof.scope("a"):
            with prof.scope("b"):
                raise RuntimeError("boom")
    assert prof.current_scope() == ""


def test_scoped_decorator():
    @prof.scoped("deco")
    def f():
        return prof.current_scope()

    assert f() == "deco"


# ---------------------------------------------------------------------------
# analytic attribution
# ---------------------------------------------------------------------------

def test_analytic_attribution_charges_innermost_scope():
    g = jax.value_and_grad(_two_layer, argnums=(1, 2))
    p = attr.analytic_profile(g, *_args())
    rows = p["scopes"]
    assert set(rows) == {"layer1", "head"}
    # fwd+bwd dot flops: layer1 fwd 2*8*16*32 + bwd dx/dw each same
    assert rows["layer1"]["flops"] > rows["head"]["flops"] > 0
    assert rows["layer1"]["hbm_bytes"] > 0
    assert p["flops_scope_coverage"] == 1.0
    assert p["total"]["flops"] == sum(r["flops"] for r in rows.values())


def test_analytic_scan_multiplies_trip_count():
    w = jnp.ones((16, 16))

    def once(x, w):
        with prof.scope("blk"):
            return jnp.tanh(x @ w)

    def scanned(x, w):
        def body(c, _):
            return once(c, w), None
        c, _ = jax.lax.scan(body, x, None, length=4)
        return c

    x = jnp.ones((8, 16))
    p1 = attr.analytic_profile(once, x, w)
    p4 = attr.analytic_profile(scanned, x, w)
    assert p4["scopes"]["blk"]["flops"] == 4 * p1["scopes"]["blk"]["flops"]


def test_analytic_collective_bytes_convention():
    from jax.sharding import PartitionSpec as P
    from apex_tpu._compat import shard_map
    from apex_tpu.transformer import parallel_state as ps

    ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(tensor_model_parallel_size_=2)

    def body(x):
        with prof.scope("reduce"):
            return jax.lax.psum(x, ps.TENSOR_AXIS)

    fn = shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                   check_vma=False)
    x = jnp.ones((4, 8), jnp.float32)
    p = attr.analytic_profile(fn, x)
    row = p["scopes"]["reduce"]
    # operand bytes, the trace-time collective-table convention
    assert row["collective_bytes"] == 4 * 8 * 4
    ps.destroy_model_parallel()


def test_analytic_unscoped_row_and_coverage():
    def f(x, w):
        y = x @ w                       # unscoped
        with prof.scope("s"):
            return jnp.sum(jnp.tanh(y))

    p = attr.analytic_profile(f, jnp.ones((8, 16)), jnp.ones((16, 16)))
    assert prof.UNSCOPED in p["scopes"]
    assert 0.0 < p["flops_scope_coverage"] < 1.0
    assert p["unscoped"]["flops"] == p["scopes"][prof.UNSCOPED]["flops"]


# ---------------------------------------------------------------------------
# measured mode
# ---------------------------------------------------------------------------

def test_measured_profile_samples_scope_wall_time():
    g = jax.value_and_grad(_two_layer, argnums=(1, 2))
    rec = monitor.Recorder(name="t")
    m = attr.measured_profile(g, *_args(), repeats=2, recorder=rec)
    assert set(m["scopes"]) == {"layer1", "head"}
    for row in m["scopes"].values():
        assert row["n"] == 2
        assert row["total_s"] > 0
    # measured and analytic agree on the scope vocabulary
    a = attr.analytic_profile(g, *_args())
    assert set(m["scopes"]) == set(a["scopes"])


def test_measured_profile_does_not_leak_measure_flag():
    attr.measured_profile(lambda x: _two_layer(x, *_args()[1:]),
                          _args()[0], repeats=1)
    rec = monitor.Recorder(name="after")
    with monitor.attached(rec):
        with prof.scope("quiet"):
            pass
    assert not rec.aggregate().get("timers")


# ---------------------------------------------------------------------------
# purity: scopes never change the traced program
# ---------------------------------------------------------------------------

def test_disabled_mode_jaxpr_byte_identity():
    def plain(x, w1, w2):
        h = jnp.tanh(x @ w1)
        return jnp.sum(h @ w2)

    args = _args()
    scoped_jx = str(jax.make_jaxpr(
        jax.value_and_grad(_two_layer, argnums=(1, 2)))(*args))
    plain_jx = str(jax.make_jaxpr(
        jax.value_and_grad(plain, argnums=(1, 2)))(*args))
    assert scoped_jx == plain_jx
    # and attaching a recorder changes nothing either (scope inserts
    # metadata, not operations — unlike the traced hooks, there is no
    # instrumented variant)
    rec = monitor.Recorder(name="t")
    with monitor.attached(rec):
        attached_jx = str(jax.make_jaxpr(
            jax.value_and_grad(_two_layer, argnums=(1, 2)))(*args))
    assert attached_jx == plain_jx
    assert "callback" not in scoped_jx


# ---------------------------------------------------------------------------
# the threaded scopes: tiny-GPT amp step coverage (acceptance bound)
# ---------------------------------------------------------------------------

def _tiny_gpt_step():
    from apex_tpu import amp
    from apex_tpu.amp import scaler as scaler_mod
    from apex_tpu.models import GPT, GPTConfig
    from apex_tpu.optimizers import FusedSGD
    from apex_tpu.transformer import parallel_state as ps

    ps.destroy_model_parallel()
    cfg = GPTConfig(vocab_size=128, max_seq_len=32, hidden_size=32,
                    num_layers=2, num_heads=2, dtype=jnp.float32,
                    attention_impl="fused_softmax", fused_lm_head=False)
    model = GPT(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 128, (2, 16)), jnp.int32)
    labels = jnp.asarray(np.roll(np.asarray(ids), -1, 1))
    variables = model.init(jax.random.PRNGKey(0), ids)
    opt = FusedSGD(lr=0.01)
    step = amp.make_train_step(model.loss, opt, donate=False)
    return step, (variables, opt.init(variables),
                  scaler_mod.init_state(2.0 ** 8), ids, labels)


def test_tiny_gpt_step_scope_coverage_at_least_90pct():
    step, args = _tiny_gpt_step()
    p = attr.analytic_profile(step, *args)
    assert p["flops_scope_coverage"] >= 0.9, (
        p["flops_scope_coverage"], p["unscoped"])
    # the per-module vocabulary is present: TP layer names, the
    # attention core and the amp phases all have rows
    names = set(p["scopes"])
    for expect in ("qkv", "proj", "fc1", "fc2", "attn_core",
                   "wte_attend", "vocab_ce"):
        assert any(expect in n for n in names), (expect, names)
    assert any(n.startswith("amp_optimizer") for n in names), names


def test_tiny_gpt_step_jaxpr_unchanged_by_recorder_attach():
    # the whole threaded-scope surface stays pure: tracing the step
    # detached and attached (host-only recorder) yields identical
    # programs
    step, args = _tiny_gpt_step()
    detached = str(jax.make_jaxpr(step)(*args))
    rec = monitor.Recorder(name="t", traced_hooks=False)
    with monitor.attached(rec):
        attached = str(jax.make_jaxpr(step)(*args))
    assert detached == attached


def _example_gpt_step():
    """``examples/gpt/main_gpt.py:make_step_fns`` on a dp=2 x tp=2 mesh of
    virtual devices, tiny."""
    import importlib.util
    import os
    from apex_tpu.models import GPT, GPTConfig
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state as ps
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "gpt", "main_gpt.py")
    spec = importlib.util.spec_from_file_location("main_gpt", path)
    main_gpt = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(main_gpt)
    ps.destroy_model_parallel()
    mesh = ps.initialize_model_parallel(tensor_model_parallel_size_=2,
                                        devices=jax.devices()[:4])
    model = GPT(GPTConfig(vocab_size=128, max_seq_len=16, hidden_size=32,
                          num_layers=1, num_heads=2, dtype=jnp.bfloat16,
                          sequence_parallel=True))
    init_f, step_f = main_gpt.make_step_fns(
        mesh, model, FusedAdam(lr=1e-3, master_weights=True))
    ids = jnp.zeros((4, 16), jnp.int32)
    return step_f, (*jax.eval_shape(init_f, ids), ids, ids)


@pytest.mark.parametrize("build", [_tiny_gpt_step, _example_gpt_step],
                         ids=["amp_step", "example_step"])
def test_step_jaxpr_byte_identical_with_and_without_the_scopes(
        build, monkeypatch):
    """The phase scopes of both train steps (``amp_grad`` ..
    ``amp_scaler``) and every scope below them are metadata: the step
    traced with ``scope`` turned into a no-op is the same program."""
    import contextlib
    from apex_tpu.transformer import parallel_state as ps
    try:
        step, args = build()
        scoped = str(jax.make_jaxpr(step)(*args))
        monkeypatch.setattr(prof, "scope",
                            lambda name: contextlib.nullcontext())
        step, args = build()
        assert str(jax.make_jaxpr(step)(*args)) == scoped
    finally:
        ps.destroy_model_parallel()


# ---------------------------------------------------------------------------
# recorder / report integration
# ---------------------------------------------------------------------------

def test_record_and_aggregate_profile_block():
    g = jax.value_and_grad(_two_layer, argnums=(1, 2))
    rec = monitor.Recorder(name="t")
    with monitor.attached(rec):
        p = attr.analytic_profile(g, *_args(), record=True)
    buf = io.StringIO()
    rec.dump_jsonl(buf)
    buf.seek(0)
    header, events = load_jsonl(buf)
    agg = aggregate(events, header=header)
    block = agg["profile"]["analytic"]
    assert block["layer1"]["flops"] == p["scopes"]["layer1"]["flops"]
    assert block["(total)"]["flops_scope_coverage"] == 1.0
    # and the rendered report carries the table
    from apex_tpu.monitor.report import render_report
    assert "per-module cost attribution" in render_report(
        events, header=header)


def test_render_profile_table():
    g = jax.value_and_grad(_two_layer, argnums=(1, 2))
    p = attr.analytic_profile(g, *_args())
    table = attr.render_profile(p)
    assert "layer1" in table and "head" in table
    assert "coverage 100.0%" in table


def test_kernel_vmem_note_reuses_tune_accounting():
    from apex_tpu.tune import vmem
    note = attr.kernel_vmem_note("flash_attention_fwd", block_q=128,
                                 block_k=128, d=64, itemsize=2)
    assert note["vmem_bytes"] == vmem.vmem_estimate(
        "flash_attention_fwd", block_q=128, block_k=128, d=64, itemsize=2)
    assert note["vmem_budget_bytes"] == vmem.FLASH_VMEM_BUDGET
    assert attr.kernel_vmem_note("nope") is None


def test_profile_cli_json(capsys):
    from apex_tpu.monitor.__main__ import main
    rc = main(["profile", "--model", "mlp", "--hidden", "8",
               "--batch", "2", "--json"])
    assert rc == 0
    import json
    out = json.loads(capsys.readouterr().out)
    assert out["analytic"]["flops_scope_coverage"] > 0.9
    assert any(n.startswith("amp_grad")
               for n in out["analytic"]["scopes"])
