"""The ``qwen3_next`` family rehearsed at a tiny size on the CPU (the same
``run_cell`` path as on the chip, from a copy of the benchmark's data with
the tiny cell ADDED), its operation and byte counts against
``cost_analysis()`` of the unfused program, and its readers. Run by hand
with the other benchmark tests. Counts no cells: a later PR adds its own."""

import types

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import bytes as bytes_mod
from benchmarks.harness import counts_qwen3_next as counts
from benchmarks.harness import manifest, peaks

from . import _tiny

CELL = "qwen3next-train-s16384"
CONFIG = "qwen3-next-80b-ep32-l4"
NEW_READERS = ("gdn_attn_share", "gdn_scan_share", "gdn_scan_fwd_roofline",
               "gdn_scan_bwd_roofline", "shared_expert_share")

#: every structure of the real file at the smallest sizes the kernels take
#: (heads of 128 lanes): one key head under two value heads, a group of two
#: query heads a key/value head, a rotary over the first 32 of 128 lanes,
#: three gated-delta layers to a full one, 8 experts top 2 of which this
#: share holds 4, a shared expert
PUBLISHED = {
    "model_type": "qwen3_next", "vocab_size": 384, "hidden_size": 128,
    "intermediate_size": 256, "moe_intermediate_size": 64,
    "shared_expert_intermediate_size": 64, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
    "linear_num_key_heads": 1, "linear_num_value_heads": 2,
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "full_attention_interval": 4,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "rope_scaling": None, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "decoder_sparse_step": 1,
    "mlp_only_layers": []}

TINY_QWEN = {
    "name": "tiny-qwen3-next", "family": "qwen3_next", "source": "test",
    **PUBLISHED, "num_hidden_layers": 4, "num_experts": 4, "vocab_size": 96,
    "published": PUBLISHED,
    "held": {"first_expert": 2, "local_experts": 4},
    "assumed": {"dtype": "bfloat16", "initializer_std": 0.02,
                "fp32_leaves": ["A_log", "dt_bias"]},
    # about twice what the checks read at this size on the CPU
    "logit_tolerance": 0.03, "routing_tie_distance": 0.05,
    "grad_tolerance": 0.1,
    "reduced": ["num_hidden_layers", "num_experts", "vocab_size"],
    "departures": [], "deployment": "a test"}

TINY_TRAIN = {**_tiny.TINY_MLM, "mask_share": None, "batch": 1, "seq": 128,
              "optimizer": {"name": "FusedAdam", "lr": 1e-3},
              "check": {"shape": [1, 128]}}

CELLS = {"tiny-qwen3-next-train": (TINY_QWEN, TINY_TRAIN, 1)}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return _tiny.make_root(tmp_path_factory.mktemp("bench"), CELLS)


def _phase(lines, name):
    return next(l for l in lines if l["phase"] == name)


# -- the files ---------------------------------------------------------------------

def test_the_files_top_level_is_the_published_config_but_for_reduced():
    man = manifest.load_manifest()
    body = manifest.load_config(man, CONFIG)
    pub = body["published"]
    assert {k for k in pub if body[k] != pub[k]} == set(body["reduced"]) \
        == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert body["family"] == "qwen3_next"
    assert (body["num_hidden_layers"], body["num_experts"],
            body["vocab_size"]) == (4, 16, 18992)
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (48, 512, 151936)
    assert body["vocab_size"] * 8 == pub["vocab_size"]
    assert body["held"]["first_expert"] == 0
    assert body["held"]["local_experts"] == 16
    # the widths are the published ones
    for k in ("hidden_size", "head_dim", "moe_intermediate_size",
              "shared_expert_intermediate_size", "num_attention_heads",
              "num_key_value_heads", "num_experts_per_tok",
              "linear_num_key_heads", "linear_num_value_heads",
              "linear_key_head_dim", "linear_value_head_dim",
              "linear_conv_kernel_dim", "partial_rotary_factor"):
        assert body[k] == pub[k]
    assert 0 < body["logit_tolerance"] < 0.05
    for k in ("logit_tolerance", "routing_tie_distance", "grad_tolerance"):
        assert len(body[k + "_why"]) > 100
    assert body["assumed"]["fp32_leaves"] == ["A_log", "dt_bias"]


def test_the_cell_is_in_the_manifest_with_its_traffic_letter_for_letter():
    man = manifest.load_manifest()
    cell = manifest.find_workload(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "train-s16384-ep32share", 1)
    t = manifest.load_traffic(cell["traffic"])
    want = {"kind": "train", "rate_metric": "train_tokens_per_s",
            "entry": "amp", "opt_level": "O2",
            "optimizer": {"name": "FusedAdam", "lr": 1e-06},
            "batch": 1, "seq": 16384, "ring": 16, "fetch_every": 8,
            "groups_in_flight": 2, "rate_from": "median_group",
            "warmup_steps": 2, "trace_steps": 8,
            "check": {"shape": [1, 16384]}}
    assert {k: t[k] for k in want} == want
    mine = {m["name"] for m in manifest.metrics_for(man, "per_layer", CELL)}
    assert mine >= {
        "train_mfu", "step_hbm_gb", "tune_hit_share",
        "pallas_time_share.train", "device_idle_share.train",
        "step_forward_share", "step_backward_share", "step_update_share",
        "moe_time_share.train", "moe_route_share.train",
        "full_attention_roofline", "moe_grouped_matmul_train_roofline",
        "full_attn_share", "moe_train_expert_imbalance", *NEW_READERS}
    # the accepted flash rooflines count one causal shape for every layer,
    # and this model has no window layer
    assert not {m for m in mine if m.startswith(("flash_attention",
                                                 "window_"))}
    for m in man["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tokens_per_s"
    assert manifest.load_family("qwen3_next").build_train


# -- the rehearsal -----------------------------------------------------------------

def test_train_cell_rehearsal(copy):
    root, man = copy
    lines, res = _tiny.run(root, man, "tiny-qwen3-next-train", seconds=0.5)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    check = _phase(lines, "reference-check")
    assert check["ok"] and check["logit_rel_err"] <= 0.03
    assert check["routing_rows_compared"] == 4 * 128
    assert check["routing_tie_distance"] <= 0.05
    from benchmarks.families import qwen3_next as fam
    assert set(check["grad_rel_err"]) == {"/".join(p)
                                          for p in fam.GRAD_LEAVES}
    assert len(fam.GRAD_LEAVES) == 15
    assert max(check["grad_rel_err"].values()) <= 0.1
    assert check["loss_sum"] == pytest.approx(check["loss_sum_reference"],
                                              rel=0.01)
    built = _phase(lines, "built")
    assert built["info"]["moe"]["experts_held"] == 4
    assert built["info"]["gdn"] == {
        "fwd_kernel": r"^apx_gdn_(chunk|scan)_fwd",
        "bwd_kernel": r"^apx_gdn_(chunk|scan)_bwd",
        "layers": 3, "batch": 1, "heads": 2, "seq": 128, "chunk": 128,
        "d_k": 128, "d_v": 128}
    assert built["info"]["recompute"] == "block"
    w = _phase(lines, "train-window")
    assert w["groups_in_flight"] == 2 and all(w["verdict"].values())
    assert _phase(lines, "window")["compiles_in_window"] == 0


def test_the_decay_leaves_stay_float32_under_amp(copy):
    root, man = copy
    fam = manifest.load_family("qwen3_next")
    config = manifest.load_config(man, "tiny-qwen3-next", root)
    prog = fam.build_train(config, TINY_TRAIN, 3)
    gdn = prog.state[0]["layer_0"]["gdn"]
    assert gdn["A_log"].dtype == gdn["dt_bias"].dtype == jnp.float32
    assert gdn["qkvz"].dtype == gdn["norm"].dtype == jnp.bfloat16


def test_traced_rehearsal_reads_the_steps_counters(copy):
    """No device plane on the CPU: the trace's shares and rooflines are left
    out; the readers of the program's counters are not, and no new reader
    raises."""
    root, man = copy
    _, res = _tiny.run(root, man, "tiny-qwen3-next-train", trace=True,
                       seconds=0.5)
    assert {"compiles_in_window", "train_mfu", "step_hbm_gb",
            "moe_train_expert_imbalance"} <= set(res["metrics"])
    assert 1.0 <= res["metrics"]["moe_train_expert_imbalance"]["value"] <= 4.0
    for name in NEW_READERS + ("full_attention_roofline", "full_attn_share"):
        assert name not in res["metrics"]


# -- the readers on a made-up run ------------------------------------------------------

def _reader(name):
    return manifest.load_layer_metric(name)


def _fake_run(kernel_s, steps=2):
    prog = types.SimpleNamespace(
        attention={"kind": "banded"},
        info={"gdn": {"fwd_kernel": r"^apx_gdn_(chunk|scan)_fwd",
                      "bwd_kernel": r"^apx_gdn_(chunk|scan)_bwd", "layers": 3,
                      "batch": 1, "heads": 32, "seq": 16384, "chunk": 64,
                      "d_k": 128, "d_v": 128}})
    return {"program": prog, "trace": {"kernel_s": kernel_s},
            "traced": {"steps": steps}, "peak": peaks.peak_for("TPU v5 lite"),
            "notes": {}}


def test_scan_rooflines_read_their_own_instructions():
    peak = peaks.peak_for("TPU v5 lite")
    run = _fake_run({"apx_gdn_scan_fwd": 0.020, "apx_gdn_chunk_fwd": 0.010,
                     "apx_gdn_scan_bwd": 0.025, "apx_gdn_chunk_bwd": 0.015,
                     "apx_flash_attention_fwd": 9.0})
    shape = (32, 16384, 64, 128, 128)
    for direction, took in (("fwd", 0.030), ("bwd", 0.040)):
        least, bound = bytes_mod.roofline_seconds(
            getattr(counts, f"scan_{direction}_flops")(*shape),
            getattr(counts, f"scan_{direction}_bytes")(*shape), peak)
        got = _reader(f"gdn_scan_{direction}_roofline").compute(run)
        assert got == pytest.approx(100 * least * 3 * 2 / took)
        assert run["notes"][f"gdn_scan_{direction}_roofline"]["bound"] == \
            bound
    # the walk streams its operands: its bytes bind, not its products
    assert run["notes"]["gdn_scan_fwd_roofline"]["bound"] == "memory"


def test_new_readers_find_nothing_in_a_program_without_the_scan():
    """The driver lays these files over the parent's checkout: in a cell of
    another family, or without a trace, each reader returns None."""
    gpt = types.SimpleNamespace(attention={"kind": "flash"}, info=None)
    run = {"program": gpt, "trace": {"kernel_s": {}}, "traced": {"steps": 2},
           "notes": {}, "workload": "nowhere"}
    for name in NEW_READERS:
        assert _reader(name).compute(run) is None
    mellum = types.SimpleNamespace(attention={"kind": "banded"},
                                   info={"moe": {"layers": 4}})
    for name in ("gdn_scan_fwd_roofline", "gdn_scan_bwd_roofline"):
        assert _reader(name).compute({**run, "program": mellum}) is None
    run = _fake_run({})
    assert _reader("gdn_scan_fwd_roofline").compute(run) is None
    run["trace"] = None
    assert _reader("gdn_scan_bwd_roofline").compute(run) is None


# -- the counts ------------------------------------------------------------------------

def _xla_flops(fn, *args):
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    return float(ca["flops"])


def test_rule_flops_match_the_unfused_chunk_and_its_gradient():
    """One chunk of one head, unfused, the solve left out of both sides:
    the forward's eight products and the backward's sixteen (XLA's gradient
    rebuilds nothing)."""
    c, dk, dv = 64, 128, 128
    f32 = jnp.float32

    def chunk(S, q, k, v, T, D):
        w_v, w_k = T @ v, T @ k
        u = w_v - w_k @ S
        o = q @ S + ((q @ k.T) * D) @ u
        return jnp.sum(o) + jnp.sum(S + k.T @ u) + jnp.sum((k @ k.T) * D)

    args = (jnp.zeros((dk, dv), f32), jnp.zeros((c, dk), f32),
            jnp.zeros((c, dk), f32), jnp.zeros((c, dv), f32),
            jnp.zeros((c, c), f32), jnp.zeros((c, c), f32))
    solve_f, solve_b = 2.0 * c ** 3 / 3, 2.0 * 2 * c ** 3
    fwd = counts.scan_fwd_flops(1, c, c, dk, dv) - solve_f
    bwd = counts.scan_bwd_flops(1, c, c, dk, dv) - solve_b
    assert fwd == pytest.approx(2.0 * (3 * c * c * dk + c * c * dv
                                       + 3 * c * dk * dv + c * c * dv))
    assert _xla_flops(chunk, *args) == pytest.approx(fwd, rel=0.05)
    both = _xla_flops(jax.value_and_grad(chunk, (0, 1, 2, 3, 4)), *args)
    assert both == pytest.approx(fwd + bwd, rel=0.05)
    # 256 chunks of 32 heads: the count is linear in both
    assert counts.scan_fwd_flops(32, 16384, c, dk, dv) == pytest.approx(
        32 * 256 * (fwd + solve_f))
    # a sequence the chunk does not divide pays for its padded tail
    assert counts.scan_fwd_flops(1, 65, c, dk, dv) == pytest.approx(
        2 * (fwd + solve_f))


def test_rule_bytes_are_each_operand_once_a_kernel():
    H, T, c, dk, dv = 32, 16384, 64, 128, 128
    rows, chunks = H * T, H * T // c
    operands = rows * (dv + 3 * dk + c) * 2 + chunks * dv * 4
    inputs = rows * ((2 * dk + dv) * 2 + 8)
    assert counts.scan_fwd_bytes(H, T, c, dk, dv) == \
        inputs + 2 * operands + rows * dv * 2 + H * dk * dv * 4
    bwd = counts.scan_bwd_bytes(H, T, c, dk, dv)
    assert bwd == 3 * operands + 2 * inputs + rows * dv * 2 \
        + (chunks + H) * dk * dv * 4
    # the float32 state each chunk met is the largest single operand
    assert chunks * dk * dv * 4 / bwd == pytest.approx(0.16, abs=0.01)


def test_the_published_step():
    pub = manifest.load_config(manifest.load_manifest(), CONFIG)["published"]
    gdn = counts.gated_delta_flops_per_token(pub, 128)
    # projections in 50.6M and out 16.8M, the rule 8.4M (5.8M at chunks of
    # 64: a chunk's own products grow with it), the convolution
    assert gdn == pytest.approx(75.8e6, rel=5e-3)
    rule = 32 * 2.0 * (3 * 128 * 128 + 2 * 128 * 128 + 3 * 128 * 128)
    assert rule == pytest.approx(8.39e6, rel=1e-2)
    kinds = ["linear_attention"] * 3 + ["full_attention"]
    fwd = counts.forward_flops_per_token(
        pub, kinds, 16384, chunk=128, experts_held=16, vocab_held=18992)
    head = 2.0 * 2048 * 18992
    triangle = 4.0 * 8192.5 * 16 * 256
    assert triangle == pytest.approx(134.2e6, rel=1e-3)
    assert fwd == pytest.approx(3 * gdn + 2.0 * 2048 * (3 * 4096 + 2 * 512)
                                + triangle + head
                                + 4 * (2.0 * 2048 * 512 + 10 * 16 / 512 * 6.0
                                       * 2048 * 512 + 6.0 * 2048 * 512
                                       + 4096.0))
    assert fwd == pytest.approx(535.5e6, rel=1e-3)
    # the routed experts held here are under 2% of a token's work
    assert 4 * 10 * 16 / 512 * 6.0 * 2048 * 512 / fwd < 0.02
