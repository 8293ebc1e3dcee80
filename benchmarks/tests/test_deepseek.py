"""The ``deepseek`` family rehearsed at a tiny size on the CPU (the same
``run_cell`` path as on the chip, from a copy of the benchmark's data with
the tiny cell ADDED), its operation and byte counts, and the readers of its
counters. Run by hand with the other benchmark tests."""

import pytest

from benchmarks.harness import counts_deepseek as counts
from benchmarks.harness import manifest

from . import _tiny

ROPE = {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "rope_type": "yarn"}

#: every structure of the real file at widths of tens: three kinds of layer,
#: five different head and rank sizes, 32 experts in 4 groups of which this
#: share holds 8 (the second quarter), top 4 of 2 groups
PUBLISHED = {
    "model_type": "deepseek_v3", "vocab_size": 384, "hidden_size": 64,
    "intermediate_size": 160, "moe_intermediate_size": 32,
    "num_hidden_layers": 6, "num_attention_heads": 4,
    "n_shared_experts": 1, "n_routed_experts": 32,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 32, "q_lora_rank": 48,
    "qk_rope_head_dim": 8, "v_head_dim": 24, "qk_nope_head_dim": 16,
    "n_group": 4, "topk_group": 2, "num_experts_per_tok": 4,
    "first_k_dense_replace": 2, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": ROPE}

TINY_DEEPSEEK = {
    "name": "tiny-deepseek", "family": "deepseek", "source": "test",
    # the top level as it is run: the three reduced keys are the share's
    **PUBLISHED, "num_hidden_layers": 3, "n_routed_experts": 8,
    "vocab_size": 96,
    "published": PUBLISHED,
    "held": {"dense_layers": 1, "first_expert": 8},
    "assumed": {"dtype": "bfloat16", "initializer_std": 0.02},
    # twice the largest bf16 error the checks read at this size on the CPU
    "logit_tolerance": 0.03, "routing_tie_distance": 0.02,
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
    "departures": [], "deployment": "a test"}

CELLS = {"tiny-latent": (TINY_DEEPSEEK, _tiny.TINY_SERVE, 1)}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return _tiny.make_root(tmp_path_factory.mktemp("bench"), CELLS)


def _phase(lines, name):
    return next(l for l in lines if l["phase"] == name)


def test_the_files_top_level_is_the_published_config_but_for_reduced():
    """The driver holds a catalog model's file to its catalog entry: every
    ``config.json`` key at the TOP level under its own name, at the published
    value unless ``reduced`` lists it (PR 26 was refused once for keeping
    them under ``published`` alone)."""
    man = manifest.load_manifest()
    for c in man["configs"]:
        body = manifest.load_config(man, c["name"])
        if body["family"] != "deepseek":
            continue
        pub = body["published"]
        differ = {k for k in pub if body[k] != pub[k]}
        assert differ == set(body["reduced"]), differ
        assert body["n_routed_experts"] < pub["n_routed_experts"]
        assert set(body["held"]) >= {"dense_layers", "first_expert"}


def test_family_is_found_by_name():
    assert manifest.load_family("deepseek").build_serve


def test_serve_cell_rehearsal(copy):
    root, man = copy
    lines, res = _tiny.run(root, man, "tiny-latent", seconds=1.0)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    check = _phase(lines, "reference-check")
    assert check["ok"] and check["routing_tie_distance"] <= 0.02
    # every fed row of both requests, in both expert layers
    assert check["routing_rows_compared"] == 2 * sum(
        n + 2 for n in check["prompt_lens"])
    built = _phase(lines, "built")
    assert built["info"]["latent_row_lanes"] == 128      # 32 + 8, padded
    assert built["info"]["moe"]["experts_held"] == 8
    assert _phase(lines, "serve-window")["completed"] > 0


def test_a_choice_that_differs_is_held_to_a_near_tie(copy):
    """Seed 1 at this size: one bf16 row picks another expert than the
    float32 reference. The check measures its tie distance over every row
    at ONE shape (the padding's rows are masked out), and passes."""
    root, man = copy
    lines, res = _tiny.run(root, man, "tiny-latent", seed=1, seconds=0.3)
    check = _phase(lines, "reference-check")
    assert check["routing_rows_that_differ"] >= 1
    assert 0 < check["routing_tie_distance"] <= 0.02
    assert check["ok"] and res["correct"] is True


def test_traced_rehearsal_reads_the_programs_counters(copy):
    """No device plane on the CPU: the trace's shares are left out, the
    counter's reader is not."""
    root, man = copy
    _, res = _tiny.run(root, man, "tiny-latent", trace=True, seconds=1.0)
    assert {"compiles_in_window", "decode_step_ms_p50",
            "moe_expert_imbalance"} <= set(res["metrics"])
    # the fullest of 8 experts holds at least the mean, at most everything
    assert 1.0 <= res["metrics"]["moe_expert_imbalance"]["value"] <= 8.0
    assert "moe_time_share" not in res["metrics"]
    assert "mla_decode_attention_roofline" not in res["metrics"]


def test_readers_find_nothing_in_a_gpt_cell():
    """What a program that lacks the counters and the shapes gives: none."""
    from types import SimpleNamespace
    run = {"program": SimpleNamespace(attention={"kind": "paged_decode"},
                                      info={}),
           "trace": {"op_s": {}, "kernel_s": {}}, "traced": {"rounds": 3},
           "window_events": [], "notes": {}, "workload": "none"}
    bench = manifest.BENCH_DIR
    for name in ("mla_decode_attention_roofline",
                 "moe_grouped_matmul_roofline", "moe_expert_imbalance"):
        assert manifest.load_layer_metric(name, bench).compute(run) is None


def test_counts_at_the_published_shapes():
    # 2 x 64 x (576 + 512) FLOPs and 1,152 B a cached token: 121 FLOP/B
    f = counts.mla_decode_flops(1000, 64, 576, 512)
    b = counts.mla_decode_bytes(1000, 64, 576, 512, batch=0)
    assert f == 1000 * 2 * 64 * 1088 and b == 1000 * 1152
    assert round(f / b) == 121
    # an expert is 3 x 7168 x 2048 weights: 88 MB in bf16, 6 FLOPs a weight
    # a row
    assert counts.moe_expert_bytes(0, 16, 7168, 2048) == \
        16 * 3 * 7168 * 2048 * 2
    assert counts.moe_expert_flops(128, 7168, 2048) == \
        128 * 6 * 7168 * 2048


def test_rounds_are_cut_by_layer_zero():
    ev = [{"kind": "counter", "name": "moe/assignments_local", "value": v,
           "layer": i} for v, i in ((5, 0), (7, 1), (6, 0), (2, 1))]
    run = {"window_events": ev, "traced": {"step_lo": 1, "step_hi": 2}}
    assert counts.per_round(run, "assignments_local") == [[5, 7], [6, 2]]
    assert counts.traced(run, "assignments_local") == [[6, 2]]
    assert counts.per_round(run, "expert_load_max") == []
