"""The ``minicpm_sala`` family rehearsed at a tiny size on the CPU (the same
``run_cell`` path as on the chip, from a copy of the benchmark's data with
the tiny cell ADDED), the readers of its counters and scopes, and its
operation and byte counts at the published shapes. Tier-1 runs this file
through ``tests/test_benchmark_entry.py``."""

import contextlib
import json
import os
import statistics
from types import SimpleNamespace

import pytest

from benchmarks.harness import counts_minicpm_sala as counts
from benchmarks.harness import manifest

from . import _tiny

M, L = "minicpm4", "lightning-attn"
#: every structure of the real file at widths of tens: both layer kinds, a
#: group of 2 query heads a K|V head, a selection of 4 blocks of 8 tokens
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 160,
    "lightning_head_dim": 16, "lightning_nh": 8, "lightning_nkv": 8,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 128, "model_type": "minicpm_sala",
    "mixer_types": [M, L, L, M, L, L], "num_attention_heads": 4,
    "num_hidden_layers": 6, "num_key_value_heads": 2, "qk_norm": True,
    "rand_init": False, "rms_norm_eps": 1e-6, "vocab_size": 96,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "mup_denominator": 6, "dim_model_base": 32,
    "tie_word_embeddings": False, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True}

TINY_SALA = {
    "name": "tiny-sala", "family": "minicpm_sala", "source": "test",
    # the top level as it is run: published layers 1-4
    **PUBLISHED, "num_hidden_layers": 4, "mixer_types": [L, L, M, L],
    "published": PUBLISHED, "held": {"first_layer": 1},
    "assumed": {"dtype": "bfloat16", "state_dtype": "float32",
                "initializer_std": 0.02,
                "kernel_size": 4, "kernel_stride": 2, "block_size": 8,
                "window_size": 16, "init_blocks": 1, "topk": 4,
                "dense_len": 32},
    # a few times the largest bf16 error and tie distance the checks read at
    # this size on the CPU
    "logit_tolerance": 0.03, "selection_tie_distance": 0.05,
    "reduced": ["num_hidden_layers", "mixer_types"],
    "departures": [], "deployment": "a test"}

#: prompts past the tiny dense_len, prefilled 16 tokens a round
TINY_DOC = {
    **_tiny.TINY_SERVE,
    "prompt_len": {"dist": "uniform", "lo": 40, "hi": 64},
    "output_len": {"dist": "loguniform", "lo": 4, "hi": 12},
    "engine": {"max_batch": 4, "max_seq_len": 96, "max_prompt_len": 64,
               "prefill_chunk": 16, "page_size": 8, "record_logits": False},
    "check": {"shape": [2, 4]}}

CELLS = {"tiny-sala": (TINY_SALA, TINY_DOC, 1)}
CELL = "minicpmsala-serve-closed32-doc16k"
NEW_READERS = ("lightning_attn_share", "sparse_attn_share",
               "sparse_select_share", "sparse_attended_share",
               "lightning_decode_roofline", "sparse_decode_attention_roofline",
               "lightning_prefill_roofline")


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root, man = _tiny.make_root(tmp_path_factory.mktemp("bench"), CELLS)
    return root, man


def _phase(lines, name):
    return next(l for l in lines if l["phase"] == name)


def test_the_files_top_level_is_the_published_config_but_for_reduced():
    man = manifest.load_manifest()
    body = manifest.load_config(man, "minicpm-sala-9b-l12")
    assert body["family"] == "minicpm_sala"
    pub = body["published"]
    assert {k for k in pub if body[k] != pub[k]} == set(body["reduced"]) \
        == {"num_hidden_layers", "mixer_types"}
    first = body["held"]["first_layer"]
    assert (first, body["num_hidden_layers"]) == (9, 12)
    assert body["mixer_types"] == pub["mixer_types"][first:first + 12]
    # three whole periods of the published 1 : 3, the adjacent pair included
    assert "".join("M" if t == M else "L" for t in body["mixer_types"]) \
        == "MLLLLLLMMLLL"
    assert pub["mixer_types"].count(M) == 8 and len(pub["mixer_types"]) == 32
    # no width is cut
    for k in ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "lightning_nh",
              "lightning_head_dim", "vocab_size"):
        assert body[k] == pub[k], k
    assert 0 < body["logit_tolerance"] < 0.05
    assert 0 < body["selection_tie_distance"] < 0.5
    for k in ("kernel_size", "kernel_stride", "block_size", "window_size",
              "init_blocks", "topk", "dense_len", "decay"):
        assert k in body["assumed"]
    assert any("ONE level" in d for d in body["departures"])


def test_the_cell_is_in_the_manifest_with_its_traffic_letter_for_letter():
    man = manifest.load_manifest()
    cell = manifest.find_workload(man, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "minicpm-sala-9b-l12", "serve-closed-c32-doc16k", 1)
    t = manifest.load_traffic(cell["traffic"])
    assert t["arrival"] == {"process": "closed", "clients": 32}
    assert (t["prompt_len"], t["output_len"]) == (
        {"dist": "uniform", "lo": 14336, "hi": 16384},
        {"dist": "loguniform", "lo": 2048, "hi": 8192})
    assert t["engine"] == {"max_batch": 32, "max_seq_len": 24576,
                           "max_prompt_len": 16384, "prefill_chunk": 1024,
                           "page_size": 64, "record_logits": False}
    assert (t["trace_seconds"], t["check"]["shape"], t["rate_metric"]) == (
        6.0, [2, 5], "serve_tokens_per_s")
    mine = {m["name"] for m in
            manifest.metrics_for(man, "per_layer", cell["name"])}
    assert len(mine) == 17 and set(NEW_READERS) <= mine
    assert "dense_ffn_share" in mine
    assert "paged_decode_attention_roofline" not in mine
    assert {m["name"] for m in manifest.metrics_for(
        man, "end_to_end", cell["name"])} == {"serve_tokens_per_s",
                                              "setup_s"}
    # the new readers are this cell's alone; no count of cells (the next
    # configuration adds one); one cell on four chips
    for m in man["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [cell["name"]]
    assert sum(c["chips"] == 4 for c in man["workloads"]) == 1


def test_cell_6_is_in_the_manifest_with_its_traffic_letter_for_letter():
    """``test_longcat.py``'s test of this name, every assertion of it but
    ``len(man["workloads"]) == 6``: that count made the seventh cell fail a
    file this PR may not edit, so ``tests/test_benchmark_entry.py``
    deselects the test there and it lives here until a ``benchmark`` PR
    drops the count (ROADMAP R-B). ``dense_ffn_share`` is cell 6's and,
    since this PR, this cell's."""
    from .test_longcat import NEW_READERS as new_readers
    man = manifest.load_manifest()
    cell = manifest.find_workload(man, "longcat-serve-closed256")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "longcat-flash-560b-ep32", "serve-closed-c256-chat", 1)
    t = manifest.load_traffic(cell["traffic"])
    assert t["arrival"] == {"process": "closed", "clients": 256}
    assert (t["prompt_len"], t["output_len"]) == (
        {"dist": "loguniform", "lo": 128, "hi": 512},
        {"dist": "loguniform", "lo": 512, "hi": 1024})
    assert t["engine"] == {"max_batch": 256, "max_seq_len": 1536,
                           "max_prompt_len": 512, "page_size": 128,
                           "record_logits": False}
    assert (t["trace_seconds"], t["check"]["shape"], t["rate_metric"]) == (
        6.0, [4, 5], "serve_tokens_per_s")
    mine = {m["name"] for m in
            manifest.metrics_for(man, "per_layer", cell["name"])}
    assert len(mine) == 17 and set(new_readers) <= mine
    assert {m["name"] for m in manifest.metrics_for(
        man, "end_to_end", cell["name"])} == {"serve_tokens_per_s",
                                              "setup_s"}
    for m in man["per_layer"]:
        if m["name"] in new_readers:
            assert m["workloads"][0] == cell["name"]
            assert m["workloads"] in ([cell["name"]], [cell["name"], CELL])
    assert sum(c["chips"] == 4 for c in man["workloads"]) == 1


def test_family_is_found_by_name():
    assert manifest.load_family("minicpm_sala").build_serve


def test_serve_cell_rehearsal(copy):
    root, man = copy
    lines, res = _tiny.run(root, man, "tiny-sala", seconds=1.0)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    check = _phase(lines, "reference-check")
    assert check["ok"] and check["selection_tie_distance"] <= 0.05
    # every fed row past dense_len, the one sparse layer, both K|V heads
    assert check["selection_rows_compared"] == 2 * sum(
        n + 3 - 32 for n in check["prompt_lens"])
    info = _phase(lines, "built")["info"]
    assert info["prefill_chunk"] == 16 and info["first_layer"] == 1
    assert info["lightning"]["layers"] == 3
    assert info["state_bytes"] == 3 * 4 * 8 * 16 * 16 * 4
    assert info["pool_bytes"] > info["state_bytes"]
    assert _phase(lines, "serve-window")["completed"] > 0


def test_a_choice_that_differs_is_held_to_a_near_tie(copy):
    """A limit of zero refuses a run in which a bf16 row chose another block
    than the float32 reference; the file's own limit admits it."""
    root, man = copy
    for seed in range(1, 9):
        lines, res = _tiny.run(root, man, "tiny-sala", seed=seed,
                               seconds=0.3)
        check = _phase(lines, "reference-check")
        if check["selection_rows_that_differ"]:
            break
    else:
        pytest.skip("no seed of eight flips a choice at this size")
    assert 0 < check["selection_tie_distance"] <= 0.05
    assert check["ok"] and res["correct"] is True
    path = os.path.join(root, "benchmarks", "configs", "tiny-sala.json")
    with open(path) as f:
        body = json.load(f)
    try:
        with open(path, "w") as f:
            json.dump({**body, "selection_tie_distance":
                       check["selection_tie_distance"] / 2}, f)
        lines, res = _tiny.run(root, man, "tiny-sala", seed=seed,
                               seconds=0.3)
    finally:
        with open(path, "w") as f:
            json.dump(body, f)
    check = _phase(lines, "reference-check")
    assert check["rel_err"] >= 1.0 and not check["ok"]
    assert res["correct"] is False


def test_a_state_of_another_dtype_than_the_files_fails_the_check(copy):
    """The check's numbers cannot tell a bf16 state from a float32 one in
    five steps (the configuration file says by how little they differ on the
    chip), so the state leaves are held to ``assumed.state_dtype`` by
    name."""
    root, man = copy
    path = os.path.join(root, "benchmarks", "configs", "tiny-sala.json")
    with open(path) as f:
        body = json.load(f)
    try:
        with open(path, "w") as f:
            json.dump({**body, "assumed": {**body["assumed"],
                                           "state_dtype": "bfloat16"}}, f)
        lines, res = _tiny.run(root, man, "tiny-sala", seconds=0.3)
    finally:
        with open(path, "w") as f:
            json.dump(body, f)
    check = _phase(lines, "reference-check")
    assert check["state_dtype"] == ["float32"]
    assert check["rel_err"] >= 1.0 and not check["ok"]
    assert max(check["rel_err_by_request"]) < 0.03
    assert res["correct"] is False


def test_traced_rehearsal_reads_the_new_counters_and_readers(copy):
    """No device plane on the CPU: the trace's shares are left out, the
    counters' reader is not."""
    root, man = copy
    _, res = _tiny.run(root, man, "tiny-sala", trace=True, seconds=1.0)
    assert {"compiles_in_window", "decode_step_ms_p50",
            "sparse_attended_share"} <= set(res["metrics"])
    # 4 blocks of 8 of contexts of 40-76 tokens
    assert 30.0 <= res["metrics"]["sparse_attended_share"]["value"] <= 80.0
    for name in ("lightning_attn_share", "lightning_decode_roofline",
                 "sparse_decode_attention_roofline"):
        assert name not in res["metrics"]


# -- the cell's window on a clock: how many requests end in it -----------------

class _NoModel:
    """Answers the engine's model interface with programs that compute
    nothing: the window's SCHEDULE at the cell's real lengths without its
    arithmetic."""

    param_rules = cache_rules = cfg = None
    max_seq_len = 1 << 20

    def check(self, **kw):
        pass

    def page_geometry(self, tp):
        import jax.numpy as jnp
        return dict(kv_heads=1, head_dim=128, group=1, dtype=jnp.bfloat16)

    def cache_config(self, *, num_pages, page_size, **kw):
        import jax.numpy as jnp
        from apex_tpu.serve.cache import CacheConfig
        return CacheConfig(num_layers=0, kv_heads=1, head_dim=128,
                           num_pages=num_pages, page_size=page_size,
                           dtype=jnp.bfloat16)

    def prefill(self, ccfg, params, state, bt, length, ids, **kw):
        import jax.numpy as jnp
        return jnp.zeros((8,), jnp.float32), state, {}

    def decode(self, ccfg, params, state, bts, pos, toks, act, **kw):
        import jax.numpy as jnp
        return jnp.zeros((toks.shape[0], 8), jnp.float32), state, {}

    def record_round(self, aux):
        pass


def _window_on_a_clock(monkeypatch, traffic, seed, first_wave, *,
                       t_decode=17.45e-3, t_chunk=56.5e-3, seconds=20.0):
    """``harness/serve.py:run_serve`` itself, with ``loadgen``'s streams,
    over the real engine and scheduler, on a clock that only the device's
    work advances: ``t_decode`` a decode round and ``t_chunk`` a chunk (the
    chip's: 17.45 and 56.5 ms at chunks of 1,024, PERF.md section 5; the
    one-round-ahead pipeline hides the host). ``first_wave`` wraps the
    engine as the family does. Returns the rate, the requests issued in the
    window and those that ended in it, and those that ended in set-up."""
    from apex_tpu import serve
    from benchmarks.harness import loadgen
    from benchmarks.harness import serve as hserve

    kw = dict(traffic["engine"])
    longest = (loadgen.longest(traffic["prompt_len"])
               + loadgen.longest(traffic["output_len"]))
    eng = serve.ServeEngine(
        _NoModel(), {}, num_pages=kw["max_batch"]
        * -(-longest // kw["page_size"]) + 1, **kw)
    clock = SimpleNamespace(t=0.0)
    clock.perf_counter = lambda: clock.t
    clock.sleep = lambda s: setattr(clock, "t", clock.t + s)
    monkeypatch.setattr(hserve, "time", clock)
    step, prefill, decode = eng.step, eng._do_prefill, eng._decode_round
    went = []
    eng._do_prefill = lambda seq: (went.append(t_chunk), prefill(seq))[1]
    eng._decode_round = lambda rows: (
        went.append(t_decode * bool(rows)), decode(rows))[1]

    def timed_step():
        del went[:]
        more = step()
        clock.t += sum(went)
        return more

    eng.step = timed_step
    lines = {}
    tracer = SimpleNamespace(on=False, done=True, active=False,
                             mark=lambda *a: None,
                             annotate=lambda *a: contextlib.nullcontext())
    out = hserve.run_serve(
        hserve.ServeProgram(first_wave(eng), 73448, None, {}, {}, {}),
        traffic, seed, seconds, tracer,
        lambda phase, **k: lines.__setitem__(phase, k))
    assert out["failed"] == 0
    return (out["end_to_end"]["serve_tokens_per_s"], out["attempted"],
            lines["serve-window"]["completed"],
            lines["first-wave"]["ended_in_setup"])


def _spread(rates):
    q = statistics.quantiles(rates, n=4)
    return (q[2] - q[0]) / statistics.median(rates)


def test_the_seed_does_not_decide_how_many_requests_end_in_a_window(
        monkeypatch):
    """The cell's traffic at its real lengths, four seeds at which the
    harness's own order of the first wave reads 1,164-1,315 tokens/s on this
    clock (the chip at six other seeds: 1,187.71-1,264.27, PERF.md section
    6): 6 to 9 requests issued in the window, 1 or 2 ended in set-up.
    Longest residual first, as the family queues the wave, every seed ends
    the same requests in the window and none in set-up, and what is left of
    the spread (a prompt is 15 or 16 chunks, by the seed) is under half the
    1% bound."""
    from benchmarks.families.minicpm_sala import FirstWaveLongestFirst
    traffic = manifest.load_traffic("serve-closed-c32-doc16k")
    seeds = (3700000022, 3700000024, 3700000026, 3700000033)
    asis = [_window_on_a_clock(monkeypatch, traffic, s, lambda e: e)
            for s in seeds[1:]]
    assert len({issued for _, issued, _, _ in asis}) > 1
    assert all(early for *_, early in asis)
    assert _spread([rate for rate, *_ in asis]) > 0.02
    ours = [_window_on_a_clock(monkeypatch, traffic, s,
                               FirstWaveLongestFirst) for s in seeds]
    assert {(issued, ended, early) for _, issued, ended, early in ours} \
        == {(7, 7, 0)}
    assert _spread([rate for rate, *_ in ours]) < 0.005


def _run(events=(), info=None, attention=None):
    return {"program": SimpleNamespace(
                attention=attention or {"kind": "paged_decode"},
                info=info or {}, programs={}),
            "trace": {"op_s": {}, "kernel_s": {}, "module_s": {}},
            "traced": {"rounds": 3, "batch_rows": 96},
            "window_events": list(events), "notes": {}, "workload": "none"}


def test_new_readers_find_nothing_in_a_program_without_them():
    """What the parent commit, or a GPT cell, gives: none, and no raise."""
    for name in NEW_READERS:
        reader = manifest.load_layer_metric(name, manifest.BENCH_DIR)
        assert reader.compute(_run()) is None, name
        assert reader.compute({**_run(), "trace": None}) is None, name


def test_attended_share_is_the_median_over_the_windows_rounds():
    def c(name, v):
        return {"kind": "counter", "name": name, "value": v}
    events = []
    for read, held in ((100, 400), (120, 400), (300, 400)):
        events += [c("state/rows_live", 4), c("sparse/blocks_chosen", 9),
                   c("sparse/tokens_attended", read),
                   c("sparse/context_tokens", held)]
    reader = manifest.load_layer_metric("sparse_attended_share",
                                        manifest.BENCH_DIR)
    assert reader.compute(_run(events)) == pytest.approx(30.0)
    assert counts.traced({**_run(events), "traced": {"step_lo": 1,
                                                     "step_hi": 3}},
                         "sparse/tokens_attended") == [120.0, 300.0]


def test_prefill_roofline_counts_the_tokens_a_chunk_was_given(monkeypatch):
    """Two traced chunks of which the window's spans say one was half
    padding: three quarters of the q, k, v, o rows that two whole chunks
    count, and the same two states (the kernel is memory-bound)."""
    from benchmarks.harness import peaks, span_reduce
    reader = manifest.load_layer_metric("lightning_prefill_roofline",
                                        manifest.BENCH_DIR)
    run = _run(info={"lightning": {
        "prefill_kernel": r"^apx_lightning_prefill", "layers": 9,
        "heads": 32, "head_dim": 128, "chunk": 1024, "sub_chunk": 256}})
    run["program"].programs = {"prefill": r"^jit_prefill$"}
    run["trace"] = {"kernel_s": {"apx_lightning_prefill.1": 2e-3},
                    "module_s": {"jit_prefill": [0.05, 0.05]}, "op_s": {}}
    run["peak"] = peaks.peak_for("TPU v5 lite")
    shares = {}
    for name, spans in (("whole", None), ("given", [
            {"name": "serve/prefill", "n_tokens": 1024},
            {"name": "serve/prefill", "n_tokens": 512},
            {"name": "serve/decode_step", "n_active": 32}])):
        monkeypatch.setattr(span_reduce, "window_spans",
                            lambda run, name, spans=spans: spans)
        shares[name] = reader.compute(run)
    assert run["notes"]["lightning_prefill_roofline_bound"] == "memory"
    assert 0 < shares["whole"] < 100
    rows, states = 2048 * 32 * 128 * 10.0, 2 * 2 * 32 * 128 * 128 * 4.0
    assert shares["given"] / shares["whole"] == pytest.approx(
        (0.75 * rows + states) / (rows + states))


def test_counts_at_the_published_shapes():
    # a row's nine lightning layers: 2 x 2.10 MB of state each
    assert counts.lightning_decode_bytes(1, 32, 128) == pytest.approx(
        2 * 32 * 128 * 128 * 4 + 4 * 32 * 128 * 4)
    assert 9 * counts.lightning_decode_bytes(32, 32, 128) == pytest.approx(
        1.2e9, rel=0.03)                    # "1.2 GB read and written"
    # a chunk of 1,024 in sub-chunks of 256: 4 x (two squares + two states)
    assert counts.lightning_prefill_flops(1024, 32, 128, 256) == \
        32 * 4 * 2.0 * (2 * 256 * 256 * 128 + 2 * 256 * 128 * 128)
    # 64 blocks of 64 tokens of one row: 4,096 tokens x 2 heads x K and V
    assert counts.sparse_decode_bytes(4096, 2, 128, 32, 1) == \
        2.0 * 4096 * 2 * 128 * 2 + 2.0 * 32 * 128 * 2
    assert counts.sparse_decode_flops(4096, 32, 128) == 4.0 * 4096 * 32 * 128
