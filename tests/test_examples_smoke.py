"""examples/simple/main_amp.py converges at its DEFAULTS.

Regression guard for the pre-existing NaN-at-default (verified at PR 2
HEAD, root-caused via monitor.Watchdog in
tests/test_health.py::test_watchdog_detects_seeded_nan_in_real_run:
pure optimizer divergence — lr 0.01 + momentum 0.9 on the 4-layer
linear MLP blew up at every opt level, fp32 included). The example now
defaults to lr 0.003 and must converge out of the box.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_serve_gpt_example_smoke():
    """examples/serve_gpt.py: the serve quickstart runs end-to-end on
    CPU, and its paged outputs match the naive full-recompute decode."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "serve_gpt.py"),
         "--requests", "3", "--max-new-tokens", "8", "--fp8-kv",
         "--compare-naive"],
        env=env, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    assert "serve ok" in proc.stdout, proc.stdout[-2000:]
    assert "fp8-KV capacity" in proc.stdout, proc.stdout[-2000:]


def test_serve_gpt_example_monitor_flag(tmp_path):
    """examples/serve_gpt.py --monitor: attaches a Recorder and prints
    the request-level span table + pool-occupancy summary at exit (the
    main_amp.py precedent); the optional path dumps a JSONL that the
    monitor report CLI can render."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    run_jsonl = str(tmp_path / "serve_run.jsonl")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "serve_gpt.py"),
         "--requests", "3", "--max-new-tokens", "6",
         "--monitor", run_jsonl],
        env=env, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    assert "serve ok" in proc.stdout, proc.stdout[-2000:]
    assert "serve telemetry" in proc.stdout, proc.stdout[-2000:]
    assert "| request |" in proc.stdout, proc.stdout[-2000:]
    assert "pool:" in proc.stdout, proc.stdout[-2000:]
    assert "token latency ms: p50" in proc.stdout, proc.stdout[-2000:]
    assert os.path.exists(run_jsonl)
    # the dump renders through the report CLI with the serve block
    proc2 = subprocess.run(
        [sys.executable, "-m", "apex_tpu.monitor", "report", run_jsonl],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc2.returncode == 0, (proc2.stdout + proc2.stderr)[-2000:]
    assert "## serve (request-level telemetry)" in proc2.stdout


def test_simple_amp_example_converges_at_defaults(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    run_jsonl = str(tmp_path / "run.jsonl")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "examples", "simple", "main_amp.py"),
         "--steps", "150", "--monitor", run_jsonl],
        env=env, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    assert "converged ok" in proc.stdout, proc.stdout[-2000:]
    # the default run is healthy: no divergence/NaN/overflow diagnoses
    # (a benign late-training plateau note is tolerated)
    for bad in ("[watchdog] nan", "[watchdog] loss_divergence",
                "[watchdog] overflow_storm"):
        assert bad not in proc.stdout, proc.stdout[-2000:]
    assert "telemetry:" in proc.stdout, proc.stdout[-2000:]


def test_gpt_example_round_trips_its_checkpoint_with_the_donating_step():
    """examples/gpt/main_gpt.py's ``main()`` on dp=4 x tp=2 virtual
    devices, tiny: its step donates its state, so the two calls of the fp32
    checkpoint round trip cannot share a buffer; the run must reach its
    bitwise assertion and pass it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "gpt", "main_gpt.py"),
         "--tp", "2", "--steps", "3", "--batch", "8", "--seq", "32",
         "--vocab", "256", "--hidden", "64", "--layers", "1", "--heads", "2"],
        env=env, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    assert ("fp32 checkpoint round trip: resumed step bitwise-identical"
            in proc.stdout), proc.stdout[-2000:]


@pytest.mark.parametrize("tp", [4, 2], ids=["tp4", "dp2xtp2"])
def test_gpt_example_step_updates_its_state_in_place(chip_smoke, tp):
    """The example's train step donates its variables, optimizer state and
    scaler state: the compiled program aliases EVERY leaf of them onto its
    output (no output buffer is allocated for the state: before PR 33, one
    for each of ~1,750 leaves a chip a step in the four-chip cell), the
    caller's old leaves are deleted, and the returned state steps again
    through the same executable, which is the loop
    ``benchmarks/harness/train.py`` runs."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.models import GPT, GPTConfig
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer import parallel_state as ps
    ps.destroy_model_parallel()
    try:
        mesh = ps.initialize_model_parallel(tensor_model_parallel_size_=tp,
                                            devices=jax.devices()[:4])
        model = GPT(GPTConfig(vocab_size=128, max_seq_len=16, hidden_size=32,
                              num_layers=2, num_heads=4, dtype=jnp.bfloat16,
                              sequence_parallel=True))
        init_f, step_f = chip_smoke._main_gpt().make_step_fns(
            mesh, model, FusedAdam(lr=1e-3, master_weights=True))
        ids = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 128,
                                 jnp.int32)
        state = init_f(ids)
        compiled = step_f.lower(*state, ids, ids).compile()
        leaves = jax.tree.leaves(state)
        per_device = sum(x.addressable_shards[0].data.nbytes for x in leaves)
        assert compiled.memory_analysis().alias_size_in_bytes == per_device
        *new, loss0 = compiled(*state, ids, ids)
        assert all(x.is_deleted() for x in leaves)
        assert not ids.is_deleted()               # the batch is the caller's
        *newer, loss1 = compiled(*new, ids, ids)
        assert all(x.is_deleted() for x in jax.tree.leaves(new))
        assert float(loss1) < float(loss0)
        assert int(newer[1].groups[0].step.addressable_shards[0].data) == 2
    finally:
        ps.destroy_model_parallel()
