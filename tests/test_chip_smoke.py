"""Rehearsal of ``chip_smoke.py`` without the chip (on-chip-measurement
guide §2, rehearsals 1 and 2): the script's own phase functions at a tiny
size on the CPU — Pallas in interpret mode, ``ServeEngine`` on its XLA
reference paths — and its four-chip phases on four of the virtual host
devices. Finds wrong paths, arguments, meshes and sharding rules before a
chip-minute is spent. What only a chip can show (the kernels in the
compiled text, per-device memory) is steered HERE by monkeypatch, never by
an option of the script; rehearsal 3, full-width compiles for the described
chip, is ``tests/test_tpu_compile.py``.
"""

import json

import jax
import pytest


@pytest.fixture
def smoke(chip_smoke):
    return chip_smoke


@pytest.fixture
def recorder():
    from apex_tpu import monitor
    rec = monitor.Recorder(name="chip_smoke_test", traced_hooks=False)
    monitor.attach(rec)
    yield rec
    monitor.detach()


def _tiny(smoke):
    return smoke.Sizes(vocab=512, max_seq_len=128, hidden=128, layers=2,
                       heads=4, batch=4, seq=128, steps=3,
                       max_prompt_len=64, max_batch=4, num_pages=16,
                       n_requests=3, prompt_lo=8, new_tokens=4,
                       mc_layers=2, mc_steps=3)


def _phase_lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


def test_no_tpu_exits_2_with_no_result_line(smoke, capsys, monkeypatch,
                                            tmp_path):
    """The contract's negative half: where JAX finds no accelerator the
    script exits non-zero before any phase and prints no result. (In
    process: this suite's JAX is on the CPU. The cache variable is set so
    that ``main`` changes no jax config here.)"""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code == 2
    out, err = capsys.readouterr()
    assert out == ""
    assert "not a TPU" in err


@pytest.mark.slow
def test_one_chip_phases_tiny_on_cpu(smoke, recorder, capsys):
    sz = _tiny(smoke)
    smoke.phase_dispatch(n=3, dim=128)
    cfg, params, train_text = smoke.phase_train(sz, seed=0)
    decode_text = smoke.phase_serve(sz, cfg, params, seed=0)
    # the gate does its job: on the CPU every kernel was interpreted and
    # the engine took the reference, and kernels-present says so
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        smoke.phase_kernels_present(train_text, decode_text, recorder)
    smoke.phase_loader()
    lines = {ln["phase"]: ln for ln in _phase_lines(capsys)}
    assert set(lines) == {"dispatch", "train", "serve", "kernels-present",
                          "loader"}
    assert all(ln["platform"] == "cpu" and ln["device_count"] == 8
               for ln in lines.values())
    assert lines["train"]["losses"][-1] < lines["train"]["losses"][0]
    assert lines["serve"]["tokens_generated"] == 3 * 4
    # LN is tuner-gated with an empty cache: the shim, by design (S2)
    tuner = lines["kernels-present"]["tuner"]
    assert tuner["fused_layer_norm"]["resolved"] == "jnp shim"
    assert tuner["flash_attention_fwd"]["hits"] == 0


@pytest.mark.slow
def test_four_chip_phases_tiny_on_virtual_devices(smoke, recorder, capsys,
                                                  monkeypatch):
    assert len(jax.devices()) >= 4
    # what only chips have: compiled-in kernels and device memory stats
    monkeypatch.setattr(smoke, "_require_kernels", lambda found, **kw: None)
    monkeypatch.setattr(smoke, "_bytes_in_use",
                        lambda devices: [1] * len(devices))
    sz = _tiny(smoke)
    smoke.phase_train_4chip(sz, seed=0)
    smoke.phase_serve_4chip(sz, seed=0)
    lines = {ln["phase"]: ln for ln in _phase_lines(capsys)}
    assert set(lines) == {"train-dp2xtp2", "train-dp4", "train-one-device",
                          "train-dp4-vs-one-device", "serve-tp4-vs-tp1"}
    assert lines["train-dp2xtp2"]["mesh"] == {"data": 2, "tensor": 2}
    assert lines["train-dp4"]["mesh"] == {"data": 4}
    sv = lines["serve-tp4-vs-tp1"]
    # handed in on one device, placed on four by the engine, still there
    # after a step — and exactly what the compiled decode program expects
    assert len(sv["params_handed_in"]["bytes_by_device"]) == 1
    for when in ("engine_after_build", "engine_after_first_step"):
        assert len(sv[when]["params"]["bytes_by_device"]) == 4
        assert len(sv[when]["state"]["bytes_by_device"]) == 4
    m = sv["decode_inputs_match_compiled_shardings_after"]
    assert m["matching"] == m["of"] > 0
