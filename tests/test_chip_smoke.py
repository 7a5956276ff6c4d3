"""chip_smoke.py rehearsed on the CPU: the phase functions at the TINY preset
with the Pallas kernels in interpret mode, and ``main()``'s refusal to run
without a TPU. ``main()`` itself always takes the REAL sizes — there is no
switch that would let the script pass here."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


@pytest.fixture
def smoke_env(monkeypatch):
    """Interpret-mode kernels + the dispatch records the checks read; the
    process-wide fallback notes start empty (another test file of the same
    worker may have left some)."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.ops import flash_attention as fa
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(fa, "_warned_shapes", set())
    telemetry.configure(enabled=True)
    yield
    telemetry.configure(enabled=False)
    telemetry.reset()


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


def test_train_phase_tiny(smoke_env, capsys):
    line = chip_smoke.phase_train(chip_smoke.TINY)
    printed = _lines(capsys)
    assert [ln["phase"] for ln in printed] == ["train"]
    assert printed[0]["losses"] == line["losses"]
    assert line["losses"][-1] < line["losses"][0]
    assert line["ref_loss_abs_err"] <= chip_smoke.TRAIN_REF_LOSS_TOL
    assert "flash_mha" in line["kernel_configs"]


def test_serve_phase_tiny(smoke_env, capsys):
    fp, q = chip_smoke.phase_serve(chip_smoke.TINY)
    assert [ln["phase"] for ln in _lines(capsys)] == ["serve", "serve_int8"]
    assert fp["kv_dtype"] == "fp" and q["kv_dtype"] == "int8"
    assert q["kv_pool_bytes"] < fp["kv_pool_bytes"]
    assert fp["ref_rel_rms_err"] <= chip_smoke.SERVE_REF_REL_RMS_TOL
    assert "paged_mha" in fp["kernel_configs"]


def test_multichip_phase_tiny(smoke_env, capsys, eight_devices):
    line = chip_smoke.phase_multichip(chip_smoke.TINY)
    assert [ln["phase"] for ln in _lines(capsys)] == ["multichip_zero3_dp2_tp2"]
    assert len(line["sharded_losses"]) == chip_smoke.TINY.multichip_steps
    assert line["flash_dispatch"]
    assert line["sharded"]["collectives"]


def test_fleet_phase_tiny(smoke_env, capsys, eight_devices):
    line = chip_smoke.phase_fleet(chip_smoke.TINY)
    assert [ln["phase"] for ln in _lines(capsys)] == ["fleet_prefill_decode"]
    assert line["pages_shipped"] == line["pages_bound"] > 0
    assert line["replica_devices"] == [[0], [1]]
    assert line["monolithic_device"] == 2


def test_fleet_parity_rejects_a_token_that_is_no_near_tie(smoke_env):
    """Two streams may part at a near-tie of the reference's logits; a token
    far below the top is a wrong answer."""
    import numpy as np
    p = chip_smoke.TINY
    cfg, model = chip_smoke._mistral(p)
    params = chip_smoke._seeded_bf16_params(model, p.seed)
    prompt = np.arange(12, dtype=np.int32)
    logits = model.apply({"params": params}, prompt[None])[0, -1]
    order = np.argsort(np.asarray(logits, np.float32))
    best, worst = int(order[-1]), int(order[0])
    tail = [1] * (p.new_tokens - 1)
    same = chip_smoke._same_or_near_tie(
        p, cfg, params, {0: prompt}, {0: [best] + tail}, {0: [best] + tail})
    assert same == {"identical_requests": 1, "split": {}}
    with pytest.raises(chip_smoke.SmokeFailure, match="not a near-tie"):
        chip_smoke._same_or_near_tie(
            p, cfg, params, {0: prompt}, {0: [best] + tail},
            {0: [worst] + tail})


def test_dense_paged_attention_fallback_fails_the_check(smoke_env):
    """Pages of 12 tokens do not tile the paged kernel: the forward takes the
    dense twin, and the dispatch check that passed before it refuses."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_implementations import paged_layer
    chip_smoke.check_dispatch()
    pool = jnp.zeros((8, 2, 12, 64))
    paged_layer._paged_attention(
        jnp.zeros((2, 1, 4, 64)), pool, pool, jnp.zeros((2, 4), jnp.int32),
        jnp.zeros((2,), jnp.int32), 12, jnp.ones((2,), jnp.int32))
    with pytest.raises(chip_smoke.SmokeFailure, match="paged_mha.*unsupported_shape"):
        chip_smoke.check_dispatch()


def test_failed_check_raises(smoke_env, monkeypatch):
    """A phase whose result is off does not print and pass: it raises."""
    monkeypatch.setattr(chip_smoke, "TRAIN_REF_LOSS_TOL", -1.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="float32 reference"):
        chip_smoke.phase_train(chip_smoke.TINY)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_without_tpu(argv, monkeypatch, capsys):
    """On this CPU backend main() exits non-zero before any phase runs and
    prints no result line."""
    for ph in ("phase_train", "phase_serve", "phase_multichip",
               "phase_fleet"):
        monkeypatch.setattr(chip_smoke, ph, lambda p: pytest.fail("phase ran"))
    assert chip_smoke.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_main_refuses_interpret_mode(monkeypatch, capsys):
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""
