"""CPU rehearsal of the Phi-4-mini-flash-reasoning serving cell at a tiny
size: the new driver, reference, traffic keys and readers end to end, the
int8 control and a state dropped between two chunks coming out as not
correct. The cell is added to a copy of the tiny benchmark by files and
entries, as a PR adds it to the real one. No number here is a device number."""

import json
import shutil
import time

import pytest

from benchmark import harness, run
from benchmark.tests.conftest import TINY

NAME = "phi4flash-tiny.reason-tiny"

CONFIG = {
    "source": "tiny rehearsal preset of the CPU tests, not a model",
    "vocab_size": 384, "hidden_size": 128, "intermediate_size": 256, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 512, "mb_per_layer": 2, "num_attention_heads": 4,
    "num_hidden_layers": 8, "num_key_value_heads": 2, "sliding_window": 16,
    "reduced": [],
    "assumed": {"sizes": {"mamba_d_state": 8, "mamba_d_conv": 4, "mamba_expand": 2,
                          "mamba_dt_rank": 8, "subln_eps": 1e-05}},
    "driver": "serve_phi4flash", "reference": "phi4flash",
    "engine": {"state_manager": {"max_ragged_sequence_count": 8, "max_ragged_batch_size": 32,
                                 "max_context": 256, "num_kv_blocks": 128, "kv_dtype": "fp"},
               "kv_cache": {"block_size": 8}},
    "limits": {"served_gap_max": 0.05, "served_gap_mean": 0.002},
}
TRAFFIC = {
    "generator": "requests", "loop": "closed", "clients": 6, "requests_per_client": 3,
    "shape_seed": 0, "order": "fixed",
    "prompt": {"dist": "uniform", "min": 20, "max": 70},
    "output": {"dist": "lognormal", "median": 48, "sigma": 0.5, "min": 16, "max": 120},
    "stagger_cap": {"clients": 3, "remaining": 5},
    "check_requests": 3, "check_pad_to": 256, "check_max_new": 128, "trace_seconds": 1,
}


@pytest.fixture
def bench(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(TINY, root)
    with open(root / "configs" / "phi4flash-tiny.json", "w") as f:
        json.dump(CONFIG, f)
    with open(root / "traffic" / "reason-tiny.json", "w") as f:
        json.dump(TRAFFIC, f)
    with open(root / "BENCHMARK.json") as f:
        b = json.load(f)
    b["configs"].append({"name": "phi4flash-tiny", "source": "test", "reduced": [],
                         "file": "configs/phi4flash-tiny.json", "why": "test"})
    b["workloads"].append({"name": NAME, "config": "phi4flash-tiny", "traffic": "reason-tiny",
                           "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(NAME)
    for m in b["per_layer"]:
        if m["name"] == "round_ms.decode":
            m["workloads"].append(NAME)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    return str(root / "BENCHMARK.json")


def test_cell_end_to_end_and_controls(bench, cpu_device, tmp_path, capsys):
    cell = harness.Cell(NAME, bench)
    devices, info = cpu_device
    result = run.run_cell(cell, 2**31 + 11, 6.0, 0, devices, info, time.perf_counter(),
                          str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert "compared " in capsys.readouterr().out

    mod = harness.load("drivers", "serve_phi4flash")
    driver = mod.Driver(cell, 5, harness.Recorder(), devices=devices, seconds=6.0)
    # the part already emitted is in the context: every client starts past its prompt
    longest_prompt = max(len(q[0][0]) for q in driver.load["clients"])
    assert max(len(r["prompt"]) for r in driver.active.values()) > longest_prompt
    driver.window(6.0, str(tmp_path))
    rounds = [a for n, _, _, a in driver.rec.spans if n == "round"]
    assert all("window_context_tokens" in a for a in rounds)
    assert any(0 < a["window_context_tokens"] < a["context_tokens"] for a in rounds)
    driver.release()
    sound = {n: v for n, v, _ in driver.compare()}
    control = {n: v for n, v, _ in driver.control()}
    limit = cell.limit("served_gap_mean")
    assert sound["served_gap.mean"] <= limit < control["served_gap.mean"]


def drop_state_between_dispatches():
    """Patch the state manager so that every dispatch starts from zero
    recurrent state: what a scheduler that lost the slot between two chunks
    of a prompt (or two decode rounds) would serve. Returns the undo."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu.inference.v2.ragged.ragged_manager as rm
    real = rm.DSStateManager.cache_update

    def dropped(self, view):
        real(self, view)
        self.slot_pools = jax.tree.map(jnp.zeros_like, self.slot_pools)

    rm.DSStateManager.cache_update = dropped
    return lambda: setattr(rm.DSStateManager, "cache_update", real)


def test_state_dropped_between_chunks_is_not_correct(bench, cpu_device, tmp_path):
    cell = harness.Cell(NAME, bench)
    mod = harness.load("drivers", "serve_phi4flash")
    undo = drop_state_between_dispatches()
    try:
        broken = mod.Driver(cell, 5, harness.Recorder(), devices=cpu_device[0], seconds=6.0)
        facts = broken.window(6.0, str(tmp_path))
    finally:
        undo()
    broken.release()
    assert facts["finished"] > 0
    assert any(v > lim for _, v, lim in broken.compare())
