"""CPU rehearsals: each driver end to end at a tiny size, the control and a
broken timed path coming out as not correct, a cell added by files alone, and
the refusal to run without a chip. No number here is a device number."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import harness, run, tails
from benchmark.tests.conftest import TINY

CELLS = ["gpt2-tiny.train-tiny", "mistral-tiny.chat-tiny", "mistral-tiny.closed-tiny"]


def _run(cell, cpu_device, tmp_path, seed=2**31 + 7, seconds=2.0):
    devices, info = cpu_device
    return run.run_cell(cell, seed, seconds, 0, devices, info, time.perf_counter(), str(tmp_path))


@pytest.mark.parametrize("name", CELLS)
def test_cell_end_to_end(name, tiny_bench, cpu_device, tmp_path, capsys):
    cell = harness.Cell(name, tiny_bench)
    result = _run(cell, cpu_device, tmp_path)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    out, err = capsys.readouterr()
    assert "setup_breakdown_s" in out and "compared " in out
    assert err.strip().splitlines()[-1].startswith("reference took")
    # every number compared beside its limit, last in the result's line
    assert list(result)[-1] == "compared" and len(result["compared"]) >= 2
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    if name.startswith("mistral"):
        # what the tail stood on (benchmark/tails.py), and the window's own gaps and rounds
        tail = result["window"]
        with open(tmp_path / "window.json") as f:
            dump = json.load(f)
        assert tail["gaps"] == len(dump["gaps_ms"]) == len(dump["gap_round"]) > 0
        assert tail["token_gap_p99_rank"] == tails.p99_rank(tail["gaps"])
        high, low = tail["token_gap_plateau_ms"]
        p99 = result["metrics"]["token_gap_p99_ms"]["value"]
        assert high >= round(p99, 3) >= low > 0
        assert tail["stall_rounds"] == len(tails.stall_rounds(dump["rounds"])) >= 0
        assert dump["round_fields"] == ["end_s", "ms", "prefill_tokens", "decode_rows"]
        assert len(dump["rounds"]) == tail["rounds"]
        # a gap ends in the round that gave its token: one a decode row
        per_round = [dump["gap_round"].count(i) for i in range(len(dump["rounds"]))]
        assert per_round == [r[3] for r in dump["rounds"]]
        assert tails.describe(dump["gaps_ms"], dump["gap_round"], dump["rounds"]) == \
            {k: dump[k] for k in ("token_gap_p99_rank", "token_gap_plateau_ms", "token_gap_on_plateau",
                                  "stall_rounds", "token_gap_p99_with_6_stalls_ms")}
    if name.startswith("gpt2"):
        with open(tmp_path / "step_times.json") as f:
            steps = json.load(f)
        assert len(steps["done_s"]) == result["attempted"] >= 12
        rate = result["metrics"]["train_tokens_per_s_per_chip"]["value"]
        assert rate == pytest.approx(4 * 64 * len(steps["done_s"]) / steps["done_s"][-1])


def test_training_control_and_broken_step_are_not_correct(tiny_bench, cpu_device):
    """The int8 reference in the program's place fails a limit the program
    keeps; so does a step that returns its state unchanged."""
    cell = harness.Cell(CELLS[0], tiny_bench)
    mod = harness.load("drivers", "train")
    driver = mod.Driver(cell, 3, harness.Recorder(), devices=cpu_device[0])
    driver.release()
    assert all(v <= lim for _, v, lim in driver.compare())
    control = {n: (v, lim) for n, v, lim in driver.control()}
    assert control["grad_norm_gap.worst_leaf"][0] > control["grad_norm_gap.worst_leaf"][1]

    import deepspeed_tpu.runtime.engine as eng_mod
    real_forward = eng_mod.DeepSpeedEngine.forward

    def forward_keeps_state(self, batch):
        import jax
        state = jax.tree.map(lambda x: x.copy() if hasattr(x, "copy") else x, self.state)
        loss = real_forward(self, batch)
        self.state = state                        # the step's update is thrown away
        return loss

    eng_mod.DeepSpeedEngine.forward = eng_mod.DeepSpeedEngine.__call__ = forward_keeps_state
    try:
        broken = mod.Driver(cell, 3, harness.Recorder(), devices=cpu_device[0])
    finally:
        eng_mod.DeepSpeedEngine.forward = eng_mod.DeepSpeedEngine.__call__ = real_forward
    broken.release()
    checks = {n: (v, lim) for n, v, lim in broken.compare()}
    assert checks["delta_norm_gap.median_leaf"][0] > checks["delta_norm_gap.median_leaf"][1]


def test_serving_control_and_altered_token_are_not_correct(tiny_bench, cpu_device, tmp_path):
    cell = harness.Cell(CELLS[2], tiny_bench)
    mod = harness.load("drivers", "serve")
    driver = mod.Driver(cell, 5, harness.Recorder(), devices=cpu_device[0], seconds=3.0)
    driver.window(3.0, str(tmp_path))
    driver.release()
    sound = {n: v for n, v, _ in driver.compare()}
    control = {n: v for n, v, _ in driver.control()}
    limit = cell.limit("served_gap_mean")
    assert sound["served_gap.mean"] <= limit < control["served_gap.mean"]

    # a token altered where it is produced: the scheduler's one fetch of the
    # sampled ids returns another id for the first row
    broken = mod.Driver(cell, 5, harness.Recorder(), devices=cpu_device[0], seconds=3.0)
    fetch = broken.engine.host_fetch

    def altered(value, what):
        ids = np.array(fetch(value, what))
        if what == "scheduler/sampled_ids":
            ids[0] = (ids[0] + 1) % cell.config["vocab_size"]
        return ids

    broken.engine.host_fetch = altered
    facts = broken.window(3.0, str(tmp_path))
    broken.release()
    checks = broken.compare()
    assert facts["finished"] > 0
    assert any(v > lim for _, v, lim in checks)


def test_open_loop_times_from_when_a_request_was_due(tiny_bench, cpu_device, tmp_path):
    """A scheduler that stalls 0.4 s on its first round makes every request
    that was due meanwhile wait: the wait is in their TTFT."""
    cell = harness.Cell(CELLS[1], tiny_bench)
    driver = harness.load("drivers", "serve").Driver(cell, 9, harness.Recorder(),
                                                 devices=cpu_device[0], seconds=2.0)
    step, stalled = driver.sched.step, []

    def stall_once():
        if not stalled:
            stalled.append(1)
            time.sleep(0.4)
        return step()

    driver.sched.step = stall_once
    facts = driver.window(2.0, str(tmp_path))
    due = [d for d, _, _ in driver.load["requests"] if d < 0.35]
    assert len(due) >= 2
    assert sorted(driver.ttft)[-len(due)] >= 0.4 - max(due)
    assert facts["attempted"] == len(driver.load["requests"]) and facts["failed"] == 0


def test_a_cell_is_added_by_files_and_one_entry_each(tmp_path, cpu_device):
    """A configuration, a traffic mix, a per-layer metric and a cell, added
    by writing files and entries; no file that was there is edited."""
    root = tmp_path / "bench"
    shutil.copytree(TINY, root)
    with open(root / "configs" / "mistral-tiny.json") as f:
        cfg = json.load(f)
    cfg["num_hidden_layers"] = 3
    with open(root / "configs" / "mistral-tiny3.json", "w") as f:
        json.dump(cfg, f)
    with open(root / "traffic" / "chat-tiny.json") as f:
        mix = json.load(f)
    mix.update(arrival="burst", burst_size=4)
    with open(root / "traffic" / "burst-tiny.json", "w") as f:
        json.dump(mix, f)
    with open(root / "metrics" / "round_ms.p90.json", "w") as f:
        json.dump({"reader": "span_ms", "params": {"span": "round", "percentile": 90}}, f)
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    name = "mistral-tiny3.burst-tiny"
    bench["configs"].append({"name": "mistral-tiny3", "source": "test", "reduced": [],
                             "file": "configs/mistral-tiny3.json", "why": "test"})
    bench["workloads"].append({"name": name, "config": "mistral-tiny3", "traffic": "burst-tiny",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p90_s", "token_gap_p99_ms"):
            m["workloads"].append(name)
    bench["per_layer"].append({"name": "round_ms.p90", "unit": "ms", "better": "lower",
                               "source": "program_span", "layer": "serving engine",
                               "moves": "ttft_p90_s", "workloads": [name]})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    cell = harness.Cell(name, str(root / "BENCHMARK.json"))
    result = _run(cell, cpu_device, tmp_path)
    assert result["correct"] and "ttft_p90_s" in result["metrics"]
    assert [m["name"] for m in cell.per_layer] == ["round_ms.p90"]
    with open(os.path.join(cell.metrics_dir, "round_ms.p90.json")) as f:
        spec = json.load(f)
    rec = harness.Recorder()
    with rec.span("round"):
        pass
    ctx = {"spans": rec.spans, "notes": []}
    assert harness.load("readers", spec["reader"]).read(ctx, spec["params"]) >= 0


def test_no_chip_is_an_error_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "gpt2-medium.train-1k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs 1 TPU chip" in proc.stderr
    assert '"metrics"' not in proc.stdout and '"correct"' not in proc.stdout
