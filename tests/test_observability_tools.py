"""Memory + goodput observability acceptance (docs/OBSERVABILITY.md).

Pins the PR-4 tentpole end to end on the 8-device CPU mesh:

- the engine train loop produces memory samples (CPU-synthesized from
  ``jax.live_arrays()``) and a goodput ledger whose categories sum to wall
  time within 5%, with nonzero ``mfu``/``goodput`` gauges;
- ``scripts/trace_merge.py`` folds two per-host JSONL streams into one
  Chrome trace with per-host memory counter tracks + a straggler report;
- the OOM post-mortem lists the top live buffers with shape/dtype/sharding;
- ``scripts/perf_gate.py`` exits 0 on a self-comparison, nonzero on an
  injected 20% throughput regression, and 0 on ``--dry-run`` against the
  repo's own BASELINE.json (the tier-1 wiring).
"""

import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu import telemetry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_MERGE = os.path.join(REPO_ROOT, "scripts", "trace_merge.py")
PERF_GATE = os.path.join(REPO_ROOT, "scripts", "perf_gate.py")
SCHEMA_PATH = os.path.join(REPO_ROOT, "deepspeed_tpu", "telemetry",
                           "summary.schema.json")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")
    yield
    telemetry.close()
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")


def _run(cmd):
    return subprocess.run([sys.executable] + cmd, capture_output=True,
                          text=True, cwd=REPO_ROOT)


# ---------------------------------------------------------------------------
# memory stream
# ---------------------------------------------------------------------------

def test_cpu_memory_stats_synthesized_from_live_arrays():
    """CPU PJRT backends expose no memory_stats; the accelerator synthesizes
    bytes_in_use from the live-array set (tagged) so CPU-mesh runs still get
    an occupancy stream and a peak watermark."""
    from deepspeed_tpu.accelerator import get_accelerator
    pin = jnp.ones((256, 256), jnp.float32)  # ≥256KB on device 0
    jax.block_until_ready(pin)
    stats = get_accelerator().memory_stats(0)
    assert stats.get("synthesized") is True
    assert stats["bytes_in_use"] >= pin.nbytes
    assert stats["peak_bytes_in_use"] >= stats["bytes_in_use"]
    del pin


def test_record_memory_stream_and_counter_track(tmp_path):
    jl = tmp_path / "m.jsonl"
    tr = tmp_path / "t.json"
    telemetry.configure(enabled=True, jsonl_path=str(jl),
                        chrome_trace_path=str(tr))
    pin = jnp.ones((128, 128), jnp.float32)
    jax.block_until_ready(pin)
    stats = telemetry.sample_memory("step", step=1)
    assert stats["bytes_in_use"] > 0
    telemetry.record_memory("ckpt/save",
                            stats={"bytes_in_use": 7, "peak_bytes_in_use": 9})
    s = telemetry.summary()
    assert s["memory"]["sample_count"] == 2
    assert s["memory"]["peak_bytes"] >= stats["peak_bytes_in_use"]
    telemetry.export_chrome_trace()
    doc = json.load(open(tr))
    counters = [e for e in doc["traceEvents"]
                if e["ph"] == "C" and e["name"] == "hbm_bytes_in_use"]
    assert len(counters) == 2
    telemetry.close()
    lines = [json.loads(ln) for ln in jl.read_text().splitlines()]
    mem_lines = [ln for ln in lines if ln["name"].startswith("memory/")]
    assert {ln["name"] for ln in mem_lines} == {"memory/step",
                                                "memory/ckpt/save"}
    assert all("host" in ln and "run_id" in ln for ln in lines)


def test_oom_postmortem_lists_top_live_buffers():
    """The RESOURCE_EXHAUSTED post-mortem names the buffers actually holding
    HBM — shape/dtype/nbytes/sharding, largest first — and lands on the
    Fault/* stream."""
    import gc
    # what an earlier test of this worker's left to the collector (an engine's
    # tiny weights in a cycle) is not this test's: it ranks what is live now
    gc.collect()
    telemetry.configure(enabled=True)
    big = jnp.ones((2048, 2048), jnp.float32)   # 16 MB: should rank first
    small = jnp.ones((8,), jnp.float32)
    jax.block_until_ready((big, small))
    report = telemetry.maybe_oom_postmortem(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating 123 bytes"))
    assert report is not None
    top = report["top_buffers"]
    assert top and top[0]["nbytes"] >= big.nbytes
    assert top[0]["shape"] == [2048, 2048] and "float32" in top[0]["dtype"]
    assert "sharding" in top[0]
    assert report["live_bytes_total"] >= big.nbytes
    s = telemetry.summary()
    assert s["memory"]["oom"] is True
    assert any(k.startswith("Fault/oom") for k in s["counters"])
    # a non-OOM error must NOT trigger a dump
    assert telemetry.maybe_oom_postmortem(ValueError("shape mismatch")) is None
    del big, small


# ---------------------------------------------------------------------------
# the 8-device acceptance run: ledger + merge + gate
# ---------------------------------------------------------------------------

def _train_run(tmp_path, eight_devices):
    """One engine train run with telemetry on; returns (jsonl, summary)."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    import deepspeed_tpu
    from deepspeed_tpu import comm as dist
    from tests.simple_model import SimpleModel, random_batches

    jl = tmp_path / "host0.jsonl"
    model = SimpleModel()
    batch = random_batches(1, 8)[0]
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "telemetry": {"enabled": True, "jsonl_path": str(jl),
                              "flops_per_step": 1e9, "peak_flops": 1e12}})
    # one explicit per-step collective through the comm shim, so the merged
    # trace has cross-host alignable comm/* records (stage-0 SimpleModel's
    # grad reduction is GSPMD-internal and invisible to host timing). A
    # fresh trace per step gives each record its own timestamp — a jitted
    # shard_map records only once, at trace time.
    mesh = Mesh(np.array(eight_devices), ("dp",))

    def _collective():
        ar = jax.jit(jax.shard_map(
            lambda x: dist.all_reduce(x, axis_name="dp"),
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False))
        jax.block_until_ready(ar(jnp.ones((8, 4), jnp.float32)))

    for b in random_batches(4, 8):
        loss = engine(b)
        engine.backward(loss)
        engine.step()
        _collective()
    summ = telemetry.summary()
    telemetry.close()
    return jl, summ


def test_train_loop_ledger_and_multihost_merge(eight_devices, tmp_path):
    jsonschema = pytest.importorskip("jsonschema")
    jl, s = _train_run(tmp_path, eight_devices)

    # summary passes the extended schema (memory + ledger streams)
    jsonschema.validate(s, json.load(open(SCHEMA_PATH)))

    # nonzero mfu/goodput gauges + ledger categories sum to wall within 5%
    led = s["ledger"]
    assert led["steps"] == 4
    assert led["mfu"] > 0 and led["mfu_rolling"] > 0
    assert led["goodput"] > 0
    assert led["seconds"]["compute"] > 0
    assert abs(sum(led["seconds"].values()) - led["wall_s"]) \
        <= 0.05 * led["wall_s"]
    gauges = {name for name, *_ in telemetry.monitor_events(1)}
    assert {"Telemetry/Ledger/mfu", "Telemetry/Ledger/goodput"} <= gauges

    # per-step memory samples with a nonzero peak (CPU-synthesized)
    assert s["memory"]["sample_count"] >= 4
    assert s["memory"]["peak_bytes"] > 0
    assert "Telemetry/Memory/peak_hbm_bytes" in gauges

    # ---- multi-host merge: a second host = the same stream re-stamped with
    # a growing skew, so host1's collectives arrive progressively later ----
    h1 = tmp_path / "host1.jsonl"
    records = [json.loads(ln) for ln in jl.read_text().splitlines()]
    with open(h1, "w") as f:
        for i, rec in enumerate(records):
            rec = dict(rec, host="host-b", pid=4242,
                       ts=rec["ts"] + 3.0 + 0.001 * i)
            f.write(json.dumps(rec) + "\n")

    merged = tmp_path / "merged_trace.json"
    report_p = tmp_path / "straggler.json"
    r = _run([TRACE_MERGE, str(jl), str(h1), "--out", str(merged),
              "--report", str(report_p)])
    assert r.returncode == 0, r.stderr

    doc = json.load(open(merged))
    # per-host tracks: 2 process_name labels, and a memory counter track
    # under EACH host pid
    metas = [e for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"]
    assert len(metas) == 2
    mem_pids = {e["pid"] for e in doc["traceEvents"]
                if e["ph"] == "C" and e["name"] == "hbm_bytes_in_use"}
    assert len(mem_pids) == 2, "memory counter track per host"
    span_names = {e["name"] for e in doc["traceEvents"] if e.get("cat") == "span"}
    assert {"fwd", "bwd", "step"} <= span_names

    # straggler report: collectives matched across hosts; the growing skew
    # makes host-b the consistently-late host
    report = json.loads(r.stdout)
    assert report["matched_collectives"] > 0
    assert report["max_skew_s"] > 0
    assert report["straggler"] == "host-b:4242"
    assert json.load(open(report_p))["matches"]

    # ---- perf gate on the run's own summary ----
    summ_p = tmp_path / "summary.json"
    summ_p.write_text(json.dumps(s))
    r = _run([PERF_GATE, "--baseline", str(summ_p), "--candidate",
              str(summ_p)])
    assert r.returncode == 0, r.stderr


# ---------------------------------------------------------------------------
# perf gate exit-code contract
# ---------------------------------------------------------------------------

def _bench_payload(value, mfu=0.32, hbm=10 << 30):
    return {"metric": "gpt2_small_bf16_zero1_tokens_per_sec_per_chip",
            "value": value, "unit": "tokens/s/chip", "vs_baseline": 1.0,
            "extra": {"mfu": mfu, "peak_hbm_bytes": hbm}}


def test_perf_gate_pass_and_regression(tmp_path):
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_bench_payload(1000.0)))
    # self-comparison passes
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(base)])
    assert r.returncode == 0, r.stderr
    verdicts = json.loads(r.stdout)["verdicts"]
    assert verdicts and not any(v["regressed"] for v in verdicts)
    # injected 20% throughput drop fails (threshold 10%)
    cand = tmp_path / "cand.json"
    cand.write_text(json.dumps(_bench_payload(800.0)))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand)])
    assert r.returncode == 3, (r.stdout, r.stderr)
    bad = [v for v in json.loads(r.stdout)["verdicts"] if v["regressed"]]
    assert [v["metric"] for v in bad] == ["tokens_per_sec"]
    # ...but passes with a generous threshold
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand),
              "--max-tokens-drop", "0.30"])
    assert r.returncode == 0
    # HBM growth gates in the OTHER direction
    fat = tmp_path / "fat.json"
    fat.write_text(json.dumps(_bench_payload(1000.0, hbm=12 << 30)))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(fat)])
    assert r.returncode == 3
    # malformed candidate -> 2
    bad_p = tmp_path / "bad.json"
    bad_p.write_text("{not json")
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(bad_p)])
    assert r.returncode == 2


def test_perf_gate_dry_run_tier1_wiring():
    """The tier-1 lane runs the gate in --dry-run against the repo's own
    BASELINE.json: a malformed baseline or summary schema must fail fast on
    CPU. The empty published{} baseline is valid (passes with a warning when
    compared)."""
    r = _run([PERF_GATE, "--baseline",
              os.path.join(REPO_ROOT, "BASELINE.json"), "--dry-run"])
    assert r.returncode == 0, (r.stdout, r.stderr)
    out = json.loads(r.stdout)
    assert out["inputs_ok"] is True
    # kernel tuning tables ride the same lane: checked-in table(s) must be
    # schema-valid and cover every bench shape (docs/AUTOTUNING.md)
    assert out["kernel_table"]["tables"], "no kernel table checked"
    for name, info in out["kernel_table"]["tables"].items():
        assert info["errors"] == [], (name, info)
    for name, cov in out["kernel_table"]["bench_coverage"].items():
        assert cov["covered"], (name, cov["missing"])
    # the overlap analyzer rides the same lane: the jax-free analytic
    # schedule must attribute as fully exposed with a non-empty critical path
    assert out["overlap"]["exposed_comm_s"] == out["overlap"]["comm_s"]
    assert out["overlap"]["critical_path_ops"] > 0
    # the postmortem exemplar rides the same lane: the checked-in bundle
    # must stay schema-valid and classify as its pinned incident type
    assert out["postmortem_bundle"] == {"bundles": 1}
    assert out["postmortem_classify"]["incidents"] == ["backend_unavailable"]


def test_perf_gate_postmortem_checks_catch_tampering(tmp_path):
    """validate_postmortem_bundle flags a schema-broken bundle and
    check_postmortem_classify flags a catalogue/classification drift."""
    import importlib.util
    import shutil
    spec = importlib.util.spec_from_file_location("_pg_pm", PERF_GATE)
    pg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pg)

    # the checked-in exemplar passes both checks
    report, errs = pg.validate_postmortem_bundle()
    assert errs == [] and report == {"bundles": 1}
    report, errs = pg.check_postmortem_classify()
    assert errs == [] and report["incidents"] == ["backend_unavailable"]

    # copy + strip a required manifest key -> validation error
    src = pg.POSTMORTEM_EXEMPLAR_DIR
    broken = tmp_path / "broken"
    shutil.copytree(src, broken)
    (bundle,) = [broken / n for n in os.listdir(broken)]
    man = json.loads((bundle / "manifest.json").read_text())
    del man["run_id"]
    (bundle / "manifest.json").write_text(json.dumps(man))
    _, errs = pg.validate_postmortem_bundle(exemplar_dir=str(broken))
    assert any("run_id" in e for e in errs)

    # copy + rewrite the flush reason -> classification pin fires
    drifted = tmp_path / "drifted"
    shutil.copytree(src, drifted)
    (bundle,) = [drifted / n for n in os.listdir(drifted)]
    man = json.loads((bundle / "manifest.json").read_text())
    man["reason"] = "oom"
    (bundle / "manifest.json").write_text(json.dumps(man))
    _, errs = pg.check_postmortem_classify(exemplar_dir=str(drifted))
    assert any("signature catalogue" in e for e in errs)

    # an empty exemplar dir is an error, a missing one is a skip
    empty = tmp_path / "empty"
    empty.mkdir()
    _, errs = pg.validate_postmortem_bundle(exemplar_dir=str(empty))
    assert errs, "an exemplar dir without a bundle must fail the gate"
    report, errs = pg.validate_postmortem_bundle(
        exemplar_dir=str(tmp_path / "absent"))
    assert errs == [] and "skipped" in report


def test_perf_gate_kernel_table_check_fails_on_bad_table(tmp_path,
                                                         monkeypatch):
    """check_kernel_tables flags schema breakage and bench-shape gaps."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("_pg", PERF_GATE)
    pg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pg)
    # empty dir -> error
    _, errs = pg.check_kernel_tables(tables_dir=str(tmp_path))
    assert any("no kernel tuning tables" in e for e in errs)
    # schema-invalid knobs -> error names the entry
    (tmp_path / "tpu_v5e.json").write_text(json.dumps({
        "format_version": 1, "device_kind": "tpu_v5e",
        "entries": {"flash_mha|tq1024,tk1024,dh64|bfloat16":
                    {"blocks": {"bogus": 7}}}}))
    _, errs = pg.check_kernel_tables(tables_dir=str(tmp_path))
    assert any("blocks must have exactly" in e for e in errs)
    # valid but missing bench shapes -> coverage error
    (tmp_path / "tpu_v5e.json").write_text(json.dumps({
        "format_version": 1, "device_kind": "tpu_v5e",
        "entries": {"flash_mha|tq1024,tk1024,dh64|bfloat16":
                    {"blocks": {"block_q": 512, "block_k": 512}}}}))
    report, errs = pg.check_kernel_tables(tables_dir=str(tmp_path))
    assert any("bench shapes uncovered" in e for e in errs)
    assert not report["bench_coverage"]["tpu_v5e.json"]["covered"]


def test_perf_gate_rejects_bad_embedded_summary(tmp_path):
    pytest.importorskip("jsonschema")
    doc = _bench_payload(1000.0)
    doc["extra"]["telemetry"] = {"enabled": True, "spans": {}, "bogus": 1}
    p = tmp_path / "badsum.json"
    p.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(p), "--dry-run"])
    assert r.returncode == 2
    assert "schema violation" in r.stderr


# ---------------------------------------------------------------------------
# serving gates (PR 6)
# ---------------------------------------------------------------------------

def _replay_payload(ttft=0.05, tpot=0.01, kv=0.4, value=500.0):
    return {"metric": "serving_replay_tokens_per_sec_per_chip",
            "value": value, "unit": "tokens/s/chip", "vs_baseline": None,
            "extra": {"ttft_p50_s": ttft, "ttft_p99_s": ttft * 3,
                      "tpot_p50_s": tpot, "tpot_p99_s": tpot * 2,
                      "peak_kv_occupancy": kv, "preemptions": 0,
                      "requests": 32, "seed": 0, "arrival": "poisson"}}


def test_perf_gate_serving_self_compare_and_ttft_regression(tmp_path):
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_replay_payload()))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(base)])
    assert r.returncode == 0, r.stderr
    compared = {v["metric"] for v in json.loads(r.stdout)["verdicts"]}
    assert {"ttft_p50_s", "ttft_p99_s", "tpot_p50_s", "tpot_p99_s",
            "peak_kv_occupancy", "tokens_per_sec"} <= compared
    # synthetic +20% TTFT (threshold 10%) -> regression, latency direction UP
    cand = tmp_path / "cand.json"
    cand.write_text(json.dumps(_replay_payload(ttft=0.06)))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand)])
    assert r.returncode == 3, (r.stdout, r.stderr)
    bad = {v["metric"] for v in json.loads(r.stdout)["verdicts"]
           if v["regressed"]}
    assert bad == {"ttft_p50_s", "ttft_p99_s"}
    # generous threshold waves the same candidate through
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand),
              "--max-ttft-growth", "0.30"])
    assert r.returncode == 0
    # TPOT gates independently
    cand.write_text(json.dumps(_replay_payload(tpot=0.02)))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand)])
    assert r.returncode == 3
    # KV-occupancy growth is a regression too (cache headroom shrank)
    cand.write_text(json.dumps(_replay_payload(kv=0.6)))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand)])
    assert r.returncode == 3


def test_perf_gate_dry_run_validates_replay_payload_shape(tmp_path):
    """--dry-run shape-checks a successful replay payload without jax: every
    serving metric present, percentiles ordered, occupancy in [0,1]. Error
    payloads (value 0) are exempt."""
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_replay_payload()))
    r = _run([PERF_GATE, "--baseline", str(good), "--dry-run"])
    assert r.returncode == 0, (r.stdout, r.stderr)
    metrics = json.loads(r.stdout)["metrics"]["baseline"]
    assert metrics["ttft_p50_s"] == 0.05

    doc = _replay_payload()
    del doc["extra"]["peak_kv_occupancy"]
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "peak_kv_occupancy" in r.stderr

    doc = _replay_payload()
    doc["extra"]["ttft_p50_s"] = doc["extra"]["ttft_p99_s"] * 2  # p50 > p99
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "p50 > p99" in r.stderr

    err_doc = {"metric": "serving_replay_tokens_per_sec_per_chip",
               "value": 0.0, "unit": "tokens/s/chip", "vs_baseline": None,
               "extra": {"error": "RuntimeError: backend init UNAVAILABLE"}}
    errp = tmp_path / "err.json"
    errp.write_text(json.dumps(err_doc))
    r = _run([PERF_GATE, "--baseline", str(errp), "--dry-run"])
    assert r.returncode == 0


# ---------------------------------------------------------------------------
# prefix-cache gates
# ---------------------------------------------------------------------------

def _prefix_payload(hit=0.6875, reduction=0.597015, saved=440, executed=297,
                    nocache=737, ttft=0.0049, ttft_nc=0.0573):
    """A --prefix-mix replay payload: the plain replay extra plus the
    prefix-cache comparison fields (internally consistent by default:
    reduction == (nocache - executed) / nocache, saved + executed <=
    prompt total, cached TTFT better than the nocache leg)."""
    doc = _replay_payload(ttft=ttft)
    doc["extra"].update({
        "prompt_tokens_total": nocache,
        "prefix_hit_rate": hit,
        "prefill_tokens_saved": saved,
        "executed_prefill_tokens": executed,
        "executed_prefill_tokens_nocache": nocache,
        "prefill_reduction": reduction,
        "ttft_p50_nocache_s": ttft_nc,
        "ttft_p99_nocache_s": ttft_nc * 2,
        "wall_nocache_s": 0.1,
        "cached_blocks_peak": 24})
    return doc


def test_perf_gate_dry_run_validates_prefix_payload_shape(tmp_path):
    """--dry-run shape-checks the prefix-mix fields without jax: hit rate
    in [0, 1], saved/executed tokens consistent with the prompt total."""
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_prefix_payload()))
    r = _run([PERF_GATE, "--baseline", str(good), "--dry-run"])
    assert r.returncode == 0, (r.stdout, r.stderr)
    metrics = json.loads(r.stdout)["metrics"]["baseline"]
    assert metrics["prefix_hit_rate"] == 0.6875
    assert metrics["prefill_reduction"] == 0.597015

    doc = _prefix_payload(hit=1.5)  # impossible hit rate
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "prefix_hit_rate" in r.stderr

    doc = _prefix_payload(saved=800)  # saved > prompt tokens
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "prefill_tokens_saved" in r.stderr

    doc = _prefix_payload()
    del doc["extra"]["executed_prefill_tokens_nocache"]
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "executed_prefill_tokens_nocache" in r.stderr


def test_perf_gate_prefix_hit_drop_gate(tmp_path):
    """prefix_hit_rate and prefill_reduction gate like any other serving
    metric: a drop past --max-prefix-hit-drop regresses."""
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_prefix_payload()))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(base)])
    assert r.returncode == 0, (r.stdout, r.stderr)
    compared = {v["metric"] for v in json.loads(r.stdout)["verdicts"]}
    assert {"prefix_hit_rate", "prefill_reduction"} <= compared
    # hit rate drops 0.6875 -> 0.5 (-27%, threshold 10%)
    cand = tmp_path / "cand.json"
    cand.write_text(json.dumps(_prefix_payload(
        hit=0.5, reduction=0.597015)))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand)])
    assert r.returncode == 3, (r.stdout, r.stderr)
    bad = {v["metric"] for v in json.loads(r.stdout)["verdicts"]
           if v["regressed"]}
    assert bad == {"prefix_hit_rate"}
    # generous threshold waves it through
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand),
              "--max-prefix-hit-drop", "0.35"])
    assert r.returncode == 0


def test_perf_gate_prefix_baseline_ratchet(tmp_path):
    """check_prefix_baseline enforces the acceptance ratchet on the
    checked-in prefix baseline: reduction >= 0.40, hit rate > 0.5, cached
    TTFT p50 no worse than the nocache leg, recorded reduction consistent
    with the executed token counts."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("_pg_prefix", PERF_GATE)
    pg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pg)

    good = tmp_path / "good.json"
    good.write_text(json.dumps(_prefix_payload()))
    report, errs = pg.check_prefix_baseline(str(good))
    assert errs == [] and report["prefix_hit_rate"] == 0.6875

    # reduction below the 0.40 ratchet ((737-516)/737 ~= 0.30)
    low = tmp_path / "low.json"
    low.write_text(json.dumps(_prefix_payload(
        reduction=0.2999, executed=516, saved=221)))
    _, errs = pg.check_prefix_baseline(str(low))
    assert any("reduction" in e for e in errs)

    # hit rate at/below 0.5 fails
    low.write_text(json.dumps(_prefix_payload(hit=0.5)))
    _, errs = pg.check_prefix_baseline(str(low))
    assert any("prefix_hit_rate" in e for e in errs)

    # cached TTFT p50 worse than the cache-off leg fails
    low.write_text(json.dumps(_prefix_payload(ttft=0.08, ttft_nc=0.05)))
    _, errs = pg.check_prefix_baseline(str(low))
    assert any("TTFT p50" in e for e in errs)

    # recorded reduction inconsistent with the token counts fails
    low.write_text(json.dumps(_prefix_payload(reduction=0.9)))
    _, errs = pg.check_prefix_baseline(str(low))
    assert any("does not match derived" in e for e in errs)

    # no baseline file -> skip, not error (pre-prefix-cache checkouts)
    report, errs = pg.check_prefix_baseline(str(tmp_path / "absent.json"))
    assert errs == [] and "skipped" in report

    # the repo's own checked-in baseline passes the ratchet
    report, errs = pg.check_prefix_baseline()
    assert errs == [], errs
    assert report["prefill_reduction"] >= pg.PREFIX_MIN_REDUCTION
    assert report["prefix_hit_rate"] > pg.PREFIX_MIN_HIT_RATE


def test_bench_serving_prefix_mix_cpu_acceptance(tmp_path):
    """The seeded shared-prefix replay end to end on CPU: one payload whose
    prefix fields are internally consistent, accepted by perf_gate both in
    self-comparison and dry-run shape validation."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                      "bench_serving.py"),
         "--replay", "--prefix-mix", "--requests", "8", "--seed", "7",
         "--rate", "200"],
        capture_output=True, text=True, cwd=REPO_ROOT, env=env, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    payloads = [json.loads(ln) for ln in r.stdout.splitlines()
                if ln.startswith("{")]
    assert len(payloads) == 1
    doc = payloads[0]
    assert doc["metric"] == "serving_replay_tokens_per_sec_per_chip"
    assert doc["value"] > 0
    ex = doc["extra"]
    assert 0.0 < ex["prefix_hit_rate"] <= 1.0
    assert ex["prefill_reduction"] > 0
    assert ex["executed_prefill_tokens"] + ex["prefill_tokens_saved"] \
        <= ex["prompt_tokens_total"]
    assert ex["executed_prefill_tokens_nocache"] == ex["prompt_tokens_total"]
    assert 0 < ex["ttft_p50_s"] <= ex["ttft_p99_s"]
    p = tmp_path / "prefix.json"
    p.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(p), "--candidate", str(p)])
    assert r.returncode == 0, (r.stdout, r.stderr)
    r = _run([PERF_GATE, "--baseline", str(p), "--dry-run"])
    assert r.returncode == 0, (r.stdout, r.stderr)


# ---------------------------------------------------------------------------
# overlap exposure (ISSUE 8)
# ---------------------------------------------------------------------------

OVERLAP_REPORT = os.path.join(REPO_ROOT, "scripts", "overlap_report.py")


def _overlap_payload(exposed=1e-3, comm=None):
    comm = exposed if comm is None else comm
    return {"metric": "overlap_exposed_comm_s", "value": exposed, "unit": "s",
            "extra": {"overlap": {
                "mode": "analytic", "devices": 1,
                "step_s": 1e-3 + comm, "compute_s": 1e-3, "comm_s": comm,
                "overlapped_comm_s": round(comm - exposed, 9),
                "exposed_comm_s": exposed, "gap_s": 0.0,
                "overlap_fraction": round(1.0 - exposed / comm, 6),
                "exposed_fraction": round(exposed / comm, 6),
                "collectives": [], "advice": [],
                "critical_path": {"device": "d0", "length_s": 1e-3 + comm,
                                  "compute_s": 1e-3, "comm_s": comm,
                                  "exposed_comm_s": exposed, "ops": []}}}}


def test_perf_gate_exposed_growth_gate(tmp_path):
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_overlap_payload(exposed=1e-3)))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(base)])
    assert r.returncode == 0, r.stderr
    compared = {v["metric"] for v in json.loads(r.stdout)["verdicts"]}
    assert compared == {"exposed_comm_s"}, \
        "exposed SECONDS must never be lifted as throughput"
    # +50% exposure (threshold 10%) -> regression in the UP direction
    cand = tmp_path / "cand.json"
    cand.write_text(json.dumps(_overlap_payload(exposed=1.5e-3, comm=1.5e-3)))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand)])
    assert r.returncode == 3, (r.stdout, r.stderr)
    bad = {v["metric"] for v in json.loads(r.stdout)["verdicts"]
           if v["regressed"]}
    assert bad == {"exposed_comm_s"}
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand),
              "--max-exposed-growth", "0.60"])
    assert r.returncode == 0
    # LESS exposure is an improvement, never a regression
    cand.write_text(json.dumps(_overlap_payload(exposed=2e-4, comm=1e-3)))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand)])
    assert r.returncode == 0, (r.stdout, r.stderr)


def test_perf_gate_validates_overlap_payload_shape(tmp_path):
    # exposure > comm total is structurally impossible -> reject (exit 2)
    doc = _overlap_payload(exposed=1e-3)
    doc["extra"]["overlap"]["exposed_comm_s"] = 5.0
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "overlap report invalid" in r.stderr
    # NaN fractions are rejected without jsonschema (pure dict checks)
    doc = _overlap_payload(exposed=1e-3)
    doc["extra"]["overlap"]["overlap_fraction"] = float("nan")
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "overlap" in r.stderr


def test_overlap_report_analytic_cpu_acceptance(tmp_path):
    """The chip-free analytic report end to end on CPU: trace a ZeRO-shaped
    collective mix on 8 forced host devices, model the serialized schedule,
    and emit a payload perf_gate accepts — the ISSUE 8 acceptance path."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, OVERLAP_REPORT, "--analytic"],
        capture_output=True, text=True, cwd=REPO_ROOT, env=env, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    payloads = [json.loads(ln) for ln in r.stdout.splitlines()
                if ln.startswith("{")]
    assert len(payloads) == 1
    doc = payloads[0]
    assert doc["metric"] == "overlap_exposed_comm_s"
    rep = doc["extra"]["overlap"]
    assert rep["mode"] == "analytic"
    # synchronous-XLA model: every collective serialized, fully exposed
    assert rep["exposed_fraction"] == 1.0
    assert doc["value"] == rep["exposed_comm_s"] > 0
    ops = {c["op"] for c in rep["collectives"]}
    assert {"all_gather", "reduce_scatter", "all_reduce"} <= ops
    assert all(c["bytes"] > 0 for c in rep["collectives"])
    assert rep["advice"], "serialized collectives next to compute must " \
                          "yield prefetch advice"
    assert len(rep["critical_path"]["ops"]) >= 4
    # the summary rides along with the overlap section attached + valid
    assert doc["extra"]["telemetry"]["overlap"] == rep
    # and the payload passes the gate: shape validation + self-comparison
    p = tmp_path / "overlap.json"
    p.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(p), "--candidate", str(p)])
    assert r.returncode == 0, (r.stdout, r.stderr)


def test_trace_merge_exposure_ranking_and_lanes(tmp_path):
    """Straggler report ranks hosts by exposed-comm seconds and the merged
    trace carries per-host exposure lanes: host-a hides its collective under
    fwd, host-b runs it in the open."""
    def _write(path, host, pid, span_end, comm_end):
        recs = [
            {"kind": "span", "name": "fwd", "ts": span_end, "value": 1.0,
             "host": host, "pid": pid, "run_id": "r"},
            {"kind": "gauge", "name": "comm/all_reduce", "ts": comm_end,
             "value": 4096, "tags": {"axis": "dp", "seconds": 1.0},
             "host": host, "pid": pid, "run_id": "r"},
        ]
        with open(path, "w") as f:
            for rec in recs:
                f.write(json.dumps(rec) + "\n")

    a = tmp_path / "a.jsonl"
    b = tmp_path / "b.jsonl"
    _write(a, "host-a", 1, span_end=2.0, comm_end=1.5)  # comm [0.5,1.5] ⊂ fwd [1,2]...
    _write(b, "host-b", 2, span_end=1.0, comm_end=3.0)  # comm [2,3] after fwd [0,1]
    merged = tmp_path / "merged.json"
    r = _run([TRACE_MERGE, str(a), str(b), "--out", str(merged)])
    assert r.returncode == 0, r.stderr
    report = json.loads(r.stdout)
    exp = report["exposure_by_host"]
    # host-a: comm [0.5,1.5] vs fwd [1.0,2.0] -> exposed [0.5,1.0] = 0.5s
    assert exp["host-a:1"]["exposed_comm_s"] == pytest.approx(0.5)
    # host-b: comm [2,3] entirely outside fwd [0,1] -> fully exposed
    assert exp["host-b:2"]["exposed_comm_s"] == pytest.approx(1.0)
    assert exp["host-b:2"]["exposed_fraction"] == pytest.approx(1.0)
    assert report["most_exposed_host"] == "host-b:2"
    # ranking order: most exposed first
    assert list(exp) == ["host-b:2", "host-a:1"]
    # merged trace: exposure lane (tid 1, cat "exposure") under both hosts
    doc = json.load(open(merged))
    lanes = [e for e in doc["traceEvents"] if e.get("cat") == "exposure"]
    assert lanes and all(e["tid"] == 1 for e in lanes)
    assert {e["name"] for e in lanes} == {"exposed:all_reduce"}
    thread_meta = [e for e in doc["traceEvents"]
                   if e["ph"] == "M" and e["name"] == "thread_name"]
    assert {e["args"]["name"] for e in thread_meta} == {"exposure"}


@pytest.mark.slow
def test_bench_serving_replay_cpu_acceptance(tmp_path):
    """The seeded replay harness end to end on CPU: one JSON payload with
    p50/p99 TTFT, TPOT, tokens/s/chip and peak KV occupancy, accepted by
    perf_gate in self-comparison (the ISSUE 6 acceptance path)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", DS_TPU_TELEMETRY="1")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                      "bench_serving.py"),
         "--replay", "--seed", "7"],
        capture_output=True, text=True, cwd=REPO_ROOT, env=env, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    payloads = [json.loads(ln) for ln in r.stdout.splitlines()
                if ln.startswith("{")]
    assert len(payloads) == 1
    doc = payloads[0]
    assert doc["metric"] == "serving_replay_tokens_per_sec_per_chip"
    assert doc["value"] > 0
    ex = doc["extra"]
    assert 0 < ex["ttft_p50_s"] <= ex["ttft_p99_s"]
    assert 0 < ex["tpot_p50_s"] <= ex["tpot_p99_s"]
    assert 0 < ex["peak_kv_occupancy"] <= 1.0
    assert ex["telemetry"]["serving"]["requests"]["finished"] == \
        ex["requests"]
    # per-SLO-class section (PR 17): both built-in classes with attainment
    # arithmetic intact and percentiles, a headline min attainment, and
    # non-empty time-series rings for >= 3 gauges
    slo = ex["slo_classes"]
    assert set(slo) == {"interactive", "batch"}
    for entry in slo.values():
        for st in entry["metrics"].values():
            assert st["attained"] + st["violations"] == st["requests"]
            assert 0.0 <= st["attainment"] <= 1.0
        pcts = entry["percentiles"]
        assert pcts["ttft"]["p50_s"] <= pcts["ttft"]["p99_s"]
    assert 0.0 <= ex["slo_min_attainment"] <= 1.0
    series = ex["telemetry"]["timeseries"]
    live = [n for n, ring in series.items() if ring["windows"]]
    assert len(live) >= 3, sorted(series)
    p = tmp_path / "replay.json"
    p.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(p), "--candidate", str(p)])
    assert r.returncode == 0, (r.stdout, r.stderr)
    # the attainment floor gates the same payload
    r = _run([PERF_GATE, "--baseline", str(p), "--candidate", str(p),
              "--min-slo-attainment", "0.5"])
    assert r.returncode == 0, (r.stdout, r.stderr)
    r = _run([PERF_GATE, "--baseline", str(p), "--candidate", str(p),
              "--min-slo-attainment", "1.01"])
    assert r.returncode == 3, (r.stdout, r.stderr)


# ---------------------------------------------------------------------------
# fleet gates (bench_serving --fleet --replay / check_fleet_baseline)
# ---------------------------------------------------------------------------

def _fleet_payload(mult=2.25, shed=0.0, handoffs=28, shipped=399, bound=399,
                   ttft99=0.34, single99=0.94):
    """A --fleet --replay payload: both legs' percentiles, the admission
    accounting, and the KV-handoff conservation counters (internally
    consistent by default: pages shipped == bound, fleet tail TTFT better
    than the saturated single replica, multiplier over the 2x ratchet)."""
    return {"metric": "serving_fleet_replay_tokens_per_sec_per_chip",
            "value": 970.0, "unit": "tokens/s/chip (prefill+decode)",
            "vs_baseline": None,
            "extra": {"ttft_p50_s": 0.18, "ttft_p99_s": ttft99,
                      "tpot_p50_s": 0.047, "tpot_p99_s": 0.075,
                      "rate_multiplier": mult, "shed_rate": shed,
                      "requests_per_sec": 69.0,
                      "single_requests_per_sec": 30.6,
                      "single_ttft_p50_s": 0.46, "single_ttft_p99_s": single99,
                      "handoffs": handoffs, "handoff_transfers": 15,
                      "pages_shipped": shipped, "pages_bound": bound,
                      "handoff_bytes": 2162688, "handoff_total_s": 0.058,
                      "prefill_replicas": 2, "decode_replicas": 1,
                      "requests": 32}}


def test_perf_gate_dry_run_validates_fleet_payload_shape(tmp_path):
    """--dry-run shape-checks a successful fleet payload without jax: both
    legs' percentiles finite and ordered, shed rate in [0, 1], every
    shipped page bound. Error payloads (value 0) are exempt."""
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_fleet_payload()))
    r = _run([PERF_GATE, "--baseline", str(good), "--dry-run"])
    assert r.returncode == 0, (r.stdout, r.stderr)
    metrics = json.loads(r.stdout)["metrics"]["baseline"]
    assert metrics["rate_multiplier"] == 2.25

    doc = _fleet_payload()
    del doc["extra"]["single_ttft_p99_s"]
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "single_ttft_p99_s" in r.stderr

    doc = _fleet_payload(ttft99=0.05)  # fleet p50 0.18 > p99 0.05
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "p50 > p99" in r.stderr

    doc = _fleet_payload(shed=1.5)
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "shed_rate" in r.stderr

    doc = _fleet_payload(bound=390)  # shipped 399 != bound 390: leak
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "pages_shipped" in r.stderr

    err_doc = {"metric": "serving_fleet_replay_tokens_per_sec_per_chip",
               "value": 0.0, "unit": "tokens/s/chip", "vs_baseline": None,
               "extra": {"error": "RuntimeError: backend init UNAVAILABLE"}}
    errp = tmp_path / "err.json"
    errp.write_text(json.dumps(err_doc))
    r = _run([PERF_GATE, "--baseline", str(errp), "--dry-run"])
    assert r.returncode == 0


def test_perf_gate_fleet_rate_multiplier_gate(tmp_path):
    """rate_multiplier gates like any other serving metric: a drop past
    --max-rate-multiplier-drop regresses."""
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_fleet_payload()))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(base)])
    assert r.returncode == 0, (r.stdout, r.stderr)
    compared = {v["metric"] for v in json.loads(r.stdout)["verdicts"]}
    assert "rate_multiplier" in compared
    # 2.25 -> 1.8 (-20%, threshold 10%)
    cand = tmp_path / "cand.json"
    cand.write_text(json.dumps(_fleet_payload(mult=1.8)))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand)])
    assert r.returncode == 3, (r.stdout, r.stderr)
    bad = {v["metric"] for v in json.loads(r.stdout)["verdicts"]
           if v["regressed"]}
    assert bad == {"rate_multiplier"}
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand),
              "--max-rate-multiplier-drop", "0.25"])
    assert r.returncode == 0


def test_perf_gate_fleet_baseline_ratchet(tmp_path):
    """check_fleet_baseline enforces the acceptance ratchet on the
    checked-in fleet baseline: multiplier >= 2x, shed rate <= 0.1, at least
    one handoff, fleet tail TTFT no worse than the saturated single
    replica."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("_pg_fleet", PERF_GATE)
    pg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pg)

    good = tmp_path / "good.json"
    good.write_text(json.dumps(_fleet_payload()))
    report, errs = pg.check_fleet_baseline(str(good))
    assert errs == [] and report["rate_multiplier"] == 2.25

    low = tmp_path / "low.json"
    low.write_text(json.dumps(_fleet_payload(mult=1.9)))
    _, errs = pg.check_fleet_baseline(str(low))
    assert any("rate multiplier" in e for e in errs)

    low.write_text(json.dumps(_fleet_payload(shed=0.2)))
    _, errs = pg.check_fleet_baseline(str(low))
    assert any("shed_rate" in e for e in errs)

    low.write_text(json.dumps(_fleet_payload(handoffs=0)))
    _, errs = pg.check_fleet_baseline(str(low))
    assert any("handoffs" in e for e in errs)

    # disaggregation that WORSENS tail TTFT vs the saturated single
    # replica defeats its own purpose
    low.write_text(json.dumps(_fleet_payload(ttft99=0.95, single99=0.94)))
    _, errs = pg.check_fleet_baseline(str(low))
    assert any("TTFT p99" in e for e in errs)

    # no baseline file -> skip, not error (pre-fleet checkouts)
    report, errs = pg.check_fleet_baseline(str(tmp_path / "absent.json"))
    assert errs == [] and "skipped" in report

    # the repo's own checked-in baseline passes the ratchet
    report, errs = pg.check_fleet_baseline()
    assert errs == [], errs
    assert report["rate_multiplier"] >= pg.FLEET_MIN_RATE_MULTIPLIER
    assert report["shed_rate"] <= pg.FLEET_MAX_SHED_RATE
    assert report["handoffs"] > 0


@pytest.mark.slow
def test_bench_serving_fleet_cpu_acceptance(tmp_path):
    """The disaggregated fleet replay end to end on CPU: one payload whose
    two legs and handoff counters are internally consistent, accepted by
    perf_gate dry-run shape validation. (The >= 2x multiplier itself is
    pinned by the checked-in serving_fleet_baseline.json ratchet — at the
    small request count this smoke run uses, saturation is too shallow to
    assert it.)"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                      "bench_serving.py"),
         "--replay", "--fleet", "--requests", "8", "--seed", "7"],
        capture_output=True, text=True, cwd=REPO_ROOT, env=env, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    payloads = [json.loads(ln) for ln in r.stdout.splitlines()
                if ln.startswith("{")]
    assert len(payloads) == 1
    doc = payloads[0]
    assert doc["metric"] == "serving_fleet_replay_tokens_per_sec_per_chip"
    assert doc["value"] > 0
    ex = doc["extra"]
    assert 0 < ex["ttft_p50_s"] <= ex["ttft_p99_s"]
    assert 0 < ex["single_ttft_p50_s"] <= ex["single_ttft_p99_s"]
    assert ex["rate_multiplier"] > 0
    assert ex["handoffs"] > 0
    assert ex["pages_shipped"] == ex["pages_bound"] > 0
    assert 0 <= ex["shed_rate"] <= 1
    p = tmp_path / "fleet.json"
    p.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(p), "--dry-run"])
    assert r.returncode == 0, (r.stdout, r.stderr)


# ---------------------------------------------------------------------------
# long-context tiering gates (bench_serving --long-context /
# check_longctx_baseline)
# ---------------------------------------------------------------------------

def _longctx_payload(mult=4.0, out=32, inn=8, dropped=0, resident=24,
                     live=0, occupancy=0.46, stall=0.13, reduction=0.55,
                     ttft99=0.135):
    """A --long-context payload: the capacity ratchet fields, the pressured
    fp leg's swap accounting (internally consistent by default:
    swapped_out == swapped_in + dropped + resident, zero live swap-outs,
    multiplier over the 2x ratchet), and finite ordered percentiles."""
    return {"metric": "serving_longctx_concurrent_seqs_per_chip",
            "value": 4.0,
            "unit": "max-context sequences/chip at the fp leg's KV HBM "
                    "budget",
            "vs_baseline": None,
            "extra": {"concurrent_sequences_per_chip": 4.0,
                      "concurrent_sequences_per_chip_fp": 1.0,
                      "capacity_multiplier": mult,
                      "kv_hbm_budget_bytes": 77824,
                      "fp_blocks": 19, "int8_blocks": 60,
                      "swapped_out": out, "swapped_in": inn,
                      "swap_dropped": dropped,
                      "resident_host_blocks": resident,
                      "host_kv_occupancy": occupancy,
                      "host_kv_capacity_blocks": 52,
                      "swap_outs_live": live,
                      "swap_in_stall_s": stall, "swap_in_p50_s": 0.0016,
                      "swap_out_stall_s": 0.0008,
                      "ttft_p50_s": 0.0039, "ttft_p99_s": ttft99,
                      "tpot_p50_s": 0.0027, "tpot_p99_s": 0.0027,
                      "prefill_reduction": reduction,
                      "prefill_tokens_saved": 384,
                      "executed_prefill_tokens": 316,
                      "prefix_hit_rate": 0.667, "requests": 6}}


def test_perf_gate_dry_run_validates_longctx_payload_shape(tmp_path):
    """--dry-run shape-checks a successful long-context payload without
    jax: finite ordered percentiles, host occupancy in [0, 1], and the
    swap accounting identity. Error payloads (value 0) are exempt."""
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_longctx_payload()))
    r = _run([PERF_GATE, "--baseline", str(good), "--dry-run"])
    assert r.returncode == 0, (r.stdout, r.stderr)
    metrics = json.loads(r.stdout)["metrics"]["baseline"]
    assert metrics["swap_in_stall_s"] == 0.13

    doc = _longctx_payload()
    del doc["extra"]["resident_host_blocks"]
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "resident_host_blocks" in r.stderr

    doc = _longctx_payload(ttft99=0.001)  # p50 0.0039 > p99 0.001
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "p50 > p99" in r.stderr

    doc = _longctx_payload(occupancy=1.5)
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "host_kv_occupancy" in r.stderr

    # 32 != 8 + 0 + 20: the host tier leaked 4 blocks
    doc = _longctx_payload(resident=20)
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "swapped_out" in r.stderr

    err_doc = {"metric": "serving_longctx_concurrent_seqs_per_chip",
               "value": 0.0, "unit": "sequences/chip", "vs_baseline": None,
               "extra": {"error": "RuntimeError: backend init UNAVAILABLE"}}
    errp = tmp_path / "err.json"
    errp.write_text(json.dumps(err_doc))
    r = _run([PERF_GATE, "--baseline", str(errp), "--dry-run"])
    assert r.returncode == 0


def test_perf_gate_swap_stall_gate(tmp_path):
    """swap_in_stall_s gates upward: stall growth past
    --max-swap-stall-growth regresses (restores stopped overlapping or the
    swap path got slower)."""
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_longctx_payload()))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(base)])
    assert r.returncode == 0, (r.stdout, r.stderr)
    compared = {v["metric"] for v in json.loads(r.stdout)["verdicts"]}
    assert "swap_in_stall_s" in compared
    # 0.13 -> 0.20 (+54%, threshold 25%)
    cand = tmp_path / "cand.json"
    cand.write_text(json.dumps(_longctx_payload(stall=0.20)))
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand)])
    assert r.returncode == 3, (r.stdout, r.stderr)
    bad = {v["metric"] for v in json.loads(r.stdout)["verdicts"]
           if v["regressed"]}
    assert bad == {"swap_in_stall_s"}
    r = _run([PERF_GATE, "--baseline", str(base), "--candidate", str(cand),
              "--max-swap-stall-growth", "0.60"])
    assert r.returncode == 0


def test_perf_gate_longctx_baseline_ratchet(tmp_path):
    """check_longctx_baseline enforces the tiering acceptance ratchet:
    capacity multiplier >= 2x, at least one spill AND one restore, zero
    live swap-outs, positive prefill reduction."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("_pg_longctx", PERF_GATE)
    pg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pg)

    good = tmp_path / "good.json"
    good.write_text(json.dumps(_longctx_payload()))
    report, errs = pg.check_longctx_baseline(str(good))
    assert errs == [] and report["capacity_multiplier"] == 4.0

    low = tmp_path / "low.json"
    low.write_text(json.dumps(_longctx_payload(mult=1.8)))
    _, errs = pg.check_longctx_baseline(str(low))
    assert any("capacity multiplier" in e for e in errs)

    low.write_text(json.dumps(_longctx_payload(out=0, inn=0, resident=0)))
    _, errs = pg.check_longctx_baseline(str(low))
    assert any("spilled" in e for e in errs)
    assert any("restored" in e for e in errs)

    low.write_text(json.dumps(_longctx_payload(live=2)))
    _, errs = pg.check_longctx_baseline(str(low))
    assert any("live swap-outs" in e for e in errs)

    low.write_text(json.dumps(_longctx_payload(reduction=0.0)))
    _, errs = pg.check_longctx_baseline(str(low))
    assert any("prefill reduction" in e for e in errs)

    # no baseline file -> skip, not error (pre-tiering checkouts)
    report, errs = pg.check_longctx_baseline(str(tmp_path / "absent.json"))
    assert errs == [] and "skipped" in report

    # the repo's own checked-in baseline passes the ratchet
    report, errs = pg.check_longctx_baseline()
    assert errs == [], errs
    assert report["capacity_multiplier"] >= \
        pg.LONGCTX_MIN_CAPACITY_MULTIPLIER
    assert report["swapped_out"] >= 1 and report["swapped_in"] >= 1
    assert report["prefill_reduction"] > 0


# ---------------------------------------------------------------------------
# speculative-decode gates (bench_serving --speculate /
# check_speculate_baseline)
# ---------------------------------------------------------------------------

def _speculate_payload(mult=2.4, accept=0.78, occ=1.0, parity=True,
                       speculated=294, accepted=231, rejected=63,
                       tpr=5.4, wall=0.085, wall_plain=0.204):
    """A --speculate payload: the multiplier ratchet field, the speculation
    counter identity (internally consistent by default: speculated ==
    accepted + rejected), and the greedy-parity oracle flag."""
    return {"metric": "serving_speculate_tokens_per_sec_multiplier",
            "value": mult,
            "unit": "x (plain wall / speculate wall, same greedy trace)",
            "vs_baseline": None,
            "extra": {"tokens_per_sec_multiplier": mult,
                      "accept_rate": accept,
                      "verify_batch_occupancy": occ,
                      "greedy_parity": parity,
                      "speculated_tokens": speculated,
                      "accepted_tokens": accepted,
                      "rejected_tokens": rejected,
                      "tokens_per_round": tpr,
                      "wall_s": wall, "wall_plain_s": wall_plain,
                      "repetitions": 3, "seed": 31,
                      "prompt_len": 40, "new_tokens": 96,
                      "max_draft_tokens": 7, "token_budget": 32}}


def test_perf_gate_dry_run_validates_speculate_payload_shape(tmp_path):
    """--dry-run shape-checks a successful --speculate payload without jax:
    finite fields, accept rate and occupancy in [0, 1], the speculation
    counter identity, and a boolean parity flag. Error payloads (value 0)
    are exempt."""
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_speculate_payload()))
    r = _run([PERF_GATE, "--baseline", str(good), "--dry-run"])
    assert r.returncode == 0, (r.stdout, r.stderr)

    doc = _speculate_payload()
    del doc["extra"]["accept_rate"]
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "accept_rate" in r.stderr

    doc = _speculate_payload(accept=1.5)
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "accept_rate" in r.stderr

    # 294 != 231 + 50: the verify loop lost 13 drafted tokens
    doc = _speculate_payload(rejected=50)
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "speculated_tokens" in r.stderr

    doc = _speculate_payload(parity="yes")
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "greedy_parity" in r.stderr

    doc = _speculate_payload(tpr=0.8)
    bad.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(bad), "--dry-run"])
    assert r.returncode == 2 and "tokens_per_round" in r.stderr

    err_doc = {"metric": "serving_speculate_tokens_per_sec_multiplier",
               "value": 0.0, "unit": "x", "vs_baseline": None,
               "extra": {"error": "RuntimeError: backend init UNAVAILABLE"}}
    errp = tmp_path / "err.json"
    errp.write_text(json.dumps(err_doc))
    r = _run([PERF_GATE, "--baseline", str(errp), "--dry-run"])
    assert r.returncode == 0


def test_perf_gate_speculate_baseline_ratchet(tmp_path):
    """check_speculate_baseline enforces the speculation acceptance
    ratchet: tokens/s multiplier >= 1.5x, greedy parity True, and at least
    one token drafted AND accepted."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("_pg_spec", PERF_GATE)
    pg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pg)

    good = tmp_path / "good.json"
    good.write_text(json.dumps(_speculate_payload()))
    report, errs = pg.check_speculate_baseline(str(good))
    assert errs == [] and report["tokens_per_sec_multiplier"] == 2.4

    low = tmp_path / "low.json"
    low.write_text(json.dumps(_speculate_payload(mult=1.2)))
    _, errs = pg.check_speculate_baseline(str(low))
    assert any("multiplier" in e for e in errs)

    low.write_text(json.dumps(_speculate_payload(parity=False)))
    _, errs = pg.check_speculate_baseline(str(low))
    assert any("parity" in e for e in errs)

    low.write_text(json.dumps(_speculate_payload(
        speculated=0, accepted=0, rejected=0)))
    _, errs = pg.check_speculate_baseline(str(low))
    assert any("drafted" in e for e in errs)

    low.write_text(json.dumps(_speculate_payload(
        speculated=5, accepted=0, rejected=5)))
    _, errs = pg.check_speculate_baseline(str(low))
    assert any("accepted" in e for e in errs)

    # no baseline file -> skip, not error (pre-speculation checkouts)
    report, errs = pg.check_speculate_baseline(str(tmp_path / "absent.json"))
    assert errs == [] and "skipped" in report

    # the repo's own checked-in baseline passes the ratchet
    report, errs = pg.check_speculate_baseline()
    assert errs == [], errs
    assert report["tokens_per_sec_multiplier"] >= \
        pg.SPECULATE_MIN_MULTIPLIER
    assert report["greedy_parity"] is True
    assert 0.0 < report["accept_rate"] <= 1.0
    assert report["speculated_tokens"] >= 1


# ---------------------------------------------------------------------------
# elastic-reshard drill gate (fault_drill --emit-elastic-baseline /
# check_elastic_baseline)
# ---------------------------------------------------------------------------

def _elastic_payload(worlds=(8, 4, 8), lost=0, doubled=0, bitwise=True,
                     opt_step=6, shrink=0.4, expand=0.1):
    """An elastic drill baseline payload: the 8→4→8 world sequence, the
    trajectory accounting (nothing lost, nothing double-applied, bitwise
    restore), and both reshard legs' wall-seconds."""
    return {"drill": "elastic-reshard-8-4-8", "steps": 6,
            "fail_at_step": 2, "expand_at": 4,
            "world_sequence": list(worlds), "reshard_count": 2,
            "reshard_s": {"shrink": shrink, "expand": expand},
            "steps_lost": lost, "steps_double_applied": doubled,
            "restore_loss_bitwise_equal": bitwise,
            "final_optimizer_step": opt_step, "restore_steps": [2, 4],
            "trajectory_max_rel_err": 1.1e-7}


def test_perf_gate_elastic_baseline_ratchet(tmp_path):
    """check_elastic_baseline enforces the elasticity acceptance ratchet:
    the recorded drill shrank 8→4 and re-expanded 4→8, lost zero steps,
    double-applied none, restored the loss bitwise, and kept each reshard
    leg under the wall-clock ceiling."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("_pg_elastic", PERF_GATE)
    pg = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pg)

    good = tmp_path / "good.json"
    good.write_text(json.dumps(_elastic_payload()))
    report, errs = pg.check_elastic_baseline(str(good))
    assert errs == [] and report["world_sequence"] == [8, 4, 8]

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(_elastic_payload(worlds=(8, 4))))
    _, errs = pg.check_elastic_baseline(str(bad))
    assert any("world sequence" in e for e in errs)

    bad.write_text(json.dumps(_elastic_payload(lost=2)))
    _, errs = pg.check_elastic_baseline(str(bad))
    assert any("steps lost" in e for e in errs)

    bad.write_text(json.dumps(_elastic_payload(doubled=1)))
    _, errs = pg.check_elastic_baseline(str(bad))
    assert any("double-applied" in e for e in errs)

    bad.write_text(json.dumps(_elastic_payload(bitwise=False)))
    _, errs = pg.check_elastic_baseline(str(bad))
    assert any("bitwise" in e for e in errs)

    from deepspeed_tpu.resilience.elastic_reshard import RESTORE_LOSS_MAX_ULPS
    assert pg.ELASTIC_RESTORE_MAX_ULPS == RESTORE_LOSS_MAX_ULPS
    good.write_text(json.dumps(dict(_elastic_payload(), restore_loss_ulps={
        "2": RESTORE_LOSS_MAX_ULPS, "4": 0})))
    assert pg.check_elastic_baseline(str(good))[1] == []
    bad.write_text(json.dumps(dict(_elastic_payload(), restore_loss_ulps={
        "2": RESTORE_LOSS_MAX_ULPS + 1, "4": 0})))
    _, errs = pg.check_elastic_baseline(str(bad))
    assert any("ulps" in e for e in errs)

    bad.write_text(json.dumps(_elastic_payload(opt_step=5)))
    _, errs = pg.check_elastic_baseline(str(bad))
    assert any("optimizer step count" in e for e in errs)

    bad.write_text(json.dumps(
        _elastic_payload(shrink=pg.ELASTIC_MAX_RESHARD_S + 1)))
    _, errs = pg.check_elastic_baseline(str(bad))
    assert any("ceiling" in e for e in errs)

    doc = _elastic_payload()
    del doc["reshard_s"]["expand"]
    bad.write_text(json.dumps(doc))
    _, errs = pg.check_elastic_baseline(str(bad))
    assert any("no expand reshard" in e for e in errs)

    doc = _elastic_payload()
    del doc["steps_lost"]
    bad.write_text(json.dumps(doc))
    _, errs = pg.check_elastic_baseline(str(bad))
    assert any("missing fields" in e for e in errs)

    bad.write_text(json.dumps({"drill": "something-else"}))
    _, errs = pg.check_elastic_baseline(str(bad))
    assert any("not an elastic-reshard drill" in e for e in errs)

    # no baseline file -> skip, not error (pre-elasticity checkouts)
    report, errs = pg.check_elastic_baseline(str(tmp_path / "absent.json"))
    assert errs == [] and "skipped" in report

    # the repo's own checked-in baseline passes the ratchet
    report, errs = pg.check_elastic_baseline()
    assert errs == [], errs
    assert report["world_sequence"] == pg.ELASTIC_WORLD_SEQUENCE
    assert report["steps_lost"] == 0 and report["steps_double_applied"] == 0
    assert report["restore_loss_bitwise_equal"] is True


@pytest.mark.slow
def test_bench_serving_longctx_cpu_acceptance(tmp_path):
    """The long-context tiering workload end to end on CPU: one payload
    whose capacity and swap-accounting fields are internally consistent,
    accepted by perf_gate dry-run shape validation."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                      "bench_serving.py"),
         "--long-context", "--seed", "3"],
        capture_output=True, text=True, cwd=REPO_ROOT, env=env, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    payloads = [json.loads(ln) for ln in r.stdout.splitlines()
                if ln.startswith("{")]
    assert len(payloads) == 1
    doc = payloads[0]
    assert doc["metric"] == "serving_longctx_concurrent_seqs_per_chip"
    assert doc["value"] > 0
    ex = doc["extra"]
    assert ex["capacity_multiplier"] >= 2.0
    assert ex["swapped_out"] == ex["swapped_in"] + ex["swap_dropped"] + \
        ex["resident_host_blocks"]
    assert ex["swapped_out"] >= 1 and ex["swapped_in"] >= 1
    assert ex["swap_outs_live"] == 0
    assert 0 <= ex["host_kv_occupancy"] <= 1
    assert ex["prefill_reduction"] > 0
    assert 0 < ex["ttft_p50_s"] <= ex["ttft_p99_s"]
    p = tmp_path / "longctx.json"
    p.write_text(json.dumps(doc))
    r = _run([PERF_GATE, "--baseline", str(p), "--dry-run"])
    assert r.returncode == 0, (r.stdout, r.stderr)
