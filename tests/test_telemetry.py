"""Unified telemetry pipeline tests (deepspeed_tpu/telemetry/).

Covers the disabled no-op fast path, span/record/counter mechanics, the
Chrome-trace + JSONL exporters, schema validation of ``summary()``, the
kernel-dispatch reason codes, the closed-form Pallas FLOP formulas, and the
acceptance path: one train-loop run on the 8-device CPU mesh with telemetry
on produces a Chrome trace with fwd/bwd/step + collective spans, a JSONL
stream with nonzero comm bytes and a ``sharded`` dispatch outcome, and the
log_summary table.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deepspeed_tpu import telemetry

SCHEMA_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "deepspeed_tpu", "telemetry",
    "summary.schema.json")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Each test sees a fresh, DISABLED global pipeline with no sinks."""
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")
    yield
    telemetry.close()
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")


# ---------------------------------------------------------------------------
# disabled fast path
# ---------------------------------------------------------------------------

def test_disabled_noop_fast_path(tmp_path, monkeypatch):
    """Disabled, every entry point is a constant-time no-op and a span is
    only its profiler annotation: it holds no reference to the pipeline and
    records nothing, no jax sync runs, and no file is touched even when sink
    paths are configured."""
    jl = tmp_path / "m.jsonl"
    telemetry.configure(jsonl_path=str(jl), chrome_trace_path="")
    assert not telemetry.enabled()

    def _boom(*a, **k):
        raise AssertionError("block_until_ready must not run when disabled")
    monkeypatch.setattr(jax, "block_until_ready", _boom)

    sp = telemetry.span("fwd", step=1)
    assert sp._tm is None, "a disabled span holds no reference to the pipeline"
    assert telemetry.span("bwd")._tm is None
    with telemetry.span("scoped"):
        pass
    assert not sp.end(token=jnp.ones(4)), "a disabled span measures nothing"
    tm = telemetry.get_telemetry()
    assert tm.span_stats == {} and tm.trace_events == [], \
        "disabled spans record nothing"

    telemetry.record("loss", 1.0, step=1)
    telemetry.count("steps")
    telemetry.record_comm("all_reduce", 1 << 20, 0.001, axis="dp")
    telemetry.record_dispatch("flash_mha", "sharded", "data")
    telemetry.record_compile("prog", 1.0)

    # serving-stream entry points (PR 6) ride the same guarantee
    telemetry.record_hist("serving/ttft_s", 0.05)
    assert telemetry.hist_percentiles("serving/ttft_s") is None
    telemetry.serving_event("submitted")
    telemetry.serving_gauge("serving/running", 3)
    telemetry.record_request_phase(0, "decode", 0.0, 0.01, tokens=1)

    # moe-stream entry points (ISSUE 15) ride the same guarantee: no
    # iteration over exp_counts, no gauge state, no sink writes
    telemetry.moe_gauge("moe/expert_load_max_frac", 0.5)
    telemetry.record_moe_step([4, 4, 8, 0], 16, dropped=2,
                              a2a_wire_bytes=1 << 20)
    assert telemetry.get_telemetry().moe_gauges == {}

    # the memory/ledger hooks must be no-ops too — zero device reads
    from deepspeed_tpu.telemetry.core import Telemetry

    def _no_read(*a, **k):
        raise AssertionError("memory_stats must not be read when disabled")
    monkeypatch.setattr(Telemetry, "_read_memory_stats",
                        staticmethod(_no_read))
    assert telemetry.record_memory("step", step=1) is None
    assert telemetry.ledger_step(step=1) is None
    telemetry.ledger_add("stall", 1.0)
    assert telemetry.maybe_oom_postmortem(
        RuntimeError("RESOURCE_EXHAUSTED: out of memory")) is None
    assert telemetry.oom_postmortem(error="x") is None

    # overlap attachment is a no-op too: no validation, no record, no state
    assert telemetry.attach_overlap({"not": "even a valid report"}) is None
    assert telemetry.get_telemetry().overlap_report is None

    # the flight recorder is the ONE always-on hook: Fault/* mirrors into
    # its bounded ring even here, without waking the telemetry pipeline
    from deepspeed_tpu.telemetry import flightrec
    ring = flightrec.get_recorder()
    base = ring.total_count
    telemetry.record("Fault/slice.lost", 1, kind="counter")
    assert ring.total_count == base + 1
    assert ring.events()[-1]["name"] == "Fault/slice.lost"
    assert flightrec.flush_bundle("stall") is None, \
        "no destination configured -> no bundle litter"

    assert not jl.exists(), "disabled record must never open the jsonl sink"
    assert telemetry.summary() == {"enabled": False}
    assert telemetry.monitor_events(1) == []
    assert telemetry.format_summary() == "telemetry disabled"


def test_configure_registers_atexit_once(monkeypatch, tmp_path):
    """configure()/reset() cycles must never stack atexit export hooks —
    each extra hook would re-export (and with multiple instances, clobber)
    the trace file."""
    import atexit
    from deepspeed_tpu.telemetry import core

    calls = []
    monkeypatch.setattr(atexit, "register", lambda fn: calls.append(fn))
    monkeypatch.setattr(core, "_ATEXIT_REGISTERED", False)
    monkeypatch.setattr(core, "_ATEXIT_INSTANCES", [])

    tr = tmp_path / "trace.json"
    for _ in range(5):  # repeated init across reset cycles
        telemetry.configure(enabled=True, chrome_trace_path=str(tr))
        telemetry.reset()
    assert len(calls) == 1, "exactly one atexit hook across reconfigures"
    # even a SECOND instance must not add a second hook
    other = core.Telemetry()
    other.configure(enabled=True, chrome_trace_path=str(tr))
    assert len(calls) == 1
    assert len(core._ATEXIT_INSTANCES) == 2
    # the single hook exports every registered instance without raising
    with telemetry.span("fwd"):
        pass
    core._atexit_export_all()
    assert tr.exists()


# ---------------------------------------------------------------------------
# spans / metrics / counters
# ---------------------------------------------------------------------------

def test_span_records_once_and_never_waits(monkeypatch):
    def _boom(*a, **k):
        raise AssertionError("a span never waits for the device")
    monkeypatch.setattr(jax, "block_until_ready", _boom)
    telemetry.configure(enabled=True)
    with telemetry.span("fwd", step=3) as sp:
        pending = jnp.ones((4,)) * 2
    sp.end()  # second end is a no-op
    s = telemetry.summary()
    assert s["spans"]["fwd"]["count"] == 1
    assert s["spans"]["fwd"]["total_s"] >= 0
    # explicit begin/end pair (the engine idiom for cross-method scopes)
    sp2 = telemetry.span_begin("step")
    dt = sp2.end(token=pending)  # accepted for old callers, ignored
    assert dt >= 0
    assert telemetry.summary()["spans"]["step"]["count"] == 1


def test_counters_accumulate_per_tag():
    telemetry.configure(enabled=True)
    telemetry.count("retries", kernel="a")
    telemetry.count("retries", n=2, kernel="a")
    telemetry.count("retries", kernel="b")
    telemetry.count("plain")
    c = telemetry.summary()["counters"]
    assert c["retries"]["kernel=a"] == 3
    assert c["retries"]["kernel=b"] == 1
    assert c["plain"]["_"] == 1


def test_record_comm_bandwidth_math():
    """record_comm must agree with calc_bw_log's ring factors."""
    telemetry.configure(enabled=True)
    n = max(jax.device_count(), 1)
    telemetry.record_comm("all_reduce", 10**9, 1.0, axis="dp")
    st = telemetry.summary()["comm"]["ops"]["all_reduce"]["dp"]
    assert st["bytes"] == 10**9
    assert st["algbw_gbs"] == pytest.approx(1.0)
    assert st["busbw_gbs"] == pytest.approx(2 * (n - 1) / n)
    # tuple axes key under "/" join; totals accumulate across ops
    telemetry.record_comm("all_gather", 500, 0.001, axis=("dp", "tp"))
    s = telemetry.summary()["comm"]
    assert s["ops"]["all_gather"]["dp/tp"]["count"] == 1
    assert s["total_bytes"] == 10**9 + 500


def test_jsonl_exporter_lines(tmp_path):
    jl = tmp_path / "metrics.jsonl"
    telemetry.configure(enabled=True, jsonl_path=str(jl))
    telemetry.record("loss", 2.5, step=1)
    with telemetry.span("fwd"):
        pass
    telemetry.record_dispatch("flash_mha", "fallback", "no_mesh")
    telemetry.close()
    lines = [json.loads(ln) for ln in jl.read_text().splitlines()]
    names = [ln["name"] for ln in lines]
    assert "loss" in names and "fwd" in names and "dispatch/flash_mha" in names
    for ln in lines:
        assert "ts" in ln and "kind" in ln and "value" in ln


def test_chrome_trace_export(tmp_path):
    tr = tmp_path / "trace.json"
    telemetry.configure(enabled=True, chrome_trace_path=str(tr))
    with telemetry.span("fwd", step=1):
        pass
    telemetry.record_comm("all_reduce", 4096, 0.002, axis="dp")
    path = telemetry.export_chrome_trace()
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    assert by_name["fwd"]["ph"] == "X" and by_name["fwd"]["cat"] == "span"
    assert by_name["fwd"]["args"] == {"step": 1}
    comm = by_name["comm:all_reduce"]
    assert comm["cat"] == "comm" and comm["args"]["bytes"] == 4096
    assert comm["dur"] == pytest.approx(2000, rel=0.01)  # 2ms in µs
    # one process_name metadata event labels the host track for trace_merge
    metas = [e for e in evs if e["ph"] == "M"]
    assert len(metas) == 1 and metas[0]["name"] == "process_name"
    for e in evs:
        if e["ph"] == "M":
            continue
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)


def test_summary_schema_validation():
    """The checked-in JSON schema accepts both the disabled stub and a fully
    populated summary (the exact object bench.py / aot_tpu_check.py embed)."""
    jsonschema = pytest.importorskip("jsonschema")
    schema = json.load(open(SCHEMA_PATH))
    jsonschema.validate(telemetry.summary(), schema)  # {"enabled": False}
    telemetry.configure(enabled=True)
    with telemetry.span("fwd"):
        pass
    telemetry.record_comm("all_reduce", 4096, 0.001, axis="dp")
    telemetry.record_dispatch("flash_mha", "sharded", "data", mesh_size=8)
    telemetry.record_dispatch("flash_mha", "veto", "accept_veto", mesh_size=8)
    telemetry.record_compile("p1", 2.0, topology="v5e:2x2", cache="miss")
    telemetry.record_compile("p2", 0.1, topology="v5e:2x2", cache="hit")
    telemetry.count("steps", phase="train")
    telemetry.record_moe_step([4, 4, 8, 0], 16, dropped=0,
                              a2a_wire_bytes=1 << 20)
    s = telemetry.summary()
    jsonschema.validate(s, schema)
    assert set(s["moe"]["gauges"]) == {"moe/expert_load_max_frac",
                                       "moe/drop_rate", "moe/a2a_wire_bytes"}
    assert s["compile"]["cache_hits"] == 1 and s["compile"]["cache_misses"] == 1
    # a malformed outcome must be rejected — the schema actually constrains
    bad = json.loads(json.dumps(s))
    bad["dispatch"]["flash_mha"]["exploded"] = bad["dispatch"]["flash_mha"].pop("sharded")
    with pytest.raises(jsonschema.ValidationError):
        jsonschema.validate(bad, schema)


# ---------------------------------------------------------------------------
# moe stream (ISSUE 15)
# ---------------------------------------------------------------------------

def test_moe_gauge_last_and_peak(tmp_path):
    jl = tmp_path / "m.jsonl"
    telemetry.configure(enabled=True, jsonl_path=str(jl))
    telemetry.moe_gauge("moe/expert_load_max_frac", 0.5)
    telemetry.moe_gauge("moe/expert_load_max_frac", 0.25, step=2)
    s = telemetry.summary()
    g = s["moe"]["gauges"]["moe/expert_load_max_frac"]
    assert g == {"last": 0.25, "peak": 0.5}
    # Chrome counter track + JSONL line per sample
    events = [e for e in telemetry.get_telemetry().trace_events
              if e.get("cat") == "moe"]
    assert len(events) == 2 and all(e["ph"] == "C" for e in events)
    telemetry.close()
    lines = [json.loads(ln) for ln in jl.read_text().splitlines()]
    moe_lines = [ln for ln in lines
                 if ln.get("name") == "moe/expert_load_max_frac"]
    assert len(moe_lines) == 2
    assert moe_lines[1]["tags"] == {"step": 2}


def test_record_moe_step_standard_gauges():
    telemetry.configure(enabled=True)
    # 16 (token, choice) assignments, 2 of which overflowed capacity
    telemetry.record_moe_step([4, 4, 8, 0], 16, dropped=2,
                              a2a_wire_bytes=2048)
    g = telemetry.summary()["moe"]["gauges"]
    assert g["moe/expert_load_max_frac"]["last"] == pytest.approx(0.5)
    assert g["moe/drop_rate"]["last"] == pytest.approx(2 / 16)
    assert g["moe/a2a_wire_bytes"]["last"] == 2048.0
    # dropless step: drop_rate pins to 0, wire gauge optional
    telemetry.record_moe_step([8, 8, 0, 0], 16)
    g = telemetry.summary()["moe"]["gauges"]
    assert g["moe/drop_rate"]["last"] == 0.0
    assert g["moe/a2a_wire_bytes"]["last"] == 2048.0  # unchanged


def test_monitor_events_bridge():
    telemetry.configure(enabled=True)
    with telemetry.span("fwd"):
        pass
    telemetry.record_comm("all_reduce", 4096, 0.001, axis="dp")
    telemetry.record_dispatch("flash_mha", "sharded", "data")
    events = telemetry.monitor_events(64)
    names = [e[0] for e in events]
    assert "Telemetry/Span/fwd_mean_ms" in names
    assert "Telemetry/Comm/total_bytes" in names
    assert "Telemetry/Dispatch/flash_mha/sharded" in names
    assert all(e[2] == 64 for e in events)


def test_telemetry_config_plumbing():
    """The ``telemetry`` config section parses into TelemetryConfig."""
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    cfg = DeepSpeedConfig({
        "train_batch_size": 8,
        "telemetry": {"enabled": True, "jsonl_path": "/tmp/x.jsonl",
                      "memory": False}})
    tc = cfg.telemetry_config
    assert tc.enabled and not tc.memory
    assert tc.jsonl_path == "/tmp/x.jsonl"
    # defaults: fully off
    dflt = DeepSpeedConfig({"train_batch_size": 8}).telemetry_config
    assert not dflt.enabled and dflt.memory and dflt.monitor
    assert not hasattr(dflt, "sample_sync") and not hasattr(dflt, "jax_annotations")


# ---------------------------------------------------------------------------
# dispatch reason codes (ops/registry.sharded_kernel_call)
# ---------------------------------------------------------------------------

def _dispatch_counts(kernel):
    return telemetry.summary().get("dispatch", {}).get(kernel, {})


def test_dispatch_reason_codes(eight_devices):
    from deepspeed_tpu.ops.registry import sharded_kernel_call
    from deepspeed_tpu.parallel.topology import use_kernel_mesh
    telemetry.configure(enabled=True)

    def double(x):
        return x * 2

    x = jnp.arange(16.0)
    # no mesh active -> fallback/no_mesh
    with use_kernel_mesh(None):
        out = sharded_kernel_call(double, (x,), (("data",),), ("data",),
                                  name="k")
    np.testing.assert_allclose(out, x * 2)
    assert _dispatch_counts("k")["fallback"]["no_mesh"] == 1

    mesh = Mesh(np.array(eight_devices), ("dp",))
    # accept veto
    with use_kernel_mesh(mesh):
        sharded_kernel_call(double, (x,), (("data",),), ("data",),
                            accept=lambda shapes: False, name="k")
    assert _dispatch_counts("k")["veto"]["accept_veto"] == 1
    # sharded over the data axis
    with use_kernel_mesh(mesh):
        out = sharded_kernel_call(double, (x,), (("data",),), ("data",),
                                  name="k")
    np.testing.assert_allclose(out, x * 2)
    assert _dispatch_counts("k")["sharded"]["data"] == 1
    # indivisible dim -> role dropped -> no_live_role
    y = jnp.arange(6.0)
    with use_kernel_mesh(mesh):
        sharded_kernel_call(double, (y,), (("data",),), ("data",), name="k")
    assert _dispatch_counts("k")["fallback"]["no_live_role"] == 1


# ---------------------------------------------------------------------------
# closed-form kernel FLOP formulas (flops profiler)
# ---------------------------------------------------------------------------

def test_kernel_flop_formulas():
    from deepspeed_tpu.profiling.flops_profiler.profiler import (
        KERNEL_FLOPS, kernel_flops, register_kernel_flops)
    # flash attention: QK^T + PV = 4*B*H*Sq*Skv*D; causal halves it
    full = kernel_flops("flash_mha", batch=2, heads=4, q_len=128,
                        kv_len=128, head_dim=64)
    assert full == 4 * 2 * 4 * 128 * 128 * 64
    causal = kernel_flops("flash_mha", batch=2, heads=4, q_len=128,
                          kv_len=128, head_dim=64, causal=True)
    assert causal == full // 2
    assert kernel_flops("paged_mha", num_seqs=3, heads=8, q_len=1,
                        kv_len=512, head_dim=64) == 4 * 3 * 8 * 512 * 64
    # block-sparse: density scales the dense count
    dense = kernel_flops("sparse_mha", batch=1, heads=2, q_len=256,
                         kv_len=256, head_dim=32)
    assert kernel_flops("sparse_mha", batch=1, heads=2, q_len=256,
                        kv_len=256, head_dim=32, density=0.25) == dense // 4
    # MoE grouped GEMM: up+down proj per routed token-copy
    assert kernel_flops("moe_ffn_gmm", tokens=64, d_model=128, d_ff=512,
                        topk=2) == 4 * 64 * 2 * 128 * 512
    assert kernel_flops("quantized_matmul", m=8, n=16, k=32) == 2 * 8 * 16 * 32
    assert set(KERNEL_FLOPS) >= {"flash_mha", "paged_mha", "sparse_mha",
                                 "moe_ffn_gmm", "quantized_matmul"}
    with pytest.raises(KeyError):
        kernel_flops("not_a_kernel")
    register_kernel_flops("custom", lambda m, n: 7 * m * n)
    assert kernel_flops("custom", m=2, n=3) == 42
    del KERNEL_FLOPS["custom"]


# ---------------------------------------------------------------------------
# acceptance: train loop + collective + kernel dispatch, all three artifacts
# ---------------------------------------------------------------------------

def test_train_loop_acceptance(eight_devices, tmp_path):
    """One engine train run on the 8-device CPU mesh with telemetry on:
    (a) Chrome trace with fwd/bwd/step + collective spans, (b) JSONL with
    nonzero comm bytes and a ``sharded`` dispatch outcome, (c) the
    log_summary table."""
    import deepspeed_tpu
    from deepspeed_tpu import comm as dist
    from deepspeed_tpu.parallel.topology import use_kernel_mesh
    from tests.simple_model import SimpleModel, random_batches

    jl = tmp_path / "metrics.jsonl"
    tr = tmp_path / "trace.json"
    model = SimpleModel()
    batch = random_batches(1, 8)[0]
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "telemetry": {"enabled": True, "jsonl_path": str(jl),
                              "chrome_trace_path": str(tr)}})
    assert telemetry.enabled(), "engine config must switch the pipeline on"

    def _loop():
        for b in random_batches(2, 8):
            loss = engine(b)
            engine.backward(loss)
            engine.step()
    _loop()

    # an explicit collective through the comm shim inside jit/shard_map —
    # traced at trace time with bytes from the tracer aval
    mesh = Mesh(np.array(eight_devices), ("dp",))
    f = jax.jit(jax.shard_map(
        lambda x: dist.all_reduce(x, axis_name="dp"),
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False))
    jax.block_until_ready(f(jnp.ones((8, 4), jnp.float32)))

    # a Pallas kernel entry point dispatching ``sharded`` over the mesh
    from deepspeed_tpu.ops.pallas.flash_attention import flash_mha
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (8, 128, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (8, 128, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (8, 128, 2, 64), jnp.float32)
    with use_kernel_mesh(mesh):
        jax.block_until_ready(flash_mha(q, k, v, causal=True, interpret=True))

    # (a) chrome trace: train-phase spans + at least one collective span
    telemetry.export_chrome_trace()
    doc = json.load(open(tr))
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"fwd", "bwd", "step"} <= names, names
    assert any(n.startswith("comm:") for n in names), names

    # (b) jsonl: nonzero comm bytes + a sharded dispatch outcome
    telemetry.close()
    lines = [json.loads(ln) for ln in jl.read_text().splitlines()]
    comm_lines = [ln for ln in lines if ln["name"].startswith("comm/")]
    assert comm_lines and sum(ln["value"] for ln in comm_lines) > 0
    sharded = [ln for ln in lines if ln["name"].startswith("dispatch/")
               and ln["tags"]["outcome"] == "sharded"]
    assert sharded, [ln for ln in lines if ln["name"].startswith("dispatch/")]
    assert sharded[0]["name"] == "dispatch/flash_mha"

    # (c) summary table over all streams
    table = telemetry.log_summary(print_log=False)
    assert "fwd" in table and "Span" in table
    assert "Comm. Op" in table and "Kernel" in table

    # and the aggregate passes the checked-in schema
    jsonschema = pytest.importorskip("jsonschema")
    jsonschema.validate(telemetry.summary(), json.load(open(SCHEMA_PATH)))
    s = telemetry.summary()
    assert s["comm"]["total_bytes"] > 0
    assert "sharded" in s["dispatch"]["flash_mha"]


def test_engine_monitor_gets_telemetry_events(tmp_path):
    """At steps_per_print cadence the engine folds telemetry aggregates into
    the monitor event stream (Telemetry/* rows land in the csv backend)."""
    import deepspeed_tpu
    from tests.simple_model import SimpleModel, random_batches
    model = SimpleModel()
    batch = random_batches(1, 8)[0]
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 8, "steps_per_print": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "telemetry": {"enabled": True},
                "csv_monitor": {"enabled": True, "output_path": str(tmp_path),
                                "job_name": "tele"}})
    for _ in range(2):
        loss = engine(batch)
        engine.backward(loss)
        engine.step()
    files = [f for root, _, fs in os.walk(tmp_path) for f in fs]
    assert any(f.startswith("Telemetry_Span_fwd") for f in files), files


def test_bench_style_payload_embeds_summary_schema(tmp_path):
    """The exact embedding bench.py / aot_tpu_check.py perform: the summary
    object dropped into an artifact validates against the checked-in
    schema after a JSON round-trip."""
    jsonschema = pytest.importorskip("jsonschema")
    telemetry.configure(enabled=True)
    with telemetry.span("fwd"):
        pass
    telemetry.record_compile("llama_tp2xdp2_zero_fwd_bwd", 12.5,
                             topology="v5e:2x2", cache="miss")
    payload = {"metric": "tokens_per_sec", "value": 1.0,
               "extra": {"telemetry": telemetry.summary()}}
    out = tmp_path / "BENCH_test.json"
    out.write_text(json.dumps(payload))
    back = json.loads(out.read_text())
    schema = json.load(open(SCHEMA_PATH))
    jsonschema.validate(back["extra"]["telemetry"], schema)
    assert back["extra"]["telemetry"]["compile"]["programs"][
        "llama_tp2xdp2_zero_fwd_bwd"]["cache"] == "miss"
