"""Serving-path observability (PR 6): per-request lifecycle tracing,
TTFT/TPOT percentiles, KV-cache & scheduler gauges.

Covers the fixed-bucket histogram primitive, an end-to-end CPU
SplitFuseScheduler run (request lanes in the Chrome trace, finite ordered
percentiles, nonzero KV-occupancy gauge), the preemption/resume counters
under a deliberately tight KV budget, the replica-skew gauge, and the
disabled-noop guarantee for every new hook: zero clock reads, zero
allocations in the telemetry core, zero state mutation per scheduler step.
"""

import json
import time

import numpy as np
import pytest

import jax

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import core as telemetry_core
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")
    yield
    telemetry.close()
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")


@pytest.fixture(scope="module")
def served():
    cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
    model = LlamaForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 8)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    return cfg, model, params


def make_engine(cfg, model, params, num_kv_blocks=64, max_tokens=16):
    return InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": max_tokens,
                          "max_context": 128,
                          "num_kv_blocks": num_kv_blocks},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})


# ---------------------------------------------------------------------------
# histogram primitive
# ---------------------------------------------------------------------------

def test_hist_percentiles_ordered_and_clamped():
    telemetry.configure(enabled=True)
    rng = np.random.default_rng(0)
    vals = rng.lognormal(-3.0, 1.0, 4000)
    for v in vals:
        telemetry.record_hist("serving/ttft_s", float(v))
    p50, p95, p99 = telemetry.hist_percentiles("serving/ttft_s")
    assert p50 <= p95 <= p99
    assert vals.min() <= p50 <= vals.max()
    assert vals.min() <= p99 <= vals.max()
    # log2 buckets: each estimate within one bucket (2x) of the true value
    true50, true99 = np.quantile(vals, [0.5, 0.99])
    assert true50 / 2 <= p50 <= true50 * 2
    assert true99 / 2 <= p99 <= true99 * 2


def test_hist_single_value_exact():
    telemetry.configure(enabled=True)
    telemetry.record_hist("h", 0.005)
    assert telemetry.hist_percentiles("h") == (0.005, 0.005, 0.005)
    assert telemetry.hist_percentiles("missing") is None


def test_hist_in_summary_and_schema(tmp_path):
    telemetry.configure(enabled=True)
    for v in (0.001, 0.002, 0.01):
        telemetry.record_hist("serving/ttft_s", v)
    telemetry.serving_event("submitted")
    telemetry.serving_gauge("serving/running", 2)
    s = telemetry.summary()
    h = s["serving"]["histograms"]["serving/ttft_s"]
    assert h["count"] == 3 and h["min_s"] == 0.001 and h["max_s"] == 0.01
    assert h["p50_s"] <= h["p95_s"] <= h["p99_s"]
    assert s["serving"]["requests"]["submitted"] == 1
    assert s["serving"]["gauges"]["serving/running"] == {"last": 2, "peak": 2}
    jsonschema = pytest.importorskip("jsonschema")
    import os
    schema_path = os.path.join(
        os.path.dirname(telemetry_core.__file__), "summary.schema.json")
    with open(schema_path) as f:
        jsonschema.validate(s, json.load(f))


# ---------------------------------------------------------------------------
# end-to-end serving stream
# ---------------------------------------------------------------------------

def test_serving_stream_end_to_end(served, tmp_path):
    """A real CPU SplitFuse run: request lanes land in the merged Chrome
    trace, TTFT/TPOT percentiles are finite and ordered, and the
    KV-occupancy gauge saw nonzero occupancy while decoding."""
    cfg, model, params = served
    tr = tmp_path / "trace.json"
    telemetry.configure(enabled=True, chrome_trace_path=str(tr))
    engine = make_engine(cfg, model, params)
    sched = SplitFuseScheduler(engine, token_budget=16)
    rng = np.random.default_rng(3)
    for uid in range(3):
        sched.submit(uid, rng.integers(0, cfg.vocab_size, 20).astype(np.int32),
                     max_new_tokens=4)
    out = sched.run_to_completion()
    assert all(len(out[u]) == 4 for u in range(3))

    s = telemetry.summary()
    srv = s["serving"]
    assert srv["requests"]["submitted"] == 3
    assert srv["requests"]["finished"] == 3
    ttft = srv["histograms"]["serving/ttft_s"]
    tpot = srv["histograms"]["serving/tpot_s"]
    assert ttft["count"] == 3
    assert tpot["count"] == 3 * 3  # 4 tokens -> 3 inter-token gaps each
    for h in (ttft, tpot, srv["histograms"]["serving/queue_wait_s"],
              srv["histograms"]["serving/e2e_s"]):
        assert np.isfinite([h["p50_s"], h["p99_s"]]).all()
        assert 0 < h["p50_s"] <= h["p99_s"]
    # the last flush empties the pool, so peak (not last) proves decoding
    # actually held blocks
    assert srv["gauges"]["serving/kv_occupancy"]["peak"] > 0
    assert srv["gauges"]["serving/running"]["peak"] >= 1
    assert srv["gauges"]["serving/token_budget_util"]["peak"] > 0

    path = telemetry.export_chrome_trace()
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    lanes = {e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "thread_name"
             and e["args"]["name"].startswith("request/")}
    assert lanes == {"request/0", "request/1", "request/2"}
    phases = {e["name"] for e in events if e["name"].startswith("req/")}
    assert {"req/submit", "req/queued", "req/prefill", "req/decode",
            "req/finish"} <= phases
    # request lanes are synthetic tids, disjoint from real-thread lanes
    lane_tids = {e["tid"] for e in events if e["name"].startswith("req/")}
    assert all(t >= 0x10000 for t in lane_tids)


def test_preemption_and_resume_counters(served):
    """10 blocks x 8 tokens with two 44+6-token requests deadlocks the pool
    (see test_scheduler_preempts_under_kv_pressure); the host-swap preemption
    that breaks it must show up in the serving counters."""
    cfg, model, params = served
    telemetry.configure(enabled=True)
    engine = make_engine(cfg, model, params, num_kv_blocks=10)
    sched = SplitFuseScheduler(engine, token_budget=16)
    rng = np.random.default_rng(7)
    for uid in range(2):
        sched.submit(uid, rng.integers(0, cfg.vocab_size, 44).astype(np.int32),
                     max_new_tokens=6)
    out = sched.run_to_completion()
    assert all(len(out[u]) == 6 for u in range(2))
    srv = telemetry.summary()["serving"]
    assert srv["requests"]["preempted"] >= 1
    assert srv["requests"]["resumed"] >= 1
    assert srv["gauges"]["serving/preempted"]["peak"] >= 1
    # fragmentation gauge exists and stays in [0, 1]
    frag = srv["gauges"]["serving/kv_fragmentation"]
    assert 0.0 <= frag["peak"] <= 1.0


def test_kv_stats_pure_read(served):
    """``kv_stats`` never records (safe to poll anywhere);
    ``sample_kv_stats`` is the recording variant — the PR 4 sample_memory
    pattern."""
    cfg, model, params = served
    engine = make_engine(cfg, model, params)
    stats = engine._state.kv_stats()
    assert stats["total_blocks"] == 64 and stats["free_blocks"] == 64
    assert stats["occupancy"] == 0.0 and stats["fragmentation"] == 0.0
    telemetry.configure(enabled=True)
    engine._state.kv_stats()  # pure read: no gauge recorded
    assert "serving/kv_occupancy" not in telemetry.summary()["serving"]["gauges"]
    engine._state.sample_kv_stats()
    assert "serving/kv_occupancy" in telemetry.summary()["serving"]["gauges"]


def test_max_context_eviction_records_terminal_latency(served, tmp_path):
    """A request retired at max_context never "finishes" — the eviction IS
    its terminal event, so it must record ``serving/e2e_s`` and an evict
    lane phase or replay percentiles silently drop exactly the
    worst-latency requests."""
    cfg, model, params = served
    tr = tmp_path / "trace.json"
    telemetry.configure(enabled=True, chrome_trace_path=str(tr))
    engine = InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 2,
                          "max_ragged_batch_size": 16,
                          "max_context": 16, "num_kv_blocks": 8},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})
    sched = SplitFuseScheduler(engine)
    rng = np.random.default_rng(9)
    sched.submit(0, rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                 max_new_tokens=10)  # 12 + 10 cannot fit 16: evicted at 4
    out = sched.run_to_completion()
    assert 1 <= len(out[0]) <= 4
    srv = telemetry.summary()["serving"]
    assert srv["requests"]["evicted"] == 1
    assert srv["requests"].get("finished", 0) == 0
    e2e = srv["histograms"]["serving/e2e_s"]
    assert e2e["count"] == 1 and np.isfinite(e2e["p50_s"])
    path = telemetry.export_chrome_trace()
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    assert any(e["name"] == "req/evict" for e in events), \
        "eviction must land in the request lane as the terminal phase"


# ---------------------------------------------------------------------------
# disabled-noop guarantee for the serving hooks
# ---------------------------------------------------------------------------

def test_disabled_serving_hooks_zero_overhead(served, monkeypatch):
    """Telemetry disabled, a full scheduler run reads the clock twice per
    REQUEST (``submit`` and its admission, for ``serving/admit``'s
    ``waited_us``) and never per round (scheduler._now patched to count),
    keeps ZERO allocations inside the telemetry core, and leaves the
    telemetry serving state untouched. With the
    ``prefix_caching`` knob off (the default) the same run must also do zero
    prefix-cache work — every ``PrefixCache`` method is patched to raise."""
    import tracemalloc
    from deepspeed_tpu.inference.v2 import scheduler as sched_mod
    from deepspeed_tpu.inference.v2.ragged import prefix_cache as pc_mod

    cfg, model, params = served
    assert not telemetry.enabled()

    def _cache_boom(*a, **kw):
        raise AssertionError(
            "prefix_caching off must mean zero hashing/refcount work")
    for name in ("__init__", "chain_digest", "lookup_chain", "acquire_chain",
                 "insert", "park_if_cached", "evict"):
        monkeypatch.setattr(pc_mod.PrefixCache, name, _cache_boom)

    engine = make_engine(cfg, model, params)
    assert engine._state.prefix_cache is None
    assert engine.prefix_caching is False
    sched = SplitFuseScheduler(engine, token_budget=16)
    assert sched._prefix_caching is False

    reads = []

    def _counted():
        reads.append(1)
        return time.perf_counter()
    monkeypatch.setattr(sched_mod, "_now", _counted)

    rng = np.random.default_rng(5)
    sched.submit(0, rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                 max_new_tokens=2)
    sched.step()  # warm the jit caches outside the traced window

    sched.submit(1, rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                 max_new_tokens=40)
    tracemalloc.start()
    snap0 = tracemalloc.take_snapshot()
    while sched.has_work:
        sched.step()
    snap1 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    core_filter = [tracemalloc.Filter(True, telemetry_core.__file__)]
    grown = [st for st in
             snap1.filter_traces(core_filter).compare_to(
                 snap0.filter_traces(core_filter), "lineno")
             if st.size_diff > 0]
    # A span's attribute dict is freed when the span ends, into the
    # interpreter's free list; whoever asks for a dict next gets that block,
    # and tracemalloc keeps the first traceback. So a few KiB stay
    # "allocated in core.py" however long the run is. What must not happen
    # is growth with the rounds: 40 rounds of retained spans would hold
    # well over the bound.
    kept = sum(st.size_diff for st in grown)
    assert kept < 16 * 1024, \
        f"telemetry core kept {kept} bytes when disabled: {grown}"
    assert sched.rounds >= 40, "the window holds 40 rounds"
    assert len(reads) == 2 * 2, \
        f"clock reads are per request, not per round: {len(reads)}"

    tm = telemetry.get_telemetry()
    assert tm.hist_stats == {}
    assert tm.serving_counters == {}
    assert tm.serving_gauges == {}
    assert tm._request_lanes == {}
    assert telemetry.summary() == {"enabled": False}


def test_disabled_swap_hooks_zero_clock_reads(served, monkeypatch):
    """The KV host-tier swap timers must be free when telemetry is off: a
    workload that spills AND restores through the host tier performs zero
    clock reads in kv_cache (``kv_cache._now`` patched to raise) and leaves
    the swap histograms unrecorded."""
    from deepspeed_tpu.inference.v2.ragged import kv_cache as kvc_mod

    cfg, model, params = served
    assert not telemetry.enabled()

    def _boom():
        raise AssertionError(
            "disabled swap path must not read the clock")
    monkeypatch.setattr(kvc_mod, "_now", _boom)

    engine = InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 16,
                          "max_context": 128, "num_kv_blocks": 12,
                          "host_kv_blocks": 16},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"},
        "prefix_caching": True})
    sched = SplitFuseScheduler(engine, token_budget=16)
    rng = np.random.default_rng(21)
    warm = rng.integers(0, cfg.vocab_size, 40).astype(np.int32)
    sched.submit(0, warm, max_new_tokens=2)
    sched.run_to_completion()   # parks warm's full blocks
    sched.submit(1, rng.integers(0, cfg.vocab_size, 60).astype(np.int32),
                 max_new_tokens=2)
    sched.run_to_completion()   # pressure: parked blocks spill to host
    assert engine.kv_stats()["kv_spilled"] >= 1
    sched.submit(2, np.concatenate(
        [warm, rng.integers(0, cfg.vocab_size, 6).astype(np.int32)]),
        max_new_tokens=2)
    sched.run_to_completion()   # shared prefix restores from the host tier
    assert engine.kv_stats()["kv_restored"] >= 1
    assert telemetry.summary() == {"enabled": False}


def test_swap_hists_recorded_when_enabled(served):
    """The enabled counterpart: the same spill/restore workload lands
    ``serving/kv_swap_out_s`` and ``serving/kv_swap_in_s`` samples and the
    ``serving/host_kv_blocks`` gauge."""
    cfg, model, params = served
    telemetry.configure(enabled=True)
    engine = InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 16,
                          "max_context": 128, "num_kv_blocks": 12,
                          "host_kv_blocks": 16},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"},
        "prefix_caching": True})
    sched = SplitFuseScheduler(engine, token_budget=16)
    rng = np.random.default_rng(21)
    warm = rng.integers(0, cfg.vocab_size, 40).astype(np.int32)
    sched.submit(0, warm, max_new_tokens=2)
    sched.run_to_completion()
    sched.submit(1, rng.integers(0, cfg.vocab_size, 60).astype(np.int32),
                 max_new_tokens=2)
    sched.run_to_completion()
    sched.submit(2, np.concatenate(
        [warm, rng.integers(0, cfg.vocab_size, 6).astype(np.int32)]),
        max_new_tokens=2)
    sched.run_to_completion()
    srv = telemetry.summary()["serving"]
    out_h = srv["histograms"]["serving/kv_swap_out_s"]
    in_h = srv["histograms"]["serving/kv_swap_in_s"]
    assert out_h["count"] >= 1 and np.isfinite(out_h["p50_s"])
    assert in_h["count"] >= 1 and np.isfinite(in_h["p50_s"])
    assert srv["gauges"]["serving/host_kv_blocks"]["peak"] >= 1


# ---------------------------------------------------------------------------
# replica skew gauge
# ---------------------------------------------------------------------------

def test_replica_group_load_report(served):
    from deepspeed_tpu.inference.v2.replica_group import ReplicaGroup
    cfg, model, params = served
    telemetry.configure(enabled=True)
    group = ReplicaGroup(model, params, replica_num=2, tp_size=1,
                         engine_config={
                             "state_manager": {"max_ragged_sequence_count": 4,
                                               "max_ragged_batch_size": 16,
                                               "max_context": 128,
                                               "num_kv_blocks": 64},
                             "kv_cache": {"block_size": 8,
                                          "cache_dtype": "fp32"}},
                         token_budget=16)
    rng = np.random.default_rng(11)
    for uid in range(4):
        group.submit(uid, rng.integers(0, cfg.vocab_size, 10).astype(np.int32),
                     max_new_tokens=2)
    rep = group.load_report()
    assert [p["assigned"] for p in rep["replicas"]] == [2, 2]
    assert rep["active_skew"] == 0.0  # round-robin with even count
    assert "serving/replica_skew" in telemetry.summary()["serving"]["gauges"]
    out = group.run_to_completion()
    assert len(out) == 4


# ---------------------------------------------------------------------------
# speculative decode hooks
# ---------------------------------------------------------------------------

def _spec_engine(model, params, num_kv_blocks=64):
    return InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 16,
                          "max_context": 128,
                          "num_kv_blocks": num_kv_blocks},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"},
        "speculative": {"enabled": True, "max_draft_tokens": 4}})


def _template_prompt(cfg, seed, reps=10):
    rng = np.random.default_rng(seed)
    return np.tile(rng.integers(0, cfg.vocab_size, 4), reps).astype(np.int32)


def test_disabled_spec_hooks_zero_overhead(served, monkeypatch):
    """Telemetry disabled, a SPECULATING run (drafts composed, verify
    chunks dispatched, accept walks + rollbacks retired) reads the
    scheduler's clock twice per request and never per round, and keeps zero
    allocations inside the telemetry core — the accept-rate EWMA and the
    always-on draft counters must not ride the telemetry path."""
    import tracemalloc
    from deepspeed_tpu.inference.v2 import scheduler as sched_mod

    cfg, model, params = served
    assert not telemetry.enabled()
    engine = _spec_engine(model, params)
    sched = SplitFuseScheduler(engine, token_budget=16)

    reads = []

    def _counted():
        reads.append(1)
        return time.perf_counter()
    monkeypatch.setattr(sched_mod, "_now", _counted)

    sched.submit(0, _template_prompt(cfg, 5), max_new_tokens=6)
    sched.step()  # warm the prefill jit caches outside the window

    sched.submit(1, _template_prompt(cfg, 5) + 1, max_new_tokens=40)
    tracemalloc.start()
    snap0 = tracemalloc.take_snapshot()
    while sched.has_work:
        sched.step()
    snap1 = tracemalloc.take_snapshot()
    tracemalloc.stop()
    core_filter = [tracemalloc.Filter(True, telemetry_core.__file__)]
    grown = [st for st in
             snap1.filter_traces(core_filter).compare_to(
                 snap0.filter_traces(core_filter), "lineno")
             if st.size_diff > 0]
    # A span's attribute dict is freed when the span ends, into the
    # interpreter's free list; whoever asks for a dict next gets that block,
    # and tracemalloc keeps the first traceback. So a few KiB stay
    # "allocated in core.py" however long the run is. What must not happen
    # is growth with the rounds: 40 rounds of retained spans would hold
    # well over the bound.
    kept = sum(st.size_diff for st in grown)
    assert kept < 16 * 1024, \
        f"telemetry core kept {kept} bytes when disabled: {grown}"
    assert len(reads) == 2 * 2, \
        f"clock reads are per request, not per round: {len(reads)}"
    # the router's load signal stays live with telemetry off
    assert sched.speculated_tokens > 0
    assert sched.tokens_per_round() >= 1.0
    assert telemetry.summary() == {"enabled": False}


def test_spec_stream_lands_gauges_events_and_phase(served, tmp_path):
    """Enabled counterpart: a speculating run lands the
    ``speculated_tokens``/``rejected_tokens`` counters, the
    ``serving/accept_rate`` and ``serving/verify_batch_occupancy`` gauges,
    a ``req/speculate`` phase in the request lanes, and the summary still
    validates against summary.schema.json."""
    cfg, model, params = served
    tr = tmp_path / "trace.json"
    telemetry.configure(enabled=True, chrome_trace_path=str(tr))
    engine = _spec_engine(model, params)
    sched = SplitFuseScheduler(engine, token_budget=16)
    sched.submit(0, _template_prompt(cfg, 5), max_new_tokens=6)
    sched.submit(1, _template_prompt(cfg, 5) + 1, max_new_tokens=8)
    out = sched.run_to_completion()
    assert len(out[0]) == 6 and len(out[1]) == 8
    assert sched.accepted_tokens > 0, "template workload must accept drafts"

    s = telemetry.summary()
    srv = s["serving"]
    assert srv["requests"]["speculated_tokens"] >= 1
    assert srv["requests"]["speculated_tokens"] == sched.speculated_tokens
    assert srv["requests"].get("rejected_tokens", 0) == sched.rejected_tokens
    acc = srv["gauges"]["serving/accept_rate"]
    assert 0.0 <= acc["last"] <= 1.0 and 0.0 <= acc["peak"] <= 1.0
    occ = srv["gauges"]["serving/verify_batch_occupancy"]
    assert 0.0 < occ["peak"] <= 1.0
    jsonschema = pytest.importorskip("jsonschema")
    import os
    schema_path = os.path.join(
        os.path.dirname(telemetry_core.__file__), "summary.schema.json")
    with open(schema_path) as f:
        jsonschema.validate(s, json.load(f))

    path = telemetry.export_chrome_trace()
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    spec_evts = [e for e in events if e["name"] == "req/speculate"]
    assert spec_evts, "verify rounds must land as a speculate lane phase"
    assert all(e["args"]["tokens"] >= 2 for e in spec_evts), \
        "a speculate phase is by definition a multi-token decode chunk"
    assert all(t >= 0x10000 for t in {e["tid"] for e in spec_evts})
