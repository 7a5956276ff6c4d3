"""Fleet SLO metrics plane + measured per-op cost store (PR 17).

Covers the SeriesRing fixed-window rollup primitive (property-tested
against a naive reference), the telemetry ``record_series`` /
``series_windows`` facade and its ``summary()["timeseries"]`` section,
per-class SLO attainment arithmetic with burn-rate / error-budget gauges,
the scheduler's end-to-end SLO tagging + cross-replica request flow
events, the disabled-noop guarantee for every new hook, the persisted
per-op profile store (round trip, fallback, env overrides — the
kernel-table matrix), its consultation by ``overlap_schedule`` ahead of
the roofline, per-host SLO/flow merging in ``trace_merge``, and the new
``perf_gate`` validators and ratchets.
"""

import importlib.util
import json
import os
import random
import time

import numpy as np
import pytest

import jax

from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import core as telemetry_core
from deepspeed_tpu.telemetry import profile_store
from deepspeed_tpu.telemetry.timeseries import SeriesRing
from deepspeed_tpu.runtime.zero import overlap_schedule
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERF_GATE = os.path.join(REPO_ROOT, "scripts", "perf_gate.py")
TRACE_MERGE = os.path.join(REPO_ROOT, "scripts", "trace_merge.py")

SLO_CLASSES = {
    "interactive": {"ttft_target_s": 0.5, "tpot_target_s": 0.25,
                    "attainment_target": 0.9},
    "batch": {"ttft_target_s": 60.0, "tpot_target_s": 30.0,
              "attainment_target": 0.9},
}


def _load_script(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch):
    monkeypatch.delenv("DS_TPU_PROFILE_STORE", raising=False)
    monkeypatch.delenv("DS_TPU_PROFILE_STORE_DEVICE", raising=False)
    profile_store.clear_cache()
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")
    yield
    telemetry.close()
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")
    profile_store.clear_cache()


@pytest.fixture(scope="module")
def served():
    cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
    model = LlamaForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 8)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    return cfg, model, params


def make_engine(cfg, model, params, slo_classes=None):
    config = {
        "state_manager": {"max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 16,
                          "max_context": 128,
                          "num_kv_blocks": 64},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}}
    if slo_classes is not None:
        config["slo_classes"] = slo_classes
    return InferenceEngineV2(model, params, config=config)


# ---------------------------------------------------------------------------
# SeriesRing primitive
# ---------------------------------------------------------------------------

class _NaiveSeries:
    """Dict-of-lists reference: identical drop/eviction semantics, none of
    the ring bookkeeping."""

    def __init__(self, window_s, num_windows):
        self.window_s, self.num_windows = window_s, num_windows
        self.values = {}  # window index -> [raw values]
        self.head = None
        self.total_count, self.total_sum = 0, 0.0

    def record(self, ts, v):
        idx = int(ts // self.window_s)
        if self.head is not None and idx <= self.head - self.num_windows:
            return False
        self.total_count += 1
        self.total_sum += v
        if self.head is None or idx > self.head:
            self.head = idx
        self.values.setdefault(idx, []).append(v)
        return True

    def windows(self):
        if self.head is None:
            return []
        tail = self.head - self.num_windows
        out = []
        for idx in sorted(i for i in self.values if i > tail):
            vals = self.values[idx]
            out.append({"index": idx,
                        "count": len(vals), "sum": sum(vals),
                        "min": min(vals), "max": max(vals)})
        return out


def test_series_ring_matches_naive_reference():
    """Random streams (forward jumps past the ring, out-of-order stragglers,
    fractional windows) produce exactly the naive rollup: same accept/drop
    verdict per record, same live windows, same lifetime totals."""
    for seed in range(6):
        rng = random.Random(seed)
        window_s = rng.choice([0.1, 0.5, 1.0, 2.5])
        num_windows = rng.choice([1, 3, 8, 32])
        ring = SeriesRing(window_s=window_s, num_windows=num_windows)
        ref = _NaiveSeries(window_s, num_windows)
        ts = 0.0
        for _ in range(800):
            r = rng.random()
            if r < 0.70:
                ts += rng.random() * window_s          # stay nearby
            elif r < 0.90:
                ts += rng.random() * window_s * num_windows * 2  # big skip
            else:
                ts = max(0.0, ts - rng.random() * window_s * num_windows)
            v = rng.uniform(-10, 10)
            assert ring.record(ts, v) == ref.record(ts, v)
        got, want = ring.windows(), ref.windows()
        assert [w["index"] for w in got] == [w["index"] for w in want]
        for g, w in zip(got, want):
            assert g["count"] == w["count"]
            assert g["sum"] == pytest.approx(w["sum"])
            assert g["min"] == w["min"] and g["max"] == w["max"]
            assert g["mean"] == pytest.approx(w["sum"] / w["count"])
            assert g["start_s"] == pytest.approx(w["index"] * window_s)
        assert ring.total_count == ref.total_count
        assert ring.total_sum == pytest.approx(ref.total_sum)
        assert len(got) <= num_windows


def test_series_ring_eviction_and_lifetime_totals():
    ring = SeriesRing(window_s=1.0, num_windows=4)
    for t in range(10):
        assert ring.record(t + 0.5, 1.0)
    win = ring.windows()
    assert [w["index"] for w in win] == [6, 7, 8, 9]  # ring keeps 4
    assert ring.total_count == 10  # lifetime totals survive eviction
    assert ring.total_sum == 10.0
    # records older than the tail are dropped, totals untouched
    assert not ring.record(2.0, 99.0)
    assert ring.total_count == 10
    # a straggler inside the live range still lands
    assert ring.record(6.1, 3.0)
    assert ring.windows()[0] == {
        "index": 6, "start_s": 6.0, "count": 2, "sum": 4.0,
        "min": 1.0, "max": 3.0, "mean": 2.0}


def test_series_ring_rates_and_validation():
    ring = SeriesRing(window_s=0.5, num_windows=8)
    assert ring.windows() == [] and ring.rate_per_s() == 0.0
    assert ring.mean_over() == 0.0
    for i in range(4):
        ring.record(i * 0.5, 2.0)
        ring.record(i * 0.5 + 0.1, 4.0)
    assert ring.rate_per_s() == pytest.approx(2 / 0.5 / 1)  # 2 per window
    assert ring.mean_over() == pytest.approx(3.0)
    assert ring.mean_over(last_n=1) == pytest.approx(3.0)
    s = ring.summary()
    assert s["total_count"] == 8 and len(s["windows"]) == 4
    with pytest.raises(ValueError):
        SeriesRing(window_s=0.0)
    with pytest.raises(ValueError):
        SeriesRing(num_windows=0)


def test_record_series_through_telemetry_summary():
    telemetry.configure(enabled=True)
    for i in range(5):
        telemetry.record_series("serving/queue_depth", float(i))
    wins = telemetry.series_windows("serving/queue_depth")
    assert wins and sum(w["count"] for w in wins) == 5
    assert telemetry.series_windows("nope") is None
    ts = telemetry.summary()["timeseries"]
    ring = ts["serving/queue_depth"]
    assert ring["total_count"] == 5
    assert ring["total_sum"] == pytest.approx(10.0)
    assert ring["windows"] == wins
    assert ring["window_s"] > 0 and ring["num_windows"] >= 1


# ---------------------------------------------------------------------------
# SLO classes: attainment arithmetic, burn rate, error budget
# ---------------------------------------------------------------------------

def test_slo_attainment_arithmetic_and_gauges(tmp_path):
    jl = tmp_path / "t.jsonl"
    telemetry.configure(enabled=True, jsonl_path=str(jl))
    telemetry.set_slo_classes(SLO_CLASSES)
    for _ in range(19):
        telemetry.slo_observe("interactive", "ttft", 0.1)   # within target
    telemetry.slo_observe("interactive", "ttft", 5.0)        # violation
    telemetry.slo_observe("batch", "tpot", 1.0)              # within target

    snap = telemetry.slo_snapshot()
    st = snap["interactive"]["metrics"]["ttft"]
    assert st["requests"] == 20
    assert st["attained"] + st["violations"] == st["requests"]
    assert st == {"requests": 20, "attained": 19, "violations": 1,
                  "attainment": 0.95}
    assert snap["interactive"]["targets"]["ttft_target_s"] == 0.5
    assert snap["interactive"]["attainment_target"] == 0.9
    assert snap["batch"]["metrics"]["tpot"]["attainment"] == 1.0

    gauges = telemetry.summary()["serving"]["gauges"]
    # budget 0.1; 1/20 violating -> burn rate 0.5, half the budget consumed
    assert gauges["slo/interactive/ttft_burn_rate"]["last"] == \
        pytest.approx(0.5)
    assert gauges["slo/interactive/ttft_error_budget_remaining"]["last"] == \
        pytest.approx(0.5)
    assert gauges["slo/batch/tpot_burn_rate"]["last"] == 0.0
    assert gauges["slo/batch/tpot_error_budget_remaining"]["last"] == 1.0
    # violation windows feed the per-class ring series
    assert telemetry.series_windows("slo/interactive/ttft_violations")
    assert sum(w["count"] for w in
               telemetry.series_windows("slo/interactive/ttft_requests")) == 20

    telemetry.close()
    recs = [json.loads(l) for l in jl.read_text().splitlines() if l.strip()]
    slo_recs = [r for r in recs if r.get("kind") == "slo"]
    assert len(slo_recs) == 21  # one line per observation
    bad = [r for r in slo_recs if not r["tags"]["attained"]]
    assert len(bad) == 1 and bad[0]["name"] == "slo/interactive/ttft"
    assert bad[0]["tags"]["target_s"] == 0.5


def test_slo_unknown_class_histogram_only():
    telemetry.configure(enabled=True)
    telemetry.set_slo_classes(SLO_CLASSES)
    telemetry.slo_observe("mystery", "ttft", 0.2)
    s = telemetry.summary()
    assert s["slo"] == {}  # no attainment counters for unknown classes
    assert s["serving"]["histograms"]["serving/ttft_s/mystery"]["count"] == 1
    # a class missing the metric's target: histogram only, too
    telemetry.set_slo_classes({"ttft_only": {"ttft_target_s": 1.0,
                                             "attainment_target": 0.9}})
    telemetry.slo_observe("ttft_only", "tpot", 0.2)
    assert "ttft_only" not in telemetry.slo_snapshot()


# ---------------------------------------------------------------------------
# scheduler end to end: SLO tagging + request flow events
# ---------------------------------------------------------------------------

def test_scheduler_slo_tagging_and_flow_events(served, tmp_path):
    cfg, model, params = served
    tr = tmp_path / "trace.json"
    telemetry.configure(enabled=True, chrome_trace_path=str(tr))
    engine = make_engine(cfg, model, params, slo_classes=SLO_CLASSES)
    sched = SplitFuseScheduler(engine, token_budget=16)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
               for _ in range(2)]
    sched.submit(0, prompts[0], max_new_tokens=3, slo_class="interactive")
    sched.submit(1, prompts[1], max_new_tokens=3, slo_class="batch")
    with pytest.raises(ValueError, match="unknown slo_class"):
        sched.submit(2, prompts[0], slo_class="platinum")
    out = sched.run_to_completion()
    assert all(len(out[u]) == 3 for u in (0, 1))

    snap = telemetry.slo_snapshot()
    assert set(snap) == {"interactive", "batch"}
    for cls in ("interactive", "batch"):
        for metric in ("ttft", "tpot"):
            st = snap[cls]["metrics"][metric]
            assert st["requests"] >= 1
            assert st["attained"] + st["violations"] == st["requests"]

    path = telemetry.export_chrome_trace()
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    flows = [e for e in events if e.get("name") == "reqflow"]
    by_id = {}
    for e in flows:
        by_id.setdefault(e["id"], []).append(e)
    assert set(by_id) == {0, 1}
    for fid, chain in by_id.items():
        phases = [e["ph"] for e in chain]
        assert phases[0] == "s"          # chain starts
        assert phases[-1] == "f"         # chain terminates
        assert chain[-1]["bp"] == "e"
        points = {e["args"]["point"] for e in chain}
        assert {"submit", "prefill", "finish"} <= points


# ---------------------------------------------------------------------------
# disabled-noop guarantee for the new hooks
# ---------------------------------------------------------------------------

def test_disabled_slo_hooks_zero_overhead(served, monkeypatch):
    """Telemetry disabled, a scheduler run with SLO classes configured and
    every request tagged reads the clock twice per request (``submit`` and
    its admission), never per round, and keeps no allocations in the
    telemetry core; record_series / slo_observe / record_request_flow /
    profile-store resolution all stay no-ops."""
    import tracemalloc
    from deepspeed_tpu.inference.v2 import scheduler as sched_mod

    cfg, model, params = served
    assert not telemetry.enabled()
    engine = make_engine(cfg, model, params, slo_classes=SLO_CLASSES)
    sched = SplitFuseScheduler(engine, token_budget=16)

    reads = []

    def _counted():
        reads.append(1)
        return time.perf_counter()
    monkeypatch.setattr(sched_mod, "_now", _counted)

    rng = np.random.default_rng(5)
    sched.submit(0, rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                 max_new_tokens=2, slo_class="interactive")
    sched.step()  # warm the jit caches outside the traced window

    sched.submit(1, rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                 max_new_tokens=40, slo_class="batch")
    tracemalloc.start()
    snap0 = tracemalloc.take_snapshot()
    while sched.has_work:
        sched.step()
    telemetry.record_series("x", 1.0)
    telemetry.slo_observe("interactive", "ttft", 0.1)
    telemetry.record_request_flow(7, "submit")
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

    tm = telemetry.get_telemetry()
    assert tm.series == {}
    assert tm.slo_stats == {}
    assert telemetry.series_windows("x") is None
    assert telemetry.slo_snapshot() == {}
    assert telemetry.summary() == {"enabled": False}


# ---------------------------------------------------------------------------
# profile store: the kernel-table matrix
# ---------------------------------------------------------------------------

def _write_store(path, nbytes=1 << 20, seconds=2e-4, op="all_reduce"):
    entries = {profile_store.bucket_key(op, nbytes):
               profile_store.make_entry(seconds, nbytes, "trace_cpu")}
    profile_store.save_store(str(path), "tpu_v5e", entries, "test")
    return entries


def test_profile_store_round_trip(tmp_path):
    p = tmp_path / "profile_tpu_v5e.json"
    _write_store(p, nbytes=1 << 20, seconds=2e-4)
    doc = profile_store.load_store(path=str(p))
    assert doc["format_version"] == 1
    assert doc["device_kind"] == "tpu_v5e"
    assert profile_store.validate_store(doc) == []
    # any nbytes in the same pow2 bucket hits the same entry
    for nbytes in (1 << 20, (1 << 19) + 1):
        secs, reason = profile_store.resolve("all_reduce", nbytes,
                                             path=str(p))
        assert (secs, reason) == (2e-4, "measured")
    # bucket / op / dtype misses fall back
    for args in (("all_reduce", 1 << 24), ("all_gather", 1 << 20)):
        assert profile_store.resolve(*args, path=str(p)) == \
            (None, "roofline_fallback")
    assert profile_store.resolve("all_reduce", 1 << 20, dtype="bf16",
                                 path=str(p)) == (None, "roofline_fallback")


def test_profile_store_bucket_key():
    assert profile_store.bucket_key("all_reduce", 1000) == \
        "all_reduce|b1024|any"
    assert profile_store.bucket_key("all_reduce", 1024) == \
        "all_reduce|b1024|any"
    assert profile_store.bucket_key("a2a", 0, dtype="int8") == "a2a|b1|int8"
    with pytest.raises(ValueError):
        profile_store.bucket_key("", 1024)


def test_profile_store_env_overrides(tmp_path, monkeypatch):
    p = tmp_path / "elsewhere.json"
    _write_store(p, seconds=7e-4)
    # DS_TPU_PROFILE_STORE redirects the default path outright
    monkeypatch.setenv("DS_TPU_PROFILE_STORE", str(p))
    profile_store.clear_cache()
    assert profile_store.resolve("all_reduce", 1 << 20) == \
        (7e-4, "measured")
    monkeypatch.delenv("DS_TPU_PROFILE_STORE")
    profile_store.clear_cache()
    # DS_TPU_PROFILE_STORE_DEVICE forces the device slug (aliases resolve)
    monkeypatch.setenv("DS_TPU_PROFILE_STORE_DEVICE", "v5e")
    assert profile_store.default_device_kind() == "tpu_v5e"
    assert profile_store.store_path("TPU v5e").endswith(
        os.path.join("onchip_results", "profile_tpu_v5e.json"))


def test_profile_store_broken_store_never_raises(tmp_path):
    p = tmp_path / "broken.json"
    p.write_text("{not json")
    assert profile_store.load_store(path=str(p)) is None
    assert profile_store.resolve("all_reduce", 1 << 20, path=str(p)) == \
        (None, "roofline_fallback")
    # valid json, invalid schema: cached as None, still a clean fallback
    p.write_text(json.dumps({"format_version": 1, "device_kind": "x",
                             "entries": {"bad_key": {}}}))
    profile_store.clear_cache()
    assert profile_store.load_store(path=str(p)) is None
    # missing file
    assert profile_store.load_store(path=str(tmp_path / "nope.json")) is None


def test_profile_store_validate_errors():
    ok = {"format_version": 1, "device_kind": "tpu_v5e",
          "entries": {"all_reduce|b1024|any":
                      profile_store.make_entry(1e-4, 1000, "trace_cpu")}}
    assert profile_store.validate_store(ok) == []
    cases = [
        ({"device_kind": "x", "entries": {}}, "format_version"),
        ({"format_version": 1, "entries": {}}, "device_kind"),
        ({"format_version": 1, "device_kind": "x"}, "entries"),
    ]
    for doc, frag in cases:
        errs = profile_store.validate_store(doc)
        assert errs and any(frag in e for e in errs), (doc, errs)
    bad_entries = {
        "no_pipes": profile_store.make_entry(1e-4, 10, "trace_cpu"),
        "op|bWAT|any": profile_store.make_entry(1e-4, 10, "trace_cpu"),
        "op|b8|any": {"seconds": -1.0, "bytes": 8, "count": 1,
                      "source": "trace_cpu"},
        "op2|b8|any": {"seconds": 1e-4, "bytes": 8, "count": 1,
                       "source": "vibes"},
    }
    for key, entry in bad_entries.items():
        errs = profile_store.validate_store(
            {"format_version": 1, "device_kind": "x",
             "entries": {key: entry}})
        assert errs, key


def test_profile_store_save_refuses_invalid_and_merge_wins(tmp_path):
    p = tmp_path / "store.json"
    with pytest.raises(ValueError):
        profile_store.save_store(
            str(p), "tpu_v5e",
            {"op|b8|any": {"seconds": -1.0, "bytes": 8, "count": 1,
                           "source": "trace_cpu"}}, "test")
    assert not p.exists()  # atomic: nothing half-written
    key = profile_store.bucket_key("all_reduce", 1 << 20)
    _write_store(p, seconds=1e-4)
    profile_store.merge_store(
        str(p), "tpu_v5e",
        {key: profile_store.make_entry(9e-4, 1 << 20, "trace_cpu"),
         profile_store.bucket_key("all_gather", 1 << 10):
         profile_store.make_entry(3e-5, 1 << 10, "trace_cpu")}, "test")
    profile_store.clear_cache()
    doc = profile_store.load_store(path=str(p))
    assert len(doc["entries"]) == 2
    assert doc["entries"][key]["seconds"] == 9e-4  # new keys win


# ---------------------------------------------------------------------------
# overlap_schedule consults the store ahead of the roofline
# ---------------------------------------------------------------------------

def test_fill_comm_seconds_measured_vs_fallback(tmp_path, monkeypatch):
    nbytes = 1 << 20
    p = tmp_path / "profile_tpu_v5e.json"
    _write_store(p, nbytes=nbytes, seconds=123e-6)
    ops = [{"op": "all_reduce", "bytes": nbytes, "count": 1, "axis": "dp"}]

    monkeypatch.setenv("DS_TPU_PROFILE_STORE", str(p))
    profile_store.clear_cache()
    telemetry.configure(enabled=True)
    spec = overlap_schedule.fill_comm_seconds(ops, device_kind="tpu_v5e")[0]
    assert spec["cost_source"] == "measured"
    assert spec["seconds"] == pytest.approx(123e-6)
    counters = telemetry.summary()["counters"]
    assert counters.get("overlap/cost_resolution/measured") == \
        {"op=all_reduce": 1}

    monkeypatch.setenv("DS_TPU_PROFILE_STORE", str(tmp_path / "nope.json"))
    profile_store.clear_cache()
    spec = overlap_schedule.fill_comm_seconds(ops, device_kind="tpu_v5e")[0]
    assert spec["cost_source"] == "roofline_fallback"
    assert spec["seconds"] > 0
    assert telemetry.summary()["counters"].get(
        "overlap/cost_resolution/roofline_fallback") == {"op=all_reduce": 1}
    # entries that already carry seconds are never re-priced
    priced = overlap_schedule.fill_comm_seconds(
        [{"op": "all_reduce", "bytes": nbytes, "seconds": 1.0}])[0]
    assert priced["seconds"] == 1.0 and "cost_source" not in priced


# ---------------------------------------------------------------------------
# trace_merge: flow events + per-host SLO attainment
# ---------------------------------------------------------------------------

def _host_jsonl(path, host, recs):
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps({"host": host, "pid": 1, **r}) + "\n")


def test_trace_merge_flow_and_slo_by_host(tmp_path):
    tm = _load_script(TRACE_MERGE, "_tm_slo")
    slo = lambda ts, cls, v, ok: {
        "ts": ts, "name": f"slo/{cls}/ttft", "kind": "slo", "value": v,
        "tags": {"slo_class": cls, "metric": "ttft", "n": 1,
                 "attained": ok, "target_s": 0.5}}
    flow = lambda ts, ph, point, fid: {
        "ts": ts, "name": f"serving/flow/{point}", "kind": "flow",
        "value": fid, "tags": {"uid": fid, "flow_phase": ph}}
    # host A admits request 7; host B prefises + finishes it — the chain
    # must bind across the two synthetic pids via the shared flow id
    a = tmp_path / "a.jsonl"
    b = tmp_path / "b.jsonl"
    _host_jsonl(a, "hostA", [
        {"ts": 1.0, "name": "comm/all_reduce", "kind": "count", "value": 8,
         "tags": {"axis": "dp", "seconds": 0.001}},
        flow(1.1, "s", "admit", 7),
        slo(1.5, "interactive", 0.1, True),
        slo(1.6, "interactive", 0.2, True)])
    _host_jsonl(b, "hostB", [
        {"ts": 5.0, "name": "comm/all_reduce", "kind": "count", "value": 8,
         "tags": {"axis": "dp", "seconds": 0.001}},
        flow(5.2, "t", "prefill", 7),
        flow(5.3, "f", "finish", 7),
        slo(5.5, "interactive", 9.0, False),
        slo(5.6, "batch", 1.0, True)])

    out = tmp_path / "merged.json"
    rep = tmp_path / "report.json"
    merged = tm.merge([str(a), str(b)], out_path=str(out),
                      report_path=str(rep))
    events = json.loads(out.read_text())["traceEvents"]
    flows = [e for e in events if e.get("name") == "reqflow"]
    assert {e["ph"] for e in flows} == {"s", "t", "f"}
    assert {e["id"] for e in flows} == {7}  # id survives the pid remap
    assert len({e["pid"] for e in flows}) == 2  # ...across two host tracks
    fin = [e for e in flows if e["ph"] == "f"]
    assert fin[0]["bp"] == "e" and fin[0]["args"]["point"] == "finish"

    report = json.loads(rep.read_text())
    per_host = report["slo_attainment_by_host"]
    assert set(per_host) == {"hostA:1", "hostB:1"}
    sa = per_host["hostA:1"]["interactive"]["ttft"]
    assert sa == {"requests": 2, "attained": 2, "violations": 0,
                  "attainment": 1.0}
    sb = per_host["hostB:1"]["interactive"]["ttft"]
    assert sb["violations"] == 1 and sb["attainment"] == 0.0
    assert report["worst_slo_host"] == "hostB:1"
    assert merged is not None


# ---------------------------------------------------------------------------
# perf_gate: validators, profile-store check, SLO ratchet
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pg():
    return _load_script(PERF_GATE, "_pg_slo")


def _slo_payload(attainment=0.95, requests=20):
    attained = round(requests * attainment)
    extra = {
        "ttft_p50_s": 0.1, "ttft_p99_s": 0.3, "tpot_p50_s": 0.05,
        "tpot_p99_s": 0.2, "peak_kv_occupancy": 0.5,
        "slo_classes": {
            cls: {"targets": {"ttft_target_s": 1.0, "tpot_target_s": 0.25},
                  "attainment_target": 0.9,
                  "metrics": {"ttft": {
                      "requests": requests, "attained": attained,
                      "violations": requests - attained,
                      "attainment": round(attained / requests, 6)}},
                  "percentiles": {"ttft": {"p50_s": 0.1, "p95_s": 0.2,
                                           "p99_s": 0.3}}}
            for cls in ("interactive", "batch")},
        "slo_min_attainment": round(attained / requests, 6),
        "telemetry": {
            "enabled": True, "spans": {},
            "timeseries": {
                f"slo/x/{i}": {"window_s": 0.5, "num_windows": 64,
                               "total_count": 2, "total_sum": 3.0,
                               "windows": [{"index": 4, "start_s": 2.0,
                                            "count": 2, "sum": 3.0,
                                            "min": 1.0, "max": 2.0,
                                            "mean": 1.5}]}
                for i in range(3)}}}
    return {"metric": "serving_replay_tps", "value": 100.0, "extra": extra}


def test_validate_timeseries_payload(pg):
    doc = _slo_payload()
    assert pg.validate_timeseries_payload(doc) is None
    assert pg.validate_timeseries_payload({"extra": {}}) is None
    ring = doc["extra"]["telemetry"]["timeseries"]["slo/x/0"]
    for mutate, frag in [
            (lambda: ring.update(window_s=0), "not positive"),
            (lambda: ring.update(window_s=0.5, total_count=1),
             "exceed lifetime"),
            (lambda: ring.update(total_count=2) or
             ring["windows"][0].update(min=9.0), "unordered"),
            (lambda: ring["windows"][0].update(min=1.0, count=0),
             "count < 1"),
            (lambda: ring["windows"][0].update(count=2, mean=float("nan")),
             "not finite")]:
        mutate()
        err = pg.validate_timeseries_payload(doc)
        assert err and frag in err, (frag, err)


def test_validate_slo_payload(pg):
    doc = _slo_payload()
    assert pg.validate_slo_payload(doc) is None
    assert pg.validate_slo_payload({"extra": {}}) is None
    st = doc["extra"]["slo_classes"]["interactive"]["metrics"]["ttft"]
    st["attained"] -= 1
    err = pg.validate_slo_payload(doc)
    assert err and "attainment counters leaked" in err
    st["attained"] += 1
    st["attainment"] = 0.1
    err = pg.validate_slo_payload(doc)
    assert err and "inconsistent with its own counters" in err
    doc = _slo_payload()
    doc["extra"]["slo_min_attainment"] = 0.123
    err = pg.validate_slo_payload(doc)
    assert err and "slo_min_attainment" in err
    doc = _slo_payload()
    p = doc["extra"]["slo_classes"]["batch"]["percentiles"]["ttft"]
    p["p50_s"] = 9.0
    err = pg.validate_slo_payload(doc)
    assert err and "percentiles unordered" in err
    assert pg._slo_min_attainment(_slo_payload(attainment=0.9)) == \
        pytest.approx(0.9)
    assert pg._slo_min_attainment({"extra": {}}) is None


def test_check_profile_store(pg, tmp_path):
    report, errors = pg.check_profile_store(stores_dir=str(tmp_path / "no"))
    assert not errors and "skipped" in report
    _write_store(tmp_path / "profile_tpu_v5e.json", seconds=1e-4)
    report, errors = pg.check_profile_store(stores_dir=str(tmp_path))
    assert errors == [], errors
    st = report["stores"]["profile_tpu_v5e.json"]
    assert st["entries"] == 1
    assert st["resolved"]["reason"] == "measured"
    assert st["resolved"]["seconds"] == pytest.approx(1e-4)
    # an empty store is an error, not a skip
    profile_store.save_store(str(tmp_path / "profile_empty.json"),
                             "empty", {}, "test")
    _, errors = pg.check_profile_store(stores_dir=str(tmp_path))
    assert any("no entries" in e for e in errors)


def test_check_slo_baseline(pg, tmp_path):
    report, errors = pg.check_slo_baseline(
        baseline_path=str(tmp_path / "nope.json"))
    assert not errors and "skipped" in report
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_slo_payload(attainment=0.95)))
    report, errors = pg.check_slo_baseline(baseline_path=str(good))
    assert errors == [], errors
    assert report["classes"] == ["batch", "interactive"]
    assert report["min_attainment"] == pytest.approx(0.95)
    assert report["live_series"] == 3
    # attainment below the ratchet floor
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(_slo_payload(attainment=0.5)))
    _, errors = pg.check_slo_baseline(baseline_path=str(bad))
    assert any("stopped meeting" in e for e in errors)
    # dead trajectory plane: no live series
    doc = _slo_payload()
    for ring in doc["extra"]["telemetry"]["timeseries"].values():
        ring["windows"] = []
        ring["total_count"] = 0
        ring["total_sum"] = 0.0
    dead = tmp_path / "dead.json"
    dead.write_text(json.dumps(doc))
    _, errors = pg.check_slo_baseline(baseline_path=str(dead))
    assert any("did not record" in e for e in errors)
    # malformed arithmetic is rejected before the ratchet even runs
    doc = _slo_payload()
    doc["extra"]["slo_classes"]["batch"]["metrics"]["ttft"]["attained"] += 2
    broken = tmp_path / "broken.json"
    broken.write_text(json.dumps(doc))
    _, errors = pg.check_slo_baseline(baseline_path=str(broken))
    assert any("attainment counters leaked" in e for e in errors)
