"""The yardstick's arithmetic, with no program under test."""

import json
import os

import numpy as np
import pytest

from benchmark import harness, peaks, trace, traffic
from benchmark.drivers.train import whole_step_rate, leaf_gaps, make_batches
from benchmark.readers import idle_share, kernel_roofline, mfu, span_ms

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHAT = {"loop": "open", "arrival": "poisson", "rate": 4.0, "shape_seed": 0,
        "prompt": {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 32, "max": 2048},
        "output": {"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 16, "max": 384}}
CLOSED = {"loop": "closed", "clients": 8, "requests_per_client": 3, "shape_seed": 0,
          "prompt": {"dist": "uniform", "min": 128, "max": 512},
          "output": {"dist": "uniform", "min": 256, "max": 512}}


# -- traffic ------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_same_seed_same_requests(seed):
    a, b = (traffic.requests(CHAT, seed, 30, 32000) for _ in range(2))
    assert len(a["requests"]) == 120
    for (d1, p1, n1), (d2, p2, n2) in zip(a["requests"], b["requests"]):
        assert d1 == d2 and n1 == n2 and np.array_equal(p1, p2)


def test_seeds_permute_one_multiset_of_sizes_and_gaps():
    a, b = (traffic.requests(CHAT, s, 30, 32000)["requests"] for s in (1, 2))
    sizes = lambda rs: sorted((len(p), n) for _, p, n in rs)
    assert sizes(a) == sizes(b)
    assert [len(p) for _, p, _ in a] != [len(p) for _, p, _ in b]
    def gaps(rs):
        due = np.array([d for d, _, _ in rs])
        smallest = 120 / 4.0 - due[-1]          # the shift: the multiset's smallest gap
        return np.sort(np.concatenate([[due[0] + smallest], np.diff(due)]))
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9, atol=1e-12)
    assert all(0 <= d < 30 for d, _, _ in a)
    lens = [len(p) for _, p, _ in a]
    assert min(lens) >= 32 and max(lens) <= 2048


def test_burst_arrivals_share_a_due_time():
    mix = dict(CHAT, arrival="burst", burst_size=4)
    due = [d for d, _, _ in traffic.requests(mix, 3, 30, 100)["requests"]]
    assert len(set(np.round(due, 9))) == len(due) // 4


def test_closed_loop_clients_and_phases():
    load = traffic.requests(CLOSED, 5, 30, 32000)
    assert len(load["clients"]) == 8 and all(len(q) == 3 for q in load["clients"])
    assert sorted(load["phase"]) == [(i + 1) / 8 for i in range(8)]
    again = traffic.requests(CLOSED, 5, 30, 32000)
    assert all(np.array_equal(p, q) for c, d in zip(load["clients"], again["clients"])
               for (p, _), (q, _) in zip(c, d))


def test_train_batches_rows_all_differ():
    b = make_batches(2**31 + 5, {"pool": 3, "batch": 4, "seq": 16}, 50257)
    rows = b.reshape(-1, 16)
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert np.array_equal(b, make_batches(2**31 + 5, {"pool": 3, "batch": 4, "seq": 16}, 50257))


# -- whole-step throughput ------------------------------------------------------

@pytest.mark.parametrize("n_steps", [60, 61, 75])
def test_whole_step_rate_has_no_edge_quantisation(n_steps):
    """A window counted against a fixed clock reads n or n+1 steps for the
    same step time; counted by whole steps it reads the step time."""
    step = 0.4
    done = [10.0 + step * (i + 1) for i in range(n_steps)]
    assert whole_step_rate(12 * 1024, 10.0, done) == pytest.approx(12 * 1024 / step)


def test_percentile_is_nearest_rank_over_all_values():
    xs = list(range(1, 101))
    assert harness.percentile(xs, 90) == 90 and harness.percentile(xs, 99) == 99
    assert harness.percentile([5.0], 99) == 5.0 and harness.percentile([], 50) is None


def test_leaf_gap_is_measured_against_the_larger_of_leaf_and_median():
    want = {"a": 1.0, "b": 2.0, "c": 1e-9}
    got = {"a": 1.1, "b": 2.0, "c": 2e-9}
    gaps = leaf_gaps(got, want)
    assert gaps["a"] == pytest.approx(0.1) and gaps["c"] == pytest.approx(1e-9)


# -- peaks and work ---------------------------------------------------------------

def test_peaks_table_and_unknown_device():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    with pytest.raises(SystemExit):
        peaks.peaks_for("cpu")


def test_gpt2_medium_flops_per_token():
    cfg = {"n_embd": 1024, "n_layer": 24, "vocab_size": 50257}
    got = peaks.gpt2_train_flops_per_token(cfg, 1024)
    assert got == 6 * (24 * 12 * 1024**2 + 50257 * 1024) + 24 * 6 * 1024 * 1024
    assert 2.2e9 < got < 2.4e9


def test_flash_work():
    f = peaks.flash_train_flops(12, 16, 1024, 64)
    assert f == 7 * 12 * 16 * 1024 * 1024 * 64
    assert peaks.flash_train_bytes(12, 16, 1024, 64) == 12 * 12 * 16 * 1024 * 64 * 2
    t, bound = peaks.roofline_seconds(f, peaks.flash_train_bytes(12, 16, 1024, 64),
                                      peaks.PEAKS["TPU v5 lite"])
    assert bound == "compute" and t == pytest.approx(f / 197e12)


def test_paged_decode_work_is_memory_bound():
    nbytes = peaks.paged_decode_bytes(64 * 700, 16, 8, 128)
    assert nbytes == 2 * 64 * 700 * 16 * 8 * 128 * 2
    flops = peaks.paged_decode_flops(64 * 700, 16, 32, 128)
    assert peaks.roofline_seconds(flops, nbytes, peaks.PEAKS["TPU v5 lite"])[1] == "memory"


# -- the trace reduction, on a trace recorded on the chip (PR 26) ------------------

@pytest.fixture(scope="module")
def probe():
    return trace.load(os.path.join(DATA, "probe.xplane.pb"))


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_short_name():
    text = ('%jvp__.1 = (bf16[2,4,512,64]{3,2,1,0:T(8,128)(2,1)S(1)}, f32[2,4,512,128]{3,2,1,0}) '
            'custom-call(bf16[2,4,512,64]{3,2,1,0} %bitcast.28), custom_call_target="tpu_custom_call"')
    assert trace.short_name(text) == "jvp__.1 bf16[2,4,512,64] custom-call"


def test_recorded_trace_reduces(probe):
    assert list(probe["devices"]) == ["/device:TPU:0"]
    assert [n for n, _, _ in probe["spans"]].count("bench/dispatch") == 3
    s = trace.summarize(probe)
    # the three bench spans cover 24.4 ms; two of the three 47 us programs
    # start inside them (the device's clock runs ~1 ms ahead of the host's)
    assert s["window_s"] == pytest.approx(0.0244, abs=2e-4)
    assert s["busy_s"] == pytest.approx(94.2e-6, rel=0.01)
    assert s["device_ops"][0][0].endswith("custom-call") and len(s["device_ops"]) == 10
    assert s["idle_gaps"][0][0] == "bench/wait"
    assert 0 < s["busy_s"] < s["window_s"]


def test_kernel_events_and_within(probe):
    events = trace.kernel_events(probe, "tpu_custom_call")
    assert len(events) == 4                       # flash forward + backward, two programs
    first = [events[0]]
    assert trace.kernel_events(probe, "tpu_custom_call", within=first) == first
    assert trace.kernel_events(probe, "no such kernel") == []


def test_readers_on_the_recorded_trace(probe):
    class C:
        config = {"n_layer": 1, "n_head": 4, "n_embd": 256}
    summary = trace.summarize(probe)
    ctx = {"cell": C, "trace": probe, "summary": summary, "chips": 1, "notes": [],
           "peaks": peaks.PEAKS["TPU v5 lite"],
           "facts": {"steps": 2, "batch": 2, "seq": 512, "tokens_per_s": 1e4},
           "spans": [("dispatch", 0.0, 0.004, {}), ("dispatch", 1.0, 1.002, {}),
                     ("round", 0.0, 0.03, {"prefill_tokens": 0}),
                     ("round", 0.0, 0.3, {"prefill_tokens": 400})]}
    assert idle_share.read(ctx, {}) == pytest.approx(100 * (1 - summary["busy_s"] / summary["window_s"]))
    share = kernel_roofline.read(ctx, {"match": "tpu_custom_call", "work": "flash_train"})
    took = sum(b - a for a, b in trace.kernel_events(probe, "tpu_custom_call")) / 1e9
    least, bound = peaks.roofline_seconds(2 * peaks.flash_train_flops(2, 4, 512, 64),
                                          2 * peaks.flash_train_bytes(2, 4, 512, 64), ctx["peaks"])
    assert bound == "memory"                      # this small a shape is
    assert share == pytest.approx(100 * least / took)
    assert 0 < share < 100
    assert kernel_roofline.read(ctx, {"match": "nothing", "work": "flash_train"}) is None
    assert span_ms.read(ctx, {"span": "dispatch"}) == pytest.approx(2.0)
    assert span_ms.read(ctx, {"span": "round", "where": {"prefill_tokens": [256, None]}}) == pytest.approx(300.0)
    assert span_ms.read(ctx, {"span": "round", "where": {"prefill_tokens": [0, 0]}}) == pytest.approx(30.0)
    assert span_ms.read(ctx, {"span": "absent"}) is None
    C.config = {"n_embd": 1024, "n_layer": 24, "vocab_size": 50257}
    assert mfu.read(ctx, {"flops_fn": "gpt2_train_flops_per_token"}) == pytest.approx(
        100 * peaks.gpt2_train_flops_per_token(C.config, 512) * 1e4 / 197e12)


# -- BENCHMARK.json against its files ------------------------------------------------

def test_every_entry_of_benchmark_json_has_its_files():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.Cell(w["name"])
        assert hasattr(harness.load("drivers", cell.config["driver"]), "Driver")
        harness.load("references", cell.config["reference"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end) and len(cell.end_to_end) >= 2
        for m in cell.per_layer:
            with open(os.path.join(cell.metrics_dir, m["name"] + ".json")) as f:
                harness.load("readers", json.load(f)["reader"])
