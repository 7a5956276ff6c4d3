"""CPU rehearsal of the Mellum2 serving cell at a tiny size: the new driver,
reference, traffic keys and readers end to end, the int8 control and a term
left out coming out as not correct; the readers on recorded spans and a made-up
device line; ``peaks_mellum2``'s counts against a hand count. The cell is added
to a copy of the tiny benchmark by files and entries, as a PR adds it to the
real one. No number here is a device number."""

import json
import os
import shutil
import time

import pytest

from benchmark import harness, peaks, peaks_mellum2 as work, run, xplane_scopes
from benchmark.readers import mellum2_kernels
from benchmark.tests.conftest import TINY

NAME = "mellum2-tiny.code-tiny"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

CONFIG = {
    "source": "tiny rehearsal preset of the CPU tests, not a model",
    "vocab_size": 384, "hidden_size": 128, "head_dim": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 128, "sliding_window": 16, "max_position_embeddings": 512,
    "rms_norm_eps": 1e-06,
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention",
                    "full_attention"] * 2,
    "mlp_layer_types": ["sparse"] * 8,
    "rope_parameters": {
        "full_attention": {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
                           "original_max_position_embeddings": 32, "beta_fast": 32,
                           "beta_slow": 1, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
    "reduced": [],
    "driver": "serve_mellum2", "reference": "mellum2",
    "engine": {"state_manager": {"max_ragged_sequence_count": 8, "max_ragged_batch_size": 32,
                                 "max_context": 256, "num_kv_blocks": 128, "kv_dtype": "fp"},
               "kv_cache": {"block_size": 8}},
    # with 2 experts of 8 a token, one near-tie of the router that bfloat16
    # decides otherwise swaps a third of a layer's feed-forward output: the
    # program reads 0.0026-0.0047 / 0.16-0.35 (mean / max over six seeds), the
    # int8 control 0.0044-0.0092 / 0.19-0.35. They do not separate by a limit
    # at this size (64 experts of 8 swap a 14th); the test compares them on the
    # same sample instead, and holds a left-out renormalisation to the limit
    "limits": {"served_gap_max": 0.6, "served_gap_mean": 0.012, "served_gap_mean_vs_int8": 0.95},
}
TRAFFIC = {
    "generator": "requests", "loop": "closed", "clients": 6, "requests_per_client": 3,
    "shape_seed": 0, "order": "fixed",
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.6, "min": 20, "max": 90},
    "output": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8, "max": 60},
    "check_requests": 6, "check_pad_to": 256, "check_max_new": 64, "trace_seconds": 1,
    "control_without": "renormalise",
}


@pytest.fixture
def bench(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(TINY, root)
    with open(root / "configs" / "mellum2-tiny.json", "w") as f:
        json.dump(CONFIG, f)
    with open(root / "traffic" / "code-tiny.json", "w") as f:
        json.dump(TRAFFIC, f)
    with open(root / "BENCHMARK.json") as f:
        b = json.load(f)
    b["configs"].append({"name": "mellum2-tiny", "source": "test", "reduced": [],
                         "file": "configs/mellum2-tiny.json", "why": "test"})
    b["workloads"].append({"name": NAME, "config": "mellum2-tiny", "traffic": "code-tiny",
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
    out = capsys.readouterr().out
    assert "compared " in out and "the router's chosen set changes" in out

    mod = harness.load("drivers", "serve_mellum2")
    driver = mod.Driver(cell, 5, harness.Recorder(), devices=devices, seconds=6.0)
    groups = driver.engine.kv_stats()["groups"]
    assert set(groups) == {"kv", "window"}
    driver.window(6.0, str(tmp_path))
    rounds = [a for n, _, _, a in driver.rec.spans if n == "round"]
    assert all("attn_rows" in a for a in rounds)
    # decode rows beside chunks, contexts on both sides of the window
    rows = [row for a in rounds for row in a["attn_rows"]]
    assert any(new == 1 and end > CONFIG["sliding_window"] for new, end in rows)
    assert any(new > 1 for new, end in rows)
    for a in rounds:                      # every token the round ran is in one row
        assert sum(new for new, _ in a["attn_rows"]) == a["prefill_tokens"] + a["decode_rows"]
    sched = driver.sched
    assert sched.expert_rows == sched.real_tokens * 2 * 4 and sched.expert_rows_padded == 0
    assert sched.window_pages_freed > 0
    driver.release()
    sound = {n: v for n, v, _ in driver.compare()}
    control = {n: v for n, v, _ in driver.control()}
    names = {"served_gap.max", "served_gap.mean", "served_gap.mean_vs_int8"}
    assert set(sound) == names
    assert set(control) == names | {"without_renormalise." + n for n in names}
    limit = cell.limit("served_gap_mean")
    assert sound["served_gap.mean"] <= limit < control["without_renormalise.served_gap.mean"]
    # int8, on the same tokens: what the third limit holds the program to
    assert sound["served_gap.mean_vs_int8"] < 1.0 == control["served_gap.mean_vs_int8"]
    assert sound["served_gap.mean"] == pytest.approx(
        sound["served_gap.mean_vs_int8"] * control["served_gap.mean"])
    assert control["without_renormalise.served_gap.mean_vs_int8"] > 1.0


def test_every_term_the_reference_can_leave_out_moves_the_served_gap(bench, cpu_device, tmp_path):
    cell = harness.Cell(NAME, bench)
    mod = harness.load("drivers", "serve_mellum2")
    driver = mod.Driver(cell, 7, harness.Recorder(), devices=cpu_device[0], seconds=4.0)
    driver.window(4.0, str(tmp_path))
    driver.release()
    limit = cell.limit("served_gap_mean")
    for term in ("renormalise", "qk_norm"):
        control = {n: v for n, v, _ in driver._gaps(f"without:{term}")}
        assert control["served_gap.mean"] > limit, term
    with pytest.raises(ValueError, match="unknown term"):
        driver._gaps("without:the_router")


# -- the work functions against a hand count ---------------------------------------

PUBLISHED = {"hidden_size": 2304, "moe_intermediate_size": 896, "num_experts": 64,
             "num_experts_per_tok": 8, "num_hidden_layers": 12, "num_attention_heads": 32,
             "num_key_value_heads": 4, "head_dim": 128, "sliding_window": 1024,
             "layer_types": ["sliding_attention"] * 3 * 7, "mlp_layer_types": ["sparse"] * 28}
PUBLISHED["layer_types"] = (["sliding_attention"] * 3 + ["full_attention"]) * 7


def test_expert_work_at_one_shape_by_hand():
    """A [64, 1] dispatch, 12 expert layers. FLOPs: 64 tokens x 8 rows x 3
    GEMMs x 2 x 2304 x 896 = 6,341,787,648 a layer. Bytes: 64 (1 - (7/8)^64)
    = 63.9876 experts x 3 x 2304 x 896 x 2 B = 12,386,304 B each, plus 512
    rows x 2304 x 2 B in and as many out."""
    assert work.expert_layers(PUBLISHED) == 12
    assert work.moe_gmm_flops(PUBLISHED, 64) == 12 * 6_341_787_648
    hit = 64 * (1 - (7 / 8) ** 64)
    assert work.experts_hit(PUBLISHED, 64) == pytest.approx(hit) and 63.98 < hit < 64
    assert work.experts_hit(PUBLISHED, 1) == pytest.approx(8.0)
    by_hand = 12 * (hit * 12_386_304 + 2 * 512 * 2304 * 2)
    assert work.moe_gmm_bytes(PUBLISHED, 64) == pytest.approx(by_hand)
    # 9.5 GB of weights a dispatch: memory-bound, 11.6 ms at 819 GB/s
    least, bound = peaks.roofline_seconds(work.moe_gmm_flops(PUBLISHED, 64),
                                          work.moe_gmm_bytes(PUBLISHED, 64),
                                          peaks.peaks_for("TPU v5e"))
    assert bound == "memory" and least == pytest.approx(by_hand / 819e9)
    assert 0.0115 < least < 0.0118


def test_attention_work_at_two_rows_by_hand():
    """A decode row at context 5000: the 3 full layers read 5000 tokens, the 9
    sliding layers 1024, 2 KiB a token and layer, plus q and o of 32 x 128 x
    2 B in each of 12 layers. A 512-token chunk ending at 512: every query
    sees the keys up to itself in both kinds (512 x 513 / 2 pairs); ending at
    2048, a sliding query sees 1024 and the row must read 1024 + 511 keys."""
    assert work.attention_layers(PUBLISHED) == (9, 3) and work.kv_token_bytes(PUBLISHED) == 2048
    assert work.mixed_attn_bytes(PUBLISHED, 1, 5000) == \
        2048 * (3 * 5000 + 9 * 1024) + 12 * 2 * 32 * 128 * 2
    assert work.mixed_attn_flops(PUBLISHED, 1, 5000) == 4 * 32 * 128 * (3 * 5000 + 9 * 1024)
    pairs = 512 * 513 // 2
    assert work.mixed_attn_flops(PUBLISHED, 512, 512) == 4 * 32 * 128 * 12 * pairs
    assert work._visible(512, 2048, 1024) == (1024 + 511, 512 * 1024)
    assert work._visible(512, 2048) == (2048, 512 * 2048 - 512 * 511 // 2)
    assert work._visible(1, 700, 1024) == (700, 700)


# -- the readers on a made-up device line and the recorded spans -------------------

class _Cell:
    name = NAME
    config = PUBLISHED


def _ctx(device_events, rounds, builds):
    window = ("bench/window", 0, 10_000_000_000)
    loaded = {"spans": [("ds/serving/build", 1000 + i, 2000 + i, a) for i, a in enumerate(builds)],
              "window": (0, 10_000_000_000), "table": [], "offset": None}
    return {"cell": _Cell, "trace": {"devices": {"/device:TPU:0": device_events},
                                     "spans": [window]},
            "spans": [("round", 0.0, 0.1, a) for a in rounds], "program_spans": loaded,
            "summary": {"busy_s": 2.0}, "peaks": peaks.peaks_for("TPU v5e"), "notes": []}


def test_readers_turn_events_and_spans_into_shares(monkeypatch):
    ms = 1_000_000
    gmm = "%gmm.7 = f32[512,896]{1,0} custom-call(bf16[512,2304]{1,0} %x), custom_call_target"
    paged = "%paged_attention.3 = bf16[64,1,32,128]{3,2,1,0} custom-call("
    sort = "%fusion.9 = s32[512]{0} fusion("
    other = "%fusion.1 = bf16[64,98304]{1,0} fusion("
    events = [(gmm, 0, 50 * ms), (gmm, 60 * ms, 110 * ms), (paged, 120 * ms, 124 * ms),
              (sort, 130 * ms, 140 * ms), (other, 150 * ms, 300 * ms)]
    builds = [{"real_tokens": 64, "expert_rows": 64 * 8 * 12, "expert_rows_padded": 0, "seqs": 64}]
    rounds = [{"attn_rows": [(1, 5000)] * 64, "decode_rows": 64}]
    ctx = _ctx(events, rounds, builds)
    least = work.moe_gmm_bytes(PUBLISHED, 64) / 819e9
    got = mellum2_kernels.read(ctx, {"match": "^%?gmm", "work": "moe_gmm"})
    assert got == pytest.approx(100 * least / 0.1)
    assert "0 of 1 dispatches compute-bound" in ctx["notes"][-1]
    got = mellum2_kernels.read(ctx, {"match": "^%?paged_attention", "work": "mixed_attn"})
    assert got == pytest.approx(100 * 64 * work.mixed_attn_bytes(PUBLISHED, 1, 5000) / 819e9 / 0.004)
    assert "64 past the window" in ctx["notes"][-1]
    scopes = {gmm: "jit(ragged_forward)/moe_ffn/moe_ffn_gmm/jit(gmm)/pallas_call",
              sort: "x.py:3\njit(ragged_forward)/moe_ffn/moe_sort/sort",
              paged: "jit(ragged_forward)/paged_attention", other: "jit(ragged_forward)/dot_general"}
    monkeypatch.setattr(xplane_scopes, "op_names", lambda path: scopes)
    ctx["trace_path"] = "unused"
    got = mellum2_kernels.read(ctx, {"scope": "/moe_ffn/(moe_router|moe_sort|moe_ffn_gmm|moe_unsort)?",
                                     "work": "moe_share"})
    assert got == pytest.approx(100 * 0.110 / 2.0)
    assert "moe_ffn_gmm 0.1000, moe_sort 0.0100" in ctx["notes"][-1]
    assert "0 / 6144 = 0.00 %" in ctx["notes"][-1]


def test_readers_give_none_for_a_program_without_the_events_or_spans():
    ctx = _ctx([("%fusion.1 = bf16[8]{0} fusion(", 0, 1000)], [{"decode_rows": 4}], [{"real_tokens": 4}])
    assert mellum2_kernels.read(ctx, {"match": "^%?gmm", "work": "moe_gmm"}) is None
    assert mellum2_kernels.read(ctx, {"match": "^%?paged_attention", "work": "mixed_attn"}) is None
    ctx["trace"] = None
    assert mellum2_kernels.read(ctx, {"match": "^%?gmm", "work": "moe_gmm"}) is None


def test_op_names_of_a_recorded_chip_trace():
    """The probe trace (one chip, PR 26): every device operation's metadata
    holds its ``op_name``, under the HLO text ``ProfileData`` shows."""
    from jax.profiler import ProfileData
    names = xplane_scopes.op_names(os.path.join(DATA, "probe.xplane.pb"))
    shown = {e.name for plane in ProfileData.from_file(os.path.join(DATA, "probe.xplane.pb")).planes
             if plane.name.startswith("/device:TPU:") for line in plane.lines
             if line.name == "XLA Ops" for e in line.events}
    assert shown and all(name in names for name in shown)
    assert sum("jit(loss)/" in names[name] for name in shown) > len(shown) // 2
    assert xplane_scopes.op_names(os.path.join(DATA, "probe.xplane.pb"), "/device:GPU:") == {}
