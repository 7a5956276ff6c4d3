"""CPU rehearsal of the Kimi-Linear serving cell at a tiny size: the new
driver, reference, traffic keys and readers end to end (the two KDA kernels,
the latent walk and the grouped GEMM in interpret mode), the device counters
against the host's count, the int8 control and the decay left out coming out
as not correct; the real cell's files through ``harness.Cell``;
``peaks_kimi_linear``'s counts against a hand count; the readers on a made-up
device line and without what they read; the reference's blocks against its
unblocked form. The cell is added to a copy of the tiny benchmark by files and
entries, as a PR adds it to the real one. No number here is a device number."""

import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import harness, peaks, peaks_kimi_linear as work, run, xplane_scopes
from benchmark.readers import kimi_linear_kernels
from benchmark.tests.conftest import TINY

NAME = "kimi-tiny.reason-tiny"
REAL = "kimi-linear-l16-ep16.reason-long-closed64"

CONFIG = {
    "source": "tiny rehearsal preset of the CPU tests, not a model",
    "vocab_size": 384, "hidden_size": 256, "intermediate_size": 256,
    "num_hidden_layers": 4, "num_attention_heads": 4, "kv_lora_rank": 128,
    "q_lora_rank": None, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "mla_use_nope": True, "first_k_dense_replace": 1, "num_experts": 8,
    "num_experts_published": 32, "experts_held": {"first": 8, "count": 8},
    "num_shared_experts": 1, "num_experts_per_token": 4, "moe_intermediate_size": 128,
    "routed_scaling_factor": 2.446, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_expert_group": 1, "topk_group": 1,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "linear_attn_config": {"full_attn_layers": [4], "kda_layers": [1, 2, 3], "num_heads": 2,
                           "head_dim": 128, "short_conv_kernel_size": 4},
    "reduced": [],
    "driver": "serve_kimi_linear", "reference": "kimi_linear",
    "engine": {"state_manager": {"max_ragged_sequence_count": 8, "max_ragged_batch_size": 32,
                                 "max_context": 256, "num_kv_blocks": 160, "kv_dtype": "fp"},
               "kv_cache": {"block_size": 8}},
    # at this size (seeds 2**31 + 11, 5, 7): the program's mean 0.0016-0.0032, the
    # int8 control on the same tokens 0.008-0.016 (the program's share of it
    # 0.18-0.23); the decay left out 0.44-0.48, beta 0.27-0.31, the scale
    # 0.046-0.065, the selecting bias 0.021-0.029
    "limits": {"served_gap_mean": 0.006, "served_gap_mean_vs_int8": 0.7},
}
TRAFFIC = {
    "generator": "requests", "loop": "closed", "clients": 6, "requests_per_client": 3,
    "shape_seed": 0, "order": "fixed",
    "prompt": {"dist": "lognormal", "median": 30, "sigma": 0.6, "min": 12, "max": 70},
    "output": {"dist": "lognormal", "median": 40, "sigma": 0.5, "min": 16, "max": 90},
    "stagger_cap": {"clients": 2, "remaining": 4},
    "check_requests": 4, "check_pad_to": 256, "check_max_new": 96, "trace_seconds": 1,
    "control_without": "decay",
}


@pytest.fixture
def bench(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(TINY, root)
    with open(root / "configs" / "kimi-tiny.json", "w") as f:
        json.dump(CONFIG, f)
    with open(root / "traffic" / "reason-tiny.json", "w") as f:
        json.dump(TRAFFIC, f)
    with open(root / "BENCHMARK.json") as f:
        b = json.load(f)
    b["configs"].append({"name": "kimi-tiny", "source": "test", "reduced": [],
                         "file": "configs/kimi-tiny.json", "why": "test"})
    b["workloads"].append({"name": NAME, "config": "kimi-tiny", "traffic": "reason-tiny",
                           "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append(NAME)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    return str(root / "BENCHMARK.json")


def test_the_real_cell_loads_through_the_harness_and_keeps_the_catalog_numbers():
    cell = harness.Cell(REAL)
    cfg, mix = cell.config, cell.traffic
    assert cell.chips == 1 and cfg["driver"] == "serve_kimi_linear"
    assert cfg["reference"] == "kimi_linear"
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in cell.per_layer}
    assert per_layer == {"kda_step_roofline.serve", "kda_chunk_roofline.serve",
                         "kda_device_share.serve", "nope_mla_roofline.serve",
                         "kda_chunk_tokens_share.serve",
                         "host_exposed_ms.serve", "host_prelaunch_ms.serve",
                         "fetch_tail_ms.serve", "dispatch_host_ms.serve"}
    for name in per_layer:
        with open(os.path.join(cell.metrics_dir, name + ".json")) as f:
            spec = json.load(f)
        assert hasattr(harness.load("readers", spec["reader"]), "read")
    entry = {c["name"]: c for c in cell.bench["configs"]}[cell.entry["config"]]
    assert entry["reduced"] == cfg["reduced"] \
        == ["num_hidden_layers", "num_experts", "linear_attn_config"]
    assert entry["source"] == cfg["source"]
    # every number of the catalog's row but the three cuts
    published = {"first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
                 "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
                 "mla_use_nope": True, "model_max_length": 1048576,
                 "model_type": "kimi_linear", "moe_intermediate_size": 1024,
                 "moe_layer_freq": 1, "moe_renormalize": True,
                 "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
                 "num_expert_group": 1, "num_experts_per_token": 8,
                 "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
                 "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
                 "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
                 "rope_theta": 10000, "routed_scaling_factor": 2.446,
                 "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
                 "v_head_dim": 128, "vocab_size": 163840, "num_experts_published": 256}
    assert {k: cfg[k] for k in published} == published
    lin = cfg["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["full_attn_layers"] == [4, 8, 12, 16]
    assert lin["kda_layers"] == [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15]
    assert (cfg["num_hidden_layers"], cfg["num_experts"]) == (16, 16)
    assert cfg["experts_held"] == {"first": 32, "count": 16}
    for key in ("deployment", "reduced_why", "assumed", "precision", "control_precision"):
        assert cfg[key]
    assert (mix["clients"], mix["requests_per_client"], mix["order"], mix["shape_seed"]) \
        == (64, 4, "fixed", 0)
    assert mix["prompt"] == {"dist": "lognormal", "median": 1024, "sigma": 0.8,
                             "min": 256, "max": 8192}
    assert mix["output"] == {"dist": "lognormal", "median": 8192, "sigma": 0.5,
                             "min": 2048, "max": 24576}
    assert mix["control_without"] == "decay"
    sm = cfg["engine"]["state_manager"]
    assert mix["prompt"]["max"] + mix["output"]["max"] <= sm["max_context"] == 32768
    assert mix["check_pad_to"] == sm["max_context"]
    assert (sm["max_ragged_sequence_count"], sm["max_ragged_batch_size"]) == (64, 512)
    assert cell.limit("served_gap_mean_vs_int8") < 1
    # the pool: 4 planes of 1,280 B a token, 5.37 GB; the slots 1.69 GB
    tokens = sm["num_kv_blocks"] * cfg["engine"]["kv_cache"]["block_size"]
    assert tokens == 1_048_576 and tokens * 1280 * 4 == 5_368_709_120
    slot = len(lin["kda_layers"]) * (work.state_bytes(cfg) + 3 * 3 * 4096 * 2)
    assert 1.68e9 < 65 * slot < 1.70e9


def test_the_pool_holds_every_first_answer_whole():
    """The count the cell's ``why`` rests on, from the lengths alone (one
    multiset in one order for every seed): what the window opens on, and that
    every client's first request fits the pool finished, so none is preempted."""
    from benchmark import traffic
    cell = harness.Cell(REAL)
    load = traffic.requests(cell.traffic, 1, 45, cell.config["vocab_size"])
    sm, bs = cell.config["engine"]["state_manager"], 64
    firsts = [(len(q[0][0]), q[0][1]) for q in load["clients"]]
    start = [p + int(o * (1 - ph)) for (p, o), ph in zip(firsts, load["phase"])]
    assert 300_000 < sum(start) < 400_000 and max(start) < 20_000
    whole = sum(-(-(p + o) // bs) + 1 for p, o in firsts)
    assert whole < 0.7 * sm["num_kv_blocks"]
    assert all(len(p) + o <= sm["max_context"] for q in load["clients"] for p, o in q)


def test_cell_end_to_end_and_controls(bench, cpu_device, tmp_path, capsys):
    cell = harness.Cell(NAME, bench)
    devices, info = cpu_device
    result = run.run_cell(cell, 2**31 + 11, 6.0, 0, devices, info, time.perf_counter(),
                          str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "compared " in out and "staggered_start" in out and "device_counters" in out

    mod = harness.load("drivers", "serve_kimi_linear")
    driver = mod.Driver(cell, 5, harness.Recorder(), devices=devices, seconds=6.0)
    groups = driver.engine.kv_stats()["groups"]
    assert set(groups) == {"kv", "state"} and groups["kv"]["leaves"] == 1
    assert groups["kv"]["bytes"] == 1 * 161 * 8 * 256 * 2         # one MLA layer's plane
    # every client decoding on its slot, the emitted part in its context
    assert driver.sched.state_slots > 0
    rows_before = driver.sched.expert_rows
    step_before, chunk_before = driver.sched.kda_step_rows, driver.sched.kda_chunk_tokens
    tokens_before = driver.sched.real_tokens
    facts = driver.window(6.0, str(tmp_path))
    counts = facts["device_counters"]
    sched = driver.sched
    assert sched.expert_rows == sched.real_tokens * 4 * 3 and sched.expert_rows_padded == 0
    assert sched.kda_step_rows - step_before + sched.kda_chunk_tokens - chunk_before \
        == sched.real_tokens - tokens_before
    assert sched.kda_step_rows > step_before
    # the device counted what the host knows (every real token routed 4 rows an
    # expert layer) and what it cannot: the rows that landed on a held expert
    assert counts["routed_rows"] == sched.expert_rows - rows_before
    assert counts["zero_rows"] == 0
    assert 0 < counts["held_rows"] < counts["routed_rows"]
    assert counts["held_rows"] >= counts["experts_hit"] > 0
    rounds = [a for n, _, _, a in driver.rec.spans if n == "round"]
    assert all("attn_rows" in a for a in rounds)
    driver.release()
    sound = {n: v for n, v, _ in driver.compare()}
    control = {n: v for n, v, _ in driver.control()}
    names = {"served_gap.mean", "served_gap.mean_vs_int8"}
    assert set(sound) == names
    assert set(control) == names | {"without_decay." + n for n in names}
    limit = cell.limit("served_gap_mean")
    assert sound["served_gap.mean"] <= limit < control["without_decay.served_gap.mean"]
    assert sound["served_gap.mean_vs_int8"] < cell.limit("served_gap_mean_vs_int8") \
        < 1.0 == control["served_gap.mean_vs_int8"]
    assert control["without_decay.served_gap.mean_vs_int8"] > 1.0


def test_every_term_the_reference_can_change_moves_the_served_gap(bench, cpu_device, tmp_path):
    cell = harness.Cell(NAME, bench)
    mod = harness.load("drivers", "serve_kimi_linear")
    driver = mod.Driver(cell, 7, harness.Recorder(), devices=cpu_device[0], seconds=4.0)
    driver.window(4.0, str(tmp_path))
    driver.release()
    limit = cell.limit("served_gap_mean")
    # ``nope`` (rotary applied to the one MLA layer of four, at contexts under
    # 160 tokens) reads 0.002-0.005 here, among the program's own: the tier-1
    # test holds it in logits (tests/test_kimi_linear_serving.py)
    for term in ("beta", "routed_scale", "bias"):
        control = {n: v for n, v, _ in driver._checks((f"without:{term}",))[f"without:{term}"]}
        assert control["served_gap.mean"] > limit, term
    with pytest.raises(ValueError, match="unknown term"):
        driver._checks(("without:the_router",))


def test_the_references_blocks_agree_with_its_unblocked_form():
    import jax
    import jax.numpy as jnp
    from benchmark import weights
    from benchmark.references import kimi_linear as reference
    cfg = {k: v for k, v in CONFIG.items() if k not in ("engine", "limits")}
    tree = reference.finish(weights.make_params(3, reference.param_spec(cfg)))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, cfg["vocab_size"], 70), jnp.int32)
    whole = np.asarray(reference.full_logits(cfg, tree, ids, q_block=512))
    blocks = np.asarray(reference.full_logits(cfg, tree, ids, q_block=16))   # 5 blocks, padded
    assert np.isfinite(whole).all() and float(np.max(np.abs(whole - blocks))) < 2e-5
    # the chip's form (weights regenerated a layer and an expert at a time)
    # gives the hidden states the whole tree gives
    with jax.default_matmul_precision("highest"):
        _, _, x, ties = reference._hidden(cfg, 3, ids[None], "f32")
        x = reference._rms(x[0], tree["norm"]["scale"], cfg["rms_norm_eps"])
        logits = np.asarray(x @ tree["lm_head"].astype(jnp.float32).T)
    assert float(np.max(np.abs(logits - whole))) < 2e-4
    assert 0 <= ties < 0.2
    # the decay's leaves, mapped: a state that remembers tens to thousands of tokens
    attn = tree["layers_0"]["self_attn"]
    assert 0 <= float(attn["A_log"].min()) and float(attn["A_log"].max()) <= np.log(16) + 1e-6
    dt = np.log1p(np.exp(np.asarray(attn["dt_bias"], np.float64)))
    assert 0.99e-3 < dt.min() and dt.max() < 1.01e-1


def test_the_drivers_weights_are_the_harnesss_value_for_value():
    import jax
    from benchmark import weights
    from benchmark.drivers import serve_kanana2
    from benchmark.references import kimi_linear as reference
    cfg = {k: v for k, v in CONFIG.items() if k not in ("engine", "limits")}
    spec = reference.param_spec(cfg)
    want = weights.make_params(2**31 + 5, spec)
    got = serve_kanana2.make_params(2**31 + 5, spec, reference)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                      err_msg=str(path))


# -- the work functions against a hand count ---------------------------------------

PUBLISHED = {"num_attention_heads": 32, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
             "qk_rope_head_dim": 64, "v_head_dim": 128, "hidden_size": 2304,
             "num_hidden_layers": 16,
             "linear_attn_config": {"full_attn_layers": [4, 8, 12, 16],
                                    "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15],
                                    "num_heads": 32, "head_dim": 128,
                                    "short_conv_kernel_size": 4}}


def test_the_work_at_the_cells_shapes_by_hand():
    """A sequence's state in one layer is 32 x 128 x 128 x 4 B = 2,097,152 B;
    a token brings 32 heads x (5 x 128 + 1) x 4 B = 82,048 B through a layer.
    A [64, 1] round over 12 KDA layers: 64 x (2 x 2,097,152 + 82,048) x 12 =
    3.28 GB, 4.0 ms at 819 GB/s, against 0.45 GFLOP a layer: memory-bound by
    two hundred times. A 512-token chunk of one row: a chunk-head of 64 tokens
    is 8,404,992 operations, so 512 tokens x 32 heads x 12 layers = 25.8
    GFLOP (0.13 ms) beside 0.55 GB (0.68 ms): memory-bound too, by the
    float32 vectors. The latent read: Kanana-2's count over 4 planes."""
    v5e = peaks.peaks_for("TPU v5e")
    assert work.state_bytes(PUBLISHED) == 2_097_152
    assert work.token_bytes(PUBLISHED) == 82_048
    assert work.kda_step_bytes(PUBLISHED, 64) == 12 * 64 * (2 * 2_097_152 + 82_048)
    assert work.kda_step_flops(PUBLISHED, 64) == 12 * 64 * 32 * 7 * 128 * 128
    least, bound = peaks.roofline_seconds(work.kda_step_flops(PUBLISHED, 64),
                                          work.kda_step_bytes(PUBLISHED, 64), v5e)
    assert bound == "memory" and least == pytest.approx(3_284_238_336 / 819e9)
    a_chunk = 64 * 63 * 128 + 64 * 65 * 128 + 4 * 64 * 128 * 128 \
        + 64 * 63 * 128 + 64 * 65 * 128 + 2 * 64 * 128 * 128 + 128 * 128
    assert a_chunk == 8_404_992
    assert work.kda_chunk_flops(PUBLISHED, 512) == 12 * 32 * 8 * a_chunk
    assert work.kda_chunk_bytes(PUBLISHED, 1, 512) == 12 * (2 * 2_097_152 + 512 * 82_048)
    assert peaks.roofline_seconds(work.kda_chunk_flops(PUBLISHED, 512),
                                  work.kda_chunk_bytes(PUBLISHED, 1, 512), v5e)[1] == "memory"
    from benchmark import peaks_kanana2
    mla = work.mla_config(PUBLISHED)
    assert mla["num_hidden_layers"] == 4
    assert peaks_kanana2.mla_attn_bytes(mla, 1, 4096) == 4 * (4096 * 1152 + 32 * 320 * 2)


# -- the readers on a made-up device line ---------------------------------------------

class _Cell:
    name = NAME
    config = PUBLISHED


def _ctx(device_events, rounds, builds):
    window = ("bench/window", 0, 10_000_000_000)
    loaded = {"spans": [("ds/serving/build", 1000 + i, 2000 + i, a) for i, a in enumerate(builds)],
              "window": (0, 10_000_000_000), "table": [], "offset": None}
    return {"cell": _Cell, "trace": {"devices": {"/device:TPU:0": device_events},
                                     "spans": [window]},
            "facts": {}, "spans": [("round", 0.0, 0.1, a) for a in rounds],
            "program_spans": loaded, "summary": {"busy_s": 0.5},
            "peaks": peaks.peaks_for("TPU v5e"), "notes": [], "trace_path": "unused"}


def _read(ctx, name):
    with open(os.path.join(harness.HERE, "metrics", name + ".json")) as f:
        spec = json.load(f)
    return harness.load("readers", spec["reader"]).read(ctx, spec["params"])


def test_readers_turn_events_and_spans_into_shares_under_100(monkeypatch):
    ms = 1_000_000
    step = "%kda_step.3 = (f32[64,32,128]{2,1,0}, f32[780,32,128,128]{3,2,1,0}) custom-call("
    chunk = "%kda_chunk.5 = (f32[1,512,4096]{2,1,0}, f32[780,32,128,128]{3,2,1,0}) custom-call("
    mla = "%paged_mla.3 = bf16[64,1,32,512]{3,2,1,0} custom-call("
    proj = "%fusion.5 = bf16[64,12288]{1,0} fusion("
    gmm = "%gmm.7 = f32[512,1024]{1,0} custom-call("
    other = "%fusion.1 = bf16[64,163840]{1,0} fusion("
    events = [(step, 0, 6 * ms), (step, 10 * ms, 16 * ms), (chunk, 20 * ms, 30 * ms),
              (mla, 40 * ms, 42 * ms), (mla, 50 * ms, 52 * ms), (proj, 60 * ms, 64 * ms),
              (gmm, 70 * ms, 78 * ms), (other, 120 * ms, 200 * ms)]
    base = "jit(ragged_forward)/jit(_kda_layer)/"
    scopes = {step: base + "kda/kda_step/pallas_call", chunk: base + "kda/kda_chunk/pallas_call",
              proj: base + "kda/kda_proj/dot_general",
              mla: "jit(ragged_forward)/jit(_mla_layer)/mla_attn/mla_read/paged_mla/pallas_call",
              gmm: base + "moe_ffn/moe_ffn_gmm/jit(gmm)/pallas_call",
              other: "jit(ragged_forward)/dot_general"}
    monkeypatch.setattr(xplane_scopes, "op_names", lambda path: scopes)
    rounds = [{"attn_rows": [(1, 4096)] * 64, "decode_rows": 64}] * 2 \
        + [{"attn_rows": [(512, 512)], "decode_rows": 0}]
    decode = {"real_tokens": 64, "seqs": 64, "kda_step_rows": 64, "kda_chunk_tokens": 0,
              "state_slots": 64, "latent_pages": 4100}
    builds = [decode, decode, dict(decode, real_tokens=512, seqs=1, kda_step_rows=0,
                                   kda_chunk_tokens=512)]
    ctx = _ctx(events, rounds, builds)
    got = _read(ctx, "kda_step_roofline.serve")
    assert got == pytest.approx(100 * work.kda_step_bytes(PUBLISHED, 128) / 819e9 / 0.012)
    assert 50 < got < 100 and "128 rows in 2 dispatches" in ctx["notes"][-1]
    got = _read(ctx, "kda_chunk_roofline.serve")
    assert got == pytest.approx(100 * work.kda_chunk_bytes(PUBLISHED, 1, 512) / 819e9 / 0.010)
    assert 1 < got < 100
    got = _read(ctx, "kda_device_share.serve")
    assert got == pytest.approx(100 * 0.026 / 0.5)
    note = ctx["notes"][-1]
    assert "kda_step 0.0120, kda_chunk 0.0100, kda_proj 0.0040" in note
    assert "mla_attn 0.0040 s" in note and "moe_ffn 0.0080 s" in note
    got = _read(ctx, "nope_mla_roofline.serve")
    assert got is not None and 0 < got < 100
    assert _read(ctx, "kda_chunk_tokens_share.serve") == pytest.approx(100 * 512 / 640)


def test_readers_give_none_for_a_program_without_the_events_scopes_or_attributes(monkeypatch):
    """What the parent commit gives under this PR's benchmark files: nothing
    to read, so nothing is reported and nothing raises."""
    monkeypatch.setattr(xplane_scopes, "op_names", lambda path: {})
    ctx = _ctx([("%fusion.1 = bf16[8]{0} fusion(", 0, 1000)], [{"decode_rows": 4}],
               [{"real_tokens": 4, "seqs": 4}])
    for name in ("kda_step_roofline.serve", "kda_chunk_roofline.serve",
                 "kda_device_share.serve", "nope_mla_roofline.serve",
                 "kda_chunk_tokens_share.serve"):
        assert _read(ctx, name) is None, name
    ctx["trace"] = None
    assert _read(ctx, "kda_step_roofline.serve") is None
    assert kimi_linear_kernels.read(ctx, {"work": "chunk_tokens_share"}) is None
