"""CPU rehearsal of the LongCat-Flash-Chat serving cell at a tiny size: the
new driver, reference, traffic keys and readers end to end (the latent walk
and the grouped GEMM in interpret mode), the device counters against the
reference's own count, the int8 control and the zero experts' term left out
coming out as not correct; the real cell's files through ``harness.Cell``;
``peaks_longcat_flash``'s counts against a hand count; the readers on a made-up
device line and without what they read; the reference's blocks against its
unblocked form. The cell is added to a copy of the tiny benchmark by files and
entries, as a PR adds it to the real one. No number here is a device number."""

import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import harness, peaks, peaks_longcat_flash as work, run, xplane_scopes
from benchmark.readers import longcat_flash_kernels
from benchmark.tests.conftest import TINY

NAME = "longcat-tiny.agent-tiny"
REAL = "longcat-flash-l4-ep32.agent-turns-closed64"

CONFIG = {
    "source": "tiny rehearsal preset of the CPU tests, not a model",
    "vocab_size": 384, "hidden_size": 256, "ffn_hidden_size": 256,
    "expert_ffn_hidden_size": 128, "num_layers": 2, "num_attention_heads": 4,
    "kv_lora_rank": 128, "q_lora_rank": 64, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
    "v_head_dim": 32, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 8, "n_routed_experts_published": 32,
    "experts_held": {"first": 8, "count": 8}, "zero_expert_num": 16,
    "zero_expert_type": "identity", "moe_topk": 6, "max_position_embeddings": 512,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "attention_method": "MLA",
    "reduced": [],
    "driver": "serve_longcat_flash", "reference": "longcat_flash",
    "engine": {"state_manager": {"max_ragged_sequence_count": 8, "max_ragged_batch_size": 32,
                                 "max_context": 256, "num_kv_blocks": 160, "kv_dtype": "fp"},
               "kv_cache": {"block_size": 8}},
    # at this size (seeds 2**31 + 11, 5, 7): the program's mean 0.0002-0.0011,
    # the int8 control on the same tokens 0.004-0.009 (the program's share of it
    # 0.03-0.25); left out, the zero experts' term 0.11-0.25
    "limits": {"served_gap_mean": 0.003, "served_gap_mean_vs_int8": 0.7},
}
TRAFFIC = {
    "generator": "requests", "loop": "closed", "clients": 6, "requests_per_client": 3,
    "shape_seed": 0, "order": "fixed",
    "prompt": {"dist": "lognormal", "median": 50, "sigma": 0.6, "min": 20, "max": 110},
    "output": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8, "max": 60},
    "check_requests": 4, "check_pad_to": 256, "check_max_new": 64, "trace_seconds": 1,
    "control_without": "zero_experts",
}


@pytest.fixture
def bench(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(TINY, root)
    with open(root / "configs" / "longcat-tiny.json", "w") as f:
        json.dump(CONFIG, f)
    with open(root / "traffic" / "agent-tiny.json", "w") as f:
        json.dump(TRAFFIC, f)
    with open(root / "BENCHMARK.json") as f:
        b = json.load(f)
    b["configs"].append({"name": "longcat-tiny", "source": "test", "reduced": [],
                         "file": "configs/longcat-tiny.json", "why": "test"})
    b["workloads"].append({"name": NAME, "config": "longcat-tiny", "traffic": "agent-tiny",
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
    assert cell.chips == 1 and cfg["driver"] == "serve_longcat_flash"
    assert cfg["reference"] == "longcat_flash"
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in cell.per_layer}
    assert per_layer == {"scmoe_mla_roofline.serve", "scmoe_gmm_roofline.serve",
                         "scmoe_dense_ffn_roofline.serve", "scmoe_moe_device_share.serve",
                         "zero_expert_rows_share.serve",
                         "host_exposed_ms.serve", "host_prelaunch_ms.serve",
                         "fetch_tail_ms.serve", "dispatch_host_ms.serve"}
    for name in per_layer:
        with open(os.path.join(cell.metrics_dir, name + ".json")) as f:
            spec = json.load(f)
        assert hasattr(harness.load("readers", spec["reader"]), "read")
    entry = {c["name"]: c for c in cell.bench["configs"]}[cell.entry["config"]]
    assert entry["reduced"] == cfg["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    # every number of the catalog's row but the three cuts, which state what
    # they were cut from
    published = {"attention_bias": False, "hidden_size": 6144, "ffn_hidden_size": 12288,
                 "expert_ffn_hidden_size": 2048, "num_attention_heads": 64, "kv_lora_rank": 512,
                 "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "qk_nope_head_dim": 128, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
                 "routed_scaling_factor": 6, "max_position_embeddings": 131072,
                 "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
                 "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12,
                 "n_routed_experts_published": 512, "num_layers_published": 28,
                 "vocab_size_published": 131072}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (4, 16, 16384)
    share = cfg["experts_held"]
    assert share["count"] == 16 and 0 < share["first"] <= 512 - 16
    for key in ("deployment", "reduced_why", "assumed", "precision", "control_precision"):
        assert cfg[key]
    assert (mix["clients"], mix["requests_per_client"], mix["order"], mix["shape_seed"]) \
        == (64, 12, "fixed", 0)
    assert mix["prompt"] == {"dist": "lognormal", "median": 2048, "sigma": 0.7,
                             "min": 256, "max": 8192}
    assert mix["output"] == {"dist": "lognormal", "median": 256, "sigma": 0.6,
                             "min": 32, "max": 1024}
    assert mix["control_without"] == "zero_experts"
    sm = cfg["engine"]["state_manager"]
    assert mix["prompt"]["max"] + mix["output"]["max"] <= sm["max_context"]
    assert mix["check_pad_to"] == mix["prompt"]["max"] + mix["output"]["max"]
    assert (sm["max_ragged_sequence_count"], sm["max_ragged_batch_size"]) == (64, 512)
    assert cell.limit("served_gap_mean_vs_int8") < 1
    # the pool: 8 planes of 1,280 B a token, ~3.2 GB
    tokens = sm["num_kv_blocks"] * cfg["engine"]["kv_cache"]["block_size"]
    assert 3.0e9 < tokens * 1280 * 2 * cfg["num_layers"] < 3.4e9


def test_no_client_runs_dry_and_the_pool_holds_the_window():
    """The count the cell's ``why`` rests on, from the lengths alone (one
    multiset in one order for every seed): the state the window opens on and
    2,000 rounds from it by the scheduler's rule."""
    from benchmark import traffic
    from benchmark.drivers.serve_kanana2 import _served_start
    cell = harness.Cell(REAL)
    load = traffic.requests(cell.traffic, 1, 45, cell.config["vocab_size"])
    sm = cell.config["engine"]["state_manager"]
    lengths = [[(len(p), o) for p, o in q] for q in load["clients"]]
    start, _ = _served_start(load["clients"], load["phase"], sm["max_ragged_batch_size"])
    live = [{"client": c, "at": at, "prompt": lengths[c][at % 12][0],
             "pos": lengths[c][at % 12][0], "n": n, "max_new": m}
            for c, (at, n, m) in enumerate(start)]
    pages, decode, finished = 0, [], 0
    for _ in range(2000):
        left = sm["max_ragged_batch_size"]
        decode.append(sum(r["n"] > 0 for r in live))
        for r in live:
            if r["n"]:
                r["n"] += 1
                left -= 1
        for r in live:
            if r["pos"] < r["prompt"] and left > 0:
                take = min(left, r["prompt"] - r["pos"])
                r["pos"] += take
                left -= take
                r["n"] = int(r["pos"] == r["prompt"])
        for r in [r for r in live if r["n"] >= r["max_new"]]:
            live.remove(r)
            finished += 1
            prompt, max_new = lengths[r["client"]][(r["at"] + 1) % 12]
            live.append({"client": r["client"], "at": r["at"] + 1, "prompt": prompt,
                         "pos": 0, "n": 0, "max_new": max_new})
        pages = max(pages, sum(-(-(r["pos"] + r["n"]) // 64) + 1 for r in live))
    assert pages < 0.7 * sm["num_kv_blocks"]            # no preemption
    assert 45 < np.mean(decode) < 58 and finished / 2000 > 0.15


def test_cell_end_to_end_and_controls(bench, cpu_device, tmp_path, capsys):
    cell = harness.Cell(NAME, bench)
    devices, info = cpu_device
    result = run.run_cell(cell, 2**31 + 11, 6.0, 0, devices, info, time.perf_counter(),
                          str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "compared " in out and "took a zero expert" in out
    counted = json.loads(next(l for l in out.splitlines() if "device_counters" in l))
    counted = counted["device_counters"]
    said = float(out.split("routed rows ")[1].split(" %")[0])
    # other tokens of the same traffic under the same weights: within a few points here
    assert abs(100 * counted["zero_rows_share"] - said) < 6.0

    mod = harness.load("drivers", "serve_longcat_flash")
    driver = mod.Driver(cell, 5, harness.Recorder(), devices=devices, seconds=6.0)
    groups = driver.engine.kv_stats()["groups"]
    assert set(groups) == {"kv"} and groups["kv"]["leaves"] == 1
    assert groups["kv"]["bytes"] == 2 * 2 * 161 * 8 * 256 * 2      # two planes a layer
    rows_before = driver.sched.expert_rows
    facts = driver.window(6.0, str(tmp_path))
    counts = facts["device_counters"]
    sched = driver.sched
    assert sched.expert_rows == sched.real_tokens * 6 * 2 and sched.expert_rows_padded == 0
    # the device counted what the host knows (every real token routed 6 rows a
    # layer) and what it cannot: the rows' three outcomes
    assert counts["routed_rows"] == sched.expert_rows - rows_before
    assert 0 < counts["zero_rows"] < counts["routed_rows"]
    assert 0 < counts["held_rows"] < counts["routed_rows"] - counts["zero_rows"]
    assert counts["held_rows"] >= counts["experts_hit"] > 0
    assert counts["experts_hit"] <= 8 * 2 * counts["dispatches"]
    rounds = [a for n, _, _, a in driver.rec.spans if n == "round"]
    assert all("attn_rows" in a for a in rounds)
    driver.release()
    sound = {n: v for n, v, _ in driver.compare()}
    control = {n: v for n, v, _ in driver.control()}
    names = {"served_gap.mean", "served_gap.mean_vs_int8"}
    assert set(sound) == names
    assert set(control) == names | {"without_zero_experts." + n for n in names}
    limit = cell.limit("served_gap_mean")
    assert sound["served_gap.mean"] <= limit < control["without_zero_experts.served_gap.mean"]
    assert sound["served_gap.mean_vs_int8"] < cell.limit("served_gap_mean_vs_int8") \
        < 1.0 == control["served_gap.mean_vs_int8"]
    assert control["without_zero_experts.served_gap.mean_vs_int8"] > 1.0


def test_every_term_the_reference_can_leave_out_moves_the_served_gap(bench, cpu_device, tmp_path):
    cell = harness.Cell(NAME, bench)
    mod = harness.load("drivers", "serve_longcat_flash")
    driver = mod.Driver(cell, 7, harness.Recorder(), devices=cpu_device[0], seconds=4.0)
    driver.window(4.0, str(tmp_path))
    driver.release()
    limit = cell.limit("served_gap_mean")
    for term in ("routed_scale", "q_scale", "kv_scale", "k_pe"):
        control = {n: v for n, v, _ in driver._checks((f"without:{term}",))[f"without:{term}"]}
        assert control["served_gap.mean"] > limit, term
    with pytest.raises(ValueError, match="unknown term"):
        driver._checks(("without:the_router",))


def test_a_bfloat16_router_chooses_other_experts_at_the_published_width():
    """The control ``router_bf16`` (the router's product, softmax and bias in
    bfloat16) at the router's published 768 columns of 6144: the 12th and 13th
    largest probabilities lie ~2 % apart, bfloat16 keeps 0.4 %, so the chosen
    set changes for a good share of tokens (the tiny preset's 24 columns are
    too few to show it, so the loop above leaves it out)."""
    import jax
    import jax.numpy as jnp
    from benchmark.references import longcat_flash as reference
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(256, 6144)), jnp.float32)
    p = {"moe": {"router": {
        "kernel": jnp.asarray(rng.normal(size=(6144, 768)) / np.sqrt(6144), jnp.float32),
        "bias": jnp.asarray(rng.uniform(-5.2e-5, 5.2e-5, 768), jnp.float32)}}}
    c = {"moe_topk": 12, "routed_scaling_factor": 6}
    with jax.default_matmul_precision("highest"):
        gate, idx = reference.router(c, "f32", (), p, h)
        _, low = reference.router(c, "f32", ("router_bf16",), p, h)
        _, plain = reference.router(c, "f32", ("bias",), p, h)
    changed = lambda other: float(np.mean(np.any(
        np.sort(np.asarray(other), -1) != np.sort(np.asarray(idx), -1), -1)))
    assert changed(low) > 0.05
    assert 0.01 < changed(plain) < 0.25          # the bias selects, for some tokens
    w = np.take_along_axis(np.asarray(gate), np.asarray(idx), -1)
    probs = np.asarray(jax.nn.softmax(h @ p["moe"]["router"]["kernel"], -1))
    np.testing.assert_allclose(w, 6 * np.take_along_axis(probs, np.asarray(idx), -1), rtol=2e-4)
    assert 0.25 < float(np.mean(np.asarray(idx) >= 512)) < 0.42


def test_the_references_blocks_agree_with_its_unblocked_form():
    import jax
    import jax.numpy as jnp
    from benchmark import weights
    from benchmark.references import longcat_flash as reference
    cfg = {k: v for k, v in CONFIG.items() if k not in ("engine", "limits")}
    tree = weights.make_params(3, reference.param_spec(cfg))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, cfg["vocab_size"], 70), jnp.int32)
    whole = np.asarray(reference.full_logits(cfg, tree, ids, q_block=512))
    blocks = np.asarray(reference.full_logits(cfg, tree, ids, q_block=16))   # 5 blocks, padded
    assert np.isfinite(whole).all() and float(np.max(np.abs(whole - blocks))) < 2e-5
    # the chip's form (weights regenerated a layer and an expert at a time)
    # gives the hidden states the whole tree gives, and counts the real positions
    with jax.default_matmul_precision("highest"):
        valid = jnp.arange(70)[None] < 64
        _, _, x, routing = reference._hidden(cfg, 3, ids[None], valid, "f32")
        x = reference._rms(x[0], tree["norm"]["scale"], cfg["rms_norm_eps"])
        logits = np.asarray(x @ tree["lm_head"].astype(jnp.float32).T)
    assert float(np.max(np.abs(logits - whole))) < 2e-4
    assert routing["routed_rows"] == 64 * 6 * 2
    assert 0 < routing["zero_rows"] < routing["routed_rows"] - routing["held_rows"]
    assert 0 <= routing["near_ties"] < 0.2 * 64 * 2


def test_the_drivers_weights_are_the_harnesss_value_for_value():
    import jax
    from benchmark import weights
    from benchmark.drivers import serve_kanana2
    from benchmark.references import longcat_flash as reference
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

PUBLISHED = {"num_layers": 4, "num_attention_heads": 64, "kv_lora_rank": 512,
             "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
             "hidden_size": 6144, "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
             "n_routed_experts": 16}


def test_the_work_at_the_cells_shapes_by_hand():
    """A decode row at context 4,096 over 8 planes: 4,096 x 1,152 B of latent
    rows a plane plus q and o of 64 x 320 x 2 B; absorbed 64 x 4,096 pairs x
    1,088 x 2 operations a plane, the lesser; memory-bound, at 121 flop/B of
    row where Kanana-2's 32 heads give 60. An expert is 3 x 6144 x 2048 x 2 B
    = 75.5 MB; 128 rows on 16 experts hit: 1.21 GB and 9.9 GFLOP a layer,
    memory-bound by 30x. The two dense FFNs of 4 layers on a 460-token chunk:
    2 x 4 x 6 x 6144 x 12288 x 460 = 1.667 TFLOP beside 3.62 GB of weights:
    compute-bound; on a 53-row decode dispatch memory-bound."""
    v5e = peaks.peaks_for("TPU v5e")
    assert work.mla_config(PUBLISHED)["num_hidden_layers"] == 8
    assert work.mla_attn_bytes(PUBLISHED, 1, 4096) == 8 * (4096 * 1152 + 64 * 320 * 2)
    assert work.mla_attn_flops(PUBLISHED, 1, 4096) == 8 * 64 * 4096 * 1088 * 2
    _, bound = peaks.roofline_seconds(work.mla_attn_flops(PUBLISHED, 1, 4096),
                                      work.mla_attn_bytes(PUBLISHED, 1, 4096), v5e)
    assert bound == "memory"
    assert work.expert_bytes(PUBLISHED) == 75_497_472
    assert work.moe_gmm_bytes(PUBLISHED, 16, 128) == 16 * 75_497_472 + 2 * 128 * 6144 * 2
    assert work.moe_gmm_flops(PUBLISHED, 128) == 6 * 6144 * 2048 * 128
    least, bound = peaks.roofline_seconds(work.moe_gmm_flops(PUBLISHED, 128),
                                          work.moe_gmm_bytes(PUBLISHED, 16, 128), v5e)
    assert bound == "memory" and least == pytest.approx(1_211_105_280 / 819e9)
    assert work.dense_ffn_flops(PUBLISHED, 460) == 2 * 4 * 6 * 6144 * 12288 * 460
    assert work.dense_ffn_bytes(PUBLISHED, 460) == 2 * 4 * (3 * 6144 * 12288 + 2 * 460 * 6144) * 2
    assert peaks.roofline_seconds(work.dense_ffn_flops(PUBLISHED, 460),
                                  work.dense_ffn_bytes(PUBLISHED, 460), v5e)[1] == "compute"
    assert peaks.roofline_seconds(work.dense_ffn_flops(PUBLISHED, 53),
                                  work.dense_ffn_bytes(PUBLISHED, 53), v5e)[1] == "memory"


# -- the readers on a made-up device line ---------------------------------------------

class _Cell:
    name = NAME
    config = PUBLISHED


def _ctx(device_events, rounds, builds, counters):
    window = ("bench/window", 0, 10_000_000_000)
    loaded = {"spans": [("ds/serving/build", 1000 + i, 2000 + i, a) for i, a in enumerate(builds)],
              "window": (0, 10_000_000_000), "table": [], "offset": None}
    return {"cell": _Cell, "trace": {"devices": {"/device:TPU:0": device_events},
                                     "spans": [window]},
            "facts": {"device_counters": counters} if counters else {},
            "spans": [("round", 0.0, 0.1, a) for a in rounds], "program_spans": loaded,
            "summary": {"busy_s": 0.5}, "peaks": peaks.peaks_for("TPU v5e"), "notes": [],
            "trace_path": "unused"}


def _read(ctx, name):
    with open(os.path.join(harness.HERE, "metrics", name + ".json")) as f:
        spec = json.load(f)
    return harness.load("readers", spec["reader"]).read(ctx, spec["params"])


def test_readers_turn_events_counters_and_spans_into_shares_under_100(monkeypatch):
    ms = 1_000_000
    mla = "%paged_mla.3 = bf16[64,1,64,512]{3,2,1,0} custom-call("
    gmm = "%gmm.7 = f32[768,2048]{1,0} custom-call("
    ffn = "%fusion.5 = bf16[64,12288]{1,0} fusion("
    zero = "%fusion.6 = bf16[64,6144]{1,0} fusion("
    other = "%fusion.1 = bf16[64,16384]{1,0} fusion("
    events = [(mla, 0, 4 * ms), (mla, 10 * ms, 14 * ms), (gmm, 20 * ms, 30 * ms),
              (ffn, 40 * ms, 60 * ms), (zero, 70 * ms, 71 * ms), (other, 120 * ms, 200 * ms)]
    base = "jit(ragged_forward)/jit(_layer)/scmoe_layer/"
    scopes = {mla: base + "mla_attn_1/mla_read/paged_mla/pallas_call",
              gmm: base + "moe_ffn/moe_ffn_gmm/jit(gmm)/pallas_call",
              ffn: base + "dense_ffn_0/dot_general", zero: base + "moe_ffn/moe_zero/mul",
              other: "jit(ragged_forward)/dot_general"}
    monkeypatch.setattr(xplane_scopes, "op_names", lambda path: scopes)
    rounds = [{"attn_rows": [(1, 4096)] * 64, "decode_rows": 64}] * 2
    builds = [{"real_tokens": 64, "latent_pages": 4100, "seqs": 64, "expert_rows": 3072}] * 2
    counters = {"routed_rows": 6144, "zero_rows": 2048, "held_rows": 192, "experts_hit": 96,
                "dispatches": 2}
    ctx = _ctx(events, rounds, builds, counters)
    got = _read(ctx, "scmoe_mla_roofline.serve")
    least = 2 * 64 * work.mla_attn_bytes(PUBLISHED, 1, 4096) / 819e9
    assert got == pytest.approx(100 * least / 0.008) and 50 < got < 100
    got = _read(ctx, "scmoe_gmm_roofline.serve")
    assert got == pytest.approx(100 * work.moe_gmm_bytes(PUBLISHED, 96, 192) / 819e9 / 0.010)
    assert 50 < got < 100 and "12.00 of 16 held experts hit" in ctx["notes"][-1]
    got = _read(ctx, "scmoe_dense_ffn_roofline.serve")
    assert got == pytest.approx(100 * 2 * work.dense_ffn_bytes(PUBLISHED, 64) / 819e9 / 0.020)
    assert 40 < got < 100
    got = _read(ctx, "scmoe_moe_device_share.serve")
    assert got == pytest.approx(100 * 0.011 / 0.5)
    assert "moe_ffn_gmm 0.0100, moe_zero 0.0010" in ctx["notes"][-1]
    assert _read(ctx, "zero_expert_rows_share.serve") == pytest.approx(100 / 3)
    # uniform routing would have read every expert in every layer and dispatch:
    # the counters say 12 of 16, so the share is counted from less work
    assert work.moe_gmm_bytes(PUBLISHED, 96, 192) < work.moe_gmm_bytes(PUBLISHED, 128, 192)


def test_readers_give_none_for_a_program_without_the_counters_events_or_scopes(monkeypatch):
    """What the parent commit gives under this PR's benchmark files: nothing
    to read, so nothing is reported and nothing raises."""
    monkeypatch.setattr(xplane_scopes, "op_names", lambda path: {})
    ctx = _ctx([("%fusion.1 = bf16[8]{0} fusion(", 0, 1000)], [{"decode_rows": 4}],
               [{"real_tokens": 4}], None)
    for name in ("scmoe_mla_roofline.serve", "scmoe_gmm_roofline.serve",
                 "scmoe_dense_ffn_roofline.serve", "scmoe_moe_device_share.serve",
                 "zero_expert_rows_share.serve"):
        assert _read(ctx, name) is None, name
    ctx["trace"] = None
    assert _read(ctx, "scmoe_gmm_roofline.serve") is None
    assert longcat_flash_kernels.read(ctx, {"work": "zero_share"}) is None
