"""CPU rehearsal of the Keye-VL-2.0 serving cell at a tiny size: the new
driver, reference, traffic keys and readers end to end (the index scores, the
threshold, the masked walk and the grouped GEMM in interpret mode), the int8
control, the selection left out and a window in its place each coming out as
not correct; the real cell's files through ``harness.Cell``; ``peaks_keye``'s
counts against a hand count; the readers on a made-up device line; the
reference's blocks against its unblocked form. The cell is added to a copy of
the tiny benchmark by files and entries, as a PR adds it to the real one. No
number here is a device number."""

import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import harness, peaks, peaks_keye as work, run, xplane_scopes
from benchmark.readers import keye_kernels
from benchmark.tests.conftest import TINY

NAME = "keye-tiny.longdoc-tiny"
REAL = "keye-vl2-l6-ep8.longdoc-closed32"

CONFIG = {
    "source": "tiny rehearsal preset of the CPU tests, not a model",
    "vocab_size": 384, "hidden_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 128,
    "num_experts": 4, "num_experts_published": 16, "experts_held": {"first": 4, "count": 4},
    "num_experts_per_tok": 4, "moe_intermediate_size": 128,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 8, "q_chunk_size": 8, "topk": 32},
    "attention_bias": False, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "norm_topk_prob": True, "sliding_window": None, "use_sliding_window": False,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "reduced": [],
    "driver": "serve_keye_vl2", "reference": "keye_vl2",
    "engine": {"state_manager": {"max_ragged_sequence_count": 8, "max_ragged_batch_size": 32,
                                 "max_context": 256, "num_kv_blocks": 160, "kv_dtype": "fp"},
               "kv_cache": {"block_size": 8}},
    # at this size, over five seeds, program / int8 control / the selection left out /
    # a window in its place: the mean 0.0002-0.0059 / 0.006-0.025 / 0.10-0.14 /
    # 0.16-0.22; each token counted at most 0.1: 0.0002-0.0021 / 0.0034-0.0072 /
    # 0.047-0.056 / 0.060-0.076, the program's share of the int8 control's 0.04-0.44
    "limits": {"served_gap_mean": 0.04, "served_gap_capped_mean": 0.015,
               "served_gap_capped_mean_vs_int8": 0.75},
}
TRAFFIC = {
    "generator": "requests", "loop": "closed", "clients": 6, "requests_per_client": 3,
    "shape_seed": 0, "order": "fixed",
    "prompt": {"dist": "lognormal", "median": 90, "sigma": 0.5, "min": 40, "max": 180},
    "output": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8, "max": 60},
    "check_requests": 4, "check_pad_to": 256, "check_max_new": 64, "check_gap_cap": 0.1,
    "trace_seconds": 1,
    "control_without": ["selection", "indexer"],
}


@pytest.fixture
def bench(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(TINY, root)
    with open(root / "configs" / "keye-tiny.json", "w") as f:
        json.dump(CONFIG, f)
    with open(root / "traffic" / "longdoc-tiny.json", "w") as f:
        json.dump(TRAFFIC, f)
    with open(root / "BENCHMARK.json") as f:
        b = json.load(f)
    b["configs"].append({"name": "keye-tiny", "source": "test", "reduced": [],
                         "file": "configs/keye-tiny.json", "why": "test"})
    b["workloads"].append({"name": NAME, "config": "keye-tiny", "traffic": "longdoc-tiny",
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
    assert cell.chips == 1 and cfg["driver"] == "serve_keye_vl2" and cfg["reference"] == "keye_vl2"
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in cell.per_layer}
    assert per_layer == {"dsa_device_share.serve", "dsa_index_roofline.serve",
                         "dsa_read_roofline.serve", "host_exposed_ms.serve",
                         "host_prelaunch_ms.serve", "fetch_tail_ms.serve",
                         "dispatch_host_ms.serve"}
    for name in per_layer:
        with open(os.path.join(cell.metrics_dir, name + ".json")) as f:
            spec = json.load(f)
        assert hasattr(harness.load("readers", spec["reader"]), "read")
    entry = {c["name"]: c for c in cell.bench["configs"]}[cell.entry["config"]]
    assert entry["reduced"] == cfg["reduced"] \
        == ["num_hidden_layers", "num_experts", "num_local_experts"]
    # every key of the catalog's row, unchanged but for the three cuts
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert entry["source"] == cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
    published = {"hidden_size": 2048, "intermediate_size": 6144, "head_dim": 128,
                 "num_attention_heads": 32, "num_key_value_heads": 4,
                 "moe_intermediate_size": 768, "num_experts_per_tok": 8,
                 "vocab_size": 151936, "rope_theta": 10000000,
                 "max_position_embeddings": 262144, "num_experts_published": 128}
    assert {k: cfg[k] for k in published} == published
    assert cfg["sa_config"] == {"indexer_head_dim": 64, "indexer_num_heads": 16,
                                "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                                "q_chunk_size": 512, "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["num_local_experts"]) == (6, 16, 16)
    share = cfg["experts_held"]
    assert share["count"] == 16 and 0 <= share["first"] <= 128 - 16
    assert set(cfg["assumed"]) >= {"qk_norm", "indexer", "indexer_storage", "chunk_sizes",
                                   "rope_layout", "intermediate_size", "vision_tower", "weights"}
    assert (mix["clients"], mix["order"], mix["shape_seed"]) == (32, "fixed", 0)
    assert mix["prompt"] == {"dist": "lognormal", "median": 16384, "sigma": 0.6,
                             "min": 4096, "max": 40960}
    assert mix["output"] == {"dist": "lognormal", "median": 768, "sigma": 0.6,
                             "min": 128, "max": 3072}
    assert mix["control_without"] == ["selection", "indexer"]
    sm = cfg["engine"]["state_manager"]
    assert (sm["max_ragged_sequence_count"], sm["max_ragged_batch_size"]) == (32, 512)
    assert mix["prompt"]["max"] + mix["output"]["max"] <= sm["max_context"] == 45056
    assert mix["prompt"]["min"] > cfg["sa_config"]["topk"]      # every row is sparse
    assert cell.limit("served_gap_capped_mean_vs_int8") < 1 and mix["check_gap_cap"] == 0.1
    assert set(cfg["limits"]) == {"served_gap_mean", "served_gap_capped_mean",
                                  "served_gap_capped_mean_vs_int8"} <= set(cfg["limits_why"])
    # the pool: 2,304 B a token and layer (K, V, an index key in 128 columns)
    tokens = sm["num_kv_blocks"] * cfg["engine"]["kv_cache"]["block_size"]
    assert tokens >= 600_000 and 9.0e9 < tokens * 2304 * 6 < 10.5e9


def test_cell_end_to_end_and_controls(bench, cpu_device, tmp_path, capsys):
    cell = harness.Cell(NAME, bench)
    devices, info = cpu_device
    result = run.run_cell(cell, 2**31 + 11, 6.0, 0, devices, info, time.perf_counter(),
                          str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "compared " in out and "the selected set changes in" in out

    mod = harness.load("drivers", "serve_keye_vl2")
    driver = mod.Driver(cell, 5, harness.Recorder(), devices=devices, seconds=6.0)
    groups = driver.engine.kv_stats()["groups"]
    assert set(groups) == {"kv"} and groups["kv"]["leaves"] == 3
    assert groups["kv"]["bytes"] == 2 * 161 * 8 * (2 * 2 * 128 + 128) * 2     # bfloat16 pools
    driver.window(6.0, str(tmp_path))
    rounds = [a for n, _, _, a in driver.rec.spans if n == "round"]
    assert all("attn_rows" in a for a in rounds)
    rows = [row for a in rounds for row in a["attn_rows"]]
    assert any(new == 1 and end > 32 for new, end in rows)
    assert any(new > 1 and end > 32 for new, end in rows)
    for a in rounds:                      # every token the round ran is in one row
        assert sum(new for new, _ in a["attn_rows"]) == a["prefill_tokens"] + a["decode_rows"]
    sched = driver.sched
    assert sched.expert_rows == sched.real_tokens * 4 * 2 and sched.expert_rows_padded == 0
    assert sched.index_pages > 0 and sched.sparse_rows > 0
    # what the selection had to read, from the lengths: the rows' own count
    assert sched.selected_tokens == 2 * sum(work.selected(cell.config, new, end)
                                            for new, end in rows)
    driver.release()
    sound = {n: v for n, v, _ in driver.compare()}
    control = {n: v for n, v, _ in driver.control()}
    names = {"served_gap.mean", "served_gap.capped_mean",          # the max is printed,
             "served_gap.capped_mean_vs_int8"}                     # not compared
    assert set(sound) == names
    assert set(control) == names | {f"without_{t}.{n}" for t in ("selection", "indexer")
                                    for n in names}
    limit = {n: cell.limit(n.replace(".", "_")) for n in names}
    assert all(sound[n] <= limit[n] for n in names)
    # the int8 control fails by ONE limit, its capped mean's share (1 by construction)
    share = "served_gap.capped_mean_vs_int8"
    assert sound[share] < limit[share] < 1.0 == control[share]
    for term in ("selection", "indexer"):           # the selection controls by every one
        for n in names:
            assert control[f"without_{term}.{n}"] > limit[n], (term, n)
    with pytest.raises(ValueError, match="unknown term"):
        driver._checks(("without:the_router",))


def test_the_drivers_weights_are_the_harnesss_value_for_value():
    import jax
    from benchmark import weights
    from benchmark.drivers import serve_kanana2
    from benchmark.references import keye_vl2 as reference
    cfg = {k: v for k, v in CONFIG.items() if k not in ("engine", "limits")}
    spec = reference.param_spec(cfg)
    want = weights.make_params(2**31 + 5, spec)
    got = serve_kanana2.make_params(2**31 + 5, spec, reference)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                      err_msg=str(path))


def test_the_references_blocks_agree_with_its_unblocked_form():
    import jax
    import jax.numpy as jnp
    from benchmark import weights
    from benchmark.references import keye_vl2 as reference
    cfg = {k: v for k, v in CONFIG.items() if k not in ("engine", "limits")}
    tree = weights.make_params(3, reference.param_spec(cfg))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, cfg["vocab_size"], 70), jnp.int32)
    whole = np.asarray(reference.full_logits(cfg, tree, ids, q_block=512))
    blocks = np.asarray(reference.full_logits(cfg, tree, ids, q_block=16))   # 5 blocks, padded
    assert np.isfinite(whole).all() and float(np.max(np.abs(whole - blocks))) < 2e-5
    # the chip's form (weights regenerated a layer and an expert at a time)
    # gives the hidden states the whole tree gives, and counts the near ties
    with jax.default_matmul_precision("highest"):
        _, _, x, (ties, queries, members) = reference._hidden(
            cfg, 3, ids[None], jnp.asarray([70], jnp.int32), "f32")
        x = reference._rms(x[0], tree["norm"]["scale"], cfg["rms_norm_eps"])
        logits = np.asarray(x @ tree["lm_head"].astype(jnp.float32).T)
        # a request of 40 tokens padded to 70: of the five blocks of 16 queries the
        # two wholly behind it are not computed (zeros), its own rows are what they
        # are alone, and the near ties are asked of its queries in block 0 only
        c, p = reference._c(cfg), reference._f32(tree["layers_0"])
        x0 = tree["embed_tokens"].astype(jnp.float32)[ids]
        padded, _, _, asked = reference._attention(c, "f32", (), p, x0, q_block=16, length=40)
        alone = reference._attention(c, "f32", (), p, x0[:40], q_block=16)[0]
        behind = reference._attention(c, "f32", (), p, x0, q_block=16)[0]
    assert float(np.max(np.abs(logits - whole))) < 2e-4
    assert 0 <= ties < 0.2 and 0 <= queries < 0.5 and (members >= 1 or queries == 0)
    assert float(np.max(np.abs(np.asarray(padded[:40]) - np.asarray(alone)))) < 2e-5
    assert int(asked) == 16 and reference.TIE_BLOCKS > 5
    assert np.array_equal(np.asarray(padded[48:]), np.asarray(x0[48:]))          # skipped
    assert not np.array_equal(np.asarray(behind[48:]), np.asarray(x0[48:]))


# -- the work functions against a hand count ---------------------------------------

PUBLISHED = {"num_hidden_layers": 6, "num_attention_heads": 32, "num_key_value_heads": 4,
             "head_dim": 128,
             "sa_config": {"indexer_num_heads": 16, "indexer_head_dim": 64, "topk": 2048}}


def test_the_work_at_two_rows_by_hand():
    """A decode row at context 20,480. Index: 20,480 keys x 128 B = 2,621,440 B
    a layer, 20,480 pairs x 16 x 64 x 2 = 41,943,040 operations a layer:
    memory-bound, 19.2 us over 6 layers at 819 GB/s. Read: 2,048 selected
    tokens x 2,048 B = 4,194,304 B a layer and 2,048 x 32 x 128 x 4 =
    33,554,432 operations: memory-bound, 30.7 us. A 512-token chunk ending at
    20,480: 512 x 20,480 - 512 x 511 / 2 = 10,354,944 pairs x 2,048 = 21.2 G
    operations a layer against the same 2.6 MB of keys: compute-bound; every
    query is past 2,048, so 512 x 2,048 = 1,048,576 selected pairs x 16,384 =
    17.2 G a layer, and the chunk reads the lesser of its 20,480 tokens and
    its 1,048,576 picks: 41.9 MB a layer; compute-bound."""
    v5e = peaks.peaks_for("TPU v5e")
    assert work.index_key_bytes(PUBLISHED) == 128 and work.kv_token_bytes(PUBLISHED) == 2048
    assert work.pairs(1, 20480) == 20480 and work.selected(PUBLISHED, 1, 20480) == 2048
    assert work.selected(PUBLISHED, 1, 1000) == 1000
    assert work.dsa_index_bytes(PUBLISHED, 1, 20480) == 6 * 2_621_440
    assert work.dsa_index_flops(PUBLISHED, 1, 20480) == 6 * 41_943_040
    least, bound = peaks.roofline_seconds(work.dsa_index_flops(PUBLISHED, 1, 20480),
                                          work.dsa_index_bytes(PUBLISHED, 1, 20480), v5e)
    assert bound == "memory" and least == pytest.approx(6 * 2_621_440 / 819e9)
    assert work.dsa_read_bytes(PUBLISHED, 1, 20480) == 6 * 4_194_304
    assert work.dsa_read_flops(PUBLISHED, 1, 20480) == 6 * 33_554_432
    least, bound = peaks.roofline_seconds(work.dsa_read_flops(PUBLISHED, 1, 20480),
                                          work.dsa_read_bytes(PUBLISHED, 1, 20480), v5e)
    assert bound == "memory" and least == pytest.approx(6 * 4_194_304 / 819e9)
    assert work.pairs(512, 20480) == 10_354_944
    assert work.dsa_index_flops(PUBLISHED, 512, 20480) == 6 * 10_354_944 * 2048
    assert peaks.roofline_seconds(work.dsa_index_flops(PUBLISHED, 512, 20480),
                                  work.dsa_index_bytes(PUBLISHED, 512, 20480), v5e)[1] == "compute"
    assert work.selected(PUBLISHED, 512, 20480) == 1_048_576
    assert work.dsa_read_flops(PUBLISHED, 512, 20480) == 6 * 1_048_576 * 16_384
    assert work.dsa_read_bytes(PUBLISHED, 512, 20480) == 6 * 20_480 * 2048
    least, bound = peaks.roofline_seconds(work.dsa_read_flops(PUBLISHED, 512, 20480),
                                          work.dsa_read_bytes(PUBLISHED, 512, 20480), v5e)
    assert bound == "compute" and 0.0005 < least < 0.0006
    # a chunk that straddles topk: queries at 2,000 .. 2,099 read 2,001 .. 2,048
    assert work.selected(PUBLISHED, 100, 2100) == sum(range(2001, 2049)) + 52 * 2048


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
            "spans": [("round", 0.0, 0.1, a) for a in rounds], "program_spans": loaded,
            "summary": {"busy_s": 0.5}, "peaks": peaks.peaks_for("TPU v5e"), "notes": [],
            "trace_path": "unused"}


def test_readers_turn_events_and_spans_into_shares_under_100(monkeypatch):
    ms = 1_000_000
    index = "%paged_index_scores.3 = f32[32,1,45056]{2,1,0} custom-call("
    select = "%topk_threshold.4 = f32[32,128]{1,0} custom-call("
    walk = "%paged_attention.5 = bf16[32,4,8,128]{3,2,1,0} custom-call("
    qkv = "%fusion.6 = bf16[32,1,4096]{2,1,0} fusion("
    gmm = "%gmm.7 = f32[512,768]{1,0} custom-call("
    other = "%fusion.1 = bf16[32,151936]{1,0} fusion("
    events = [(index, 0, 2 * ms), (select, 3 * ms, 4 * ms), (walk, 5 * ms, 45 * ms),
              (qkv, 50 * ms, 56 * ms), (gmm, 60 * ms, 70 * ms), (other, 120 * ms, 200 * ms)]
    base = "jit(packed_forward)/jit(_layer)/dsa_attn/"
    scopes = {index: base + "cond/branch_1_fun/dsa_index/paged_index_scores/pallas_call",
              select: base + "cond/branch_1_fun/dsa_select/topk_threshold/pallas_call",
              walk: base + "cond/branch_1_fun/dsa_read/paged_attention/pallas_call",
              qkv: base + "dsa_qkv/dot_general",
              gmm: "jit(packed_forward)/jit(_layer)/moe_ffn/moe_ffn_gmm/jit(gmm)/pallas_call",
              other: "jit(packed_forward)/dot_general"}
    monkeypatch.setattr(xplane_scopes, "op_names", lambda path: scopes)
    # a short row (end 1,500) is no work of the selection's
    rounds = [{"attn_rows": [(1, 20480)] * 32 + [(1, 1500)], "decode_rows": 33}] * 2
    builds = [{"real_tokens": 33, "index_pages": 9000, "sparse_rows": 32, "round": r,
               "selected_tokens": 6 * (32 * 2048 + 1500), "seqs": 33} for r in (7, 8)]
    ctx = _ctx(events, rounds, builds)
    got = keye_kernels.read(ctx, {"work": "dsa_index", "scopes": ["dsa_index", "dsa_select"]})
    least = 2 * 32 * work.dsa_index_bytes(PUBLISHED, 1, 20480) / 819e9
    assert got == pytest.approx(100 * least / 0.003) and 30 < got < 100
    assert "64 rows past 2048 tokens in 2 rounds" in ctx["notes"][-1]
    assert "0.0020 s under 'dsa_index', 0.0010 s under 'dsa_select'" in ctx["notes"][-1]
    got = keye_kernels.read(ctx, {"work": "dsa_read", "scopes": ["dsa_read"]})
    least = 2 * 32 * work.dsa_read_bytes(PUBLISHED, 1, 20480) / 819e9
    assert got == pytest.approx(100 * least / 0.040) and 0 < got < 10   # a walk of every page
    got = keye_kernels.read(ctx, {"work": "dsa_share"})
    assert got == pytest.approx(100 * 0.049 / 0.5) and got < 100
    assert "dsa_read 0.0400, dsa_qkv 0.0060, dsa_index 0.0020, dsa_select 0.0010" \
        in ctx["notes"][-1]
    assert "moe_ffn 0.0100 s = 2.00 %" in ctx["notes"][-1]
    assert "64 sparse rows" in ctx["notes"][-1] and "9000-9000" in ctx["notes"][-1]
    assert "the rows' own count from their lengths in 2 of the window's 2 rounds" in ctx["notes"][-1]
    builds[1]["selected_tokens"] += 1               # a span that miscounts shows
    ctx = _ctx(events, rounds, builds)
    keye_kernels.read(ctx, {"work": "dsa_share"})
    assert "in 1 of the window's 2 rounds" in ctx["notes"][-1]


def test_readers_give_none_for_a_program_without_the_events_or_scopes(monkeypatch):
    monkeypatch.setattr(xplane_scopes, "op_names", lambda path: {})
    ctx = _ctx([("%fusion.1 = bf16[8]{0} fusion(", 0, 1000)], [{"decode_rows": 4}],
               [{"real_tokens": 4}])
    for params in ({"work": "dsa_index", "scopes": ["dsa_index", "dsa_select"]},
                   {"work": "dsa_read", "scopes": ["dsa_read"]}, {"work": "dsa_share"}):
        assert keye_kernels.read(ctx, params) is None
    ctx["trace"] = None
    assert keye_kernels.read(ctx, {"work": "dsa_share"}) is None
