"""CPU rehearsal of the Kanana-2 serving cell at a tiny size: the new driver,
reference, traffic keys and readers end to end (the latent walk and the
grouped GEMM in interpret mode), the int8 control and the ``k_pe`` term left
out coming out as not correct; the real cell's files through ``harness.Cell``;
``peaks_kanana2``'s counts against a hand count; the reader on a made-up device
line; the reference's blocks against its unblocked form. The cell is added to
a copy of the tiny benchmark by files and entries, as a PR adds it to the real
one. No number here is a device number."""

import json
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import harness, peaks, peaks_kanana2 as work, run, xplane_scopes
from benchmark.readers import kanana2_kernels
from benchmark.tests.conftest import TINY

NAME = "kanana2-tiny.docqa-tiny"
REAL = "kanana2-l12-ep8.docqa-closed64"

CONFIG = {
    "source": "tiny rehearsal preset of the CPU tests, not a model",
    "vocab_size": 384, "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 3,
    "num_attention_heads": 4, "kv_lora_rank": 128, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "first_k_dense_replace": 1,
    "n_routed_experts": 8, "n_routed_experts_published": 32,
    "experts_held": {"first": 8, "count": 8}, "n_shared_experts": 2,
    "num_experts_per_tok": 6, "moe_intermediate_size": 128, "routed_scaling_factor": 2.448,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "q_lora_rank": None, "rope_scaling": None, "n_group": 1, "topk_group": 1,
    "scoring_func": "sigmoid", "norm_topk_prob": True,
    "reduced": [],
    "driver": "serve_kanana2", "reference": "kanana2",
    "engine": {"state_manager": {"max_ragged_sequence_count": 8, "max_ragged_batch_size": 32,
                                 "max_context": 256, "num_kv_blocks": 160, "kv_dtype": "fp"},
               "kv_cache": {"block_size": 8}},
    # at this size, over three seeds (mean / max): the program 0.00008-0.00053 /
    # 0.005-0.022, the int8 control on the same tokens 0.0010-0.0035 (the
    # program's share of it 0.05-0.53); left out, the k_pe term 0.12-0.22, the
    # scale 0.019-0.024, the bias 0.0035-0.0079
    "limits": {"served_gap_mean": 0.002, "served_gap_mean_vs_int8": 0.95},
}
TRAFFIC = {
    "generator": "requests", "loop": "closed", "clients": 6, "requests_per_client": 3,
    "shape_seed": 0, "order": "fixed",
    "prompt": {"dist": "lognormal", "median": 50, "sigma": 0.6, "min": 20, "max": 110},
    "output": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8, "max": 60},
    "check_requests": 4, "check_pad_to": 256, "check_max_new": 64, "trace_seconds": 1,
    "control_without": "k_pe",
}


@pytest.fixture
def bench(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(TINY, root)
    with open(root / "configs" / "kanana2-tiny.json", "w") as f:
        json.dump(CONFIG, f)
    with open(root / "traffic" / "docqa-tiny.json", "w") as f:
        json.dump(TRAFFIC, f)
    with open(root / "BENCHMARK.json") as f:
        b = json.load(f)
    b["configs"].append({"name": "kanana2-tiny", "source": "test", "reduced": [],
                         "file": "configs/kanana2-tiny.json", "why": "test"})
    b["workloads"].append({"name": NAME, "config": "kanana2-tiny", "traffic": "docqa-tiny",
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
    assert cell.chips == 1 and cfg["driver"] == "serve_kanana2" and cfg["reference"] == "kanana2"
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in cell.per_layer}
    # ``round_ms.decode`` reads rounds WITHOUT prompt tokens: this traffic has none
    assert per_layer == {"mla_attn_roofline.serve", "mla_device_share.serve",
                         # PR 39's four: what the host did for each dispatch
                         "host_exposed_ms.serve", "host_prelaunch_ms.serve",
                         "fetch_tail_ms.serve", "dispatch_host_ms.serve"}
    for name in per_layer:
        with open(os.path.join(cell.metrics_dir, name + ".json")) as f:
            spec = json.load(f)
        assert hasattr(harness.load("readers", spec["reader"]), "read")
    entry = {c["name"]: c for c in cell.bench["configs"]}[cell.entry["config"]]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    # every width as published; the two cuts state what they were cut from
    published = {"hidden_size": 2048, "intermediate_size": 6144, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "qk_head_dim": 192, "head_dim": 64, "num_attention_heads": 32,
                 "num_key_value_heads": 32, "moe_intermediate_size": 768,
                 "num_experts_per_tok": 6, "n_shared_experts": 2, "vocab_size": 128256,
                 "first_k_dense_replace": 1, "routed_scaling_factor": 2.448,
                 "max_position_embeddings": 32768, "rope_theta": 1000000,
                 "n_routed_experts_published": 128}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"]) == (12, 16)
    share = cfg["experts_held"]
    assert share["count"] == 16 and 0 <= share["first"] <= 128 - 16
    assert (mix["clients"], mix["requests_per_client"], mix["order"], mix["shape_seed"]) \
        == (64, 8, "fixed", 0)
    assert mix["prompt"] == {"dist": "lognormal", "median": 6144, "sigma": 0.7,
                             "min": 1024, "max": 16384}
    assert mix["output"] == {"dist": "lognormal", "median": 768, "sigma": 0.6,
                             "min": 128, "max": 3072}
    sm = cfg["engine"]["state_manager"]
    assert mix["prompt"]["max"] + mix["output"]["max"] <= sm["max_context"] == 20480
    assert mix["check_pad_to"] == mix["prompt"]["max"] + mix["output"]["max"]
    assert cell.limit("served_gap_mean") < 0.0778 and cell.limit("served_gap_mean_vs_int8") < 1
    # the pool: at least 600 k tokens of latent rows, 1,280 B a token and layer
    tokens = sm["num_kv_blocks"] * cfg["engine"]["kv_cache"]["block_size"]
    assert tokens >= 600_000
    assert 8.8e9 < tokens * 1280 * 12 < 10.0e9


def test_cell_end_to_end_and_controls(bench, cpu_device, tmp_path, capsys):
    cell = harness.Cell(NAME, bench)
    devices, info = cpu_device
    result = run.run_cell(cell, 2**31 + 11, 6.0, 0, devices, info, time.perf_counter(),
                          str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    out = capsys.readouterr().out
    assert "compared " in out and "the router's chosen set changes" in out

    mod = harness.load("drivers", "serve_kanana2")
    driver = mod.Driver(cell, 5, harness.Recorder(), devices=devices, seconds=6.0)
    groups = driver.engine.kv_stats()["groups"]
    assert set(groups) == {"kv"} and groups["kv"]["leaves"] == 1
    assert groups["kv"]["bytes"] == 3 * 161 * 8 * 256 * 2          # ONE bfloat16 pool
    driver.window(6.0, str(tmp_path))
    rounds = [a for n, _, _, a in driver.rec.spans if n == "round"]
    assert all("attn_rows" in a for a in rounds)
    rows = [row for a in rounds for row in a["attn_rows"]]
    assert any(new == 1 for new, _ in rows) and any(new > 1 for new, _ in rows)
    for a in rounds:                      # every token the round ran is in one row
        assert sum(new for new, _ in a["attn_rows"]) == a["prefill_tokens"] + a["decode_rows"]
    sched = driver.sched
    assert sched.expert_rows == sched.real_tokens * 6 * 2 and sched.expert_rows_padded == 0
    assert sched.latent_pages > 0
    driver.release()
    sound = {n: v for n, v, _ in driver.compare()}
    control = {n: v for n, v, _ in driver.control()}
    names = {"served_gap.mean", "served_gap.mean_vs_int8"}      # the max is printed, not compared
    assert set(sound) == names
    assert set(control) == names | {"without_k_pe." + n for n in names}
    limit = cell.limit("served_gap_mean")
    assert sound["served_gap.mean"] <= limit < control["without_k_pe.served_gap.mean"]
    assert sound["served_gap.mean_vs_int8"] < cell.limit("served_gap_mean_vs_int8") \
        < 1.0 == control["served_gap.mean_vs_int8"]
    assert control["without_k_pe.served_gap.mean_vs_int8"] > 1.0


def test_every_term_the_reference_can_leave_out_moves_the_served_gap(bench, cpu_device, tmp_path):
    cell = harness.Cell(NAME, bench)
    mod = harness.load("drivers", "serve_kanana2")
    driver = mod.Driver(cell, 7, harness.Recorder(), devices=cpu_device[0], seconds=4.0)
    driver.window(4.0, str(tmp_path))
    driver.release()
    limit = cell.limit("served_gap_mean")
    for term in ("bias", "routed_scale"):
        control = {n: v for n, v, _ in driver._checks((f"without:{term}",))[f"without:{term}"]}
        assert control["served_gap.mean"] > limit, term
    with pytest.raises(ValueError, match="unknown term"):
        driver._checks(("without:the_router",))


def test_the_drivers_weights_are_the_harnesss_value_for_value():
    """``serve_kanana2.make_params`` makes a layer kind a program; the tree
    is ``weights.make_params``'s, which the reference regenerates from."""
    import jax
    from benchmark import weights
    from benchmark.drivers import serve_kanana2
    from benchmark.references import kanana2 as reference
    cfg = {k: v for k, v in CONFIG.items() if k not in ("engine", "limits")}
    spec = reference.param_spec(cfg)
    want = weights.make_params(2**31 + 5, spec)
    got = serve_kanana2.make_params(2**31 + 5, spec, reference)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                      err_msg=str(path))


def _clients(driver):
    """{client: (the request it is at, tokens in its context, tokens still to come)}"""
    reqs = driver.sched._requests
    return {r["client"]: (driver.cursor[r["client"]] - 1,
                          len(reqs[u].prompt) + len(reqs[u].generated),
                          reqs[u].max_new_tokens - len(reqs[u].generated))
            for u, r in driver.active.items()}


def test_the_window_opens_on_the_state_the_base_drivers_start_ends_in(
        bench, cpu_device, tmp_path, monkeypatch, capsys):
    """``serve_kanana2.Driver._stagger`` builds by prefill the state that
    ``serve.Driver._stagger`` serves its way to: its count of the base
    driver's rounds is the scheduler's own, request for request and token
    for token, and the state it builds from the count has every client at
    that request, decoding, within a few tokens of that context."""
    from benchmark.drivers import serve
    cell = harness.Cell(NAME, bench)
    mod = harness.load("drivers", "serve_kanana2")
    built = mod.Driver(cell, 5, harness.Recorder(), devices=cpu_device[0], seconds=4.0)
    line = next(l for l in capsys.readouterr().out.splitlines() if "staggered_start" in l)
    note = json.loads(line)["staggered_start"]
    monkeypatch.setattr(mod.Driver, "_stagger", serve.Driver._stagger)
    base = mod.Driver(cell, 5, harness.Recorder(), devices=cpu_device[0], seconds=4.0)
    served = _clients(base)
    budget = CONFIG["engine"]["state_manager"]["max_ragged_batch_size"]
    count, rounds = mod._served_start(base.load["clients"], base.load["phase"], budget)
    lengths = [[len(p) for p, _ in q] for q in base.load["clients"]]
    assert rounds == len(base.rec.named("round")) == note["rounds_the_base_driver_would_serve"]
    assert served == {c: (at, lengths[c][at % 3] + n, max_new - n)
                      for c, (at, n, max_new) in enumerate(count)}
    got = _clients(built)
    assert {c: at for c, (at, _, _) in got.items()} == {c: at for c, (at, _, _) in served.items()}
    assert note["decoding"] == len(got) == 6 and note["in_prefill"] == 0 and note["preempted"] == 0
    for c in served:
        assert abs(got[c][1] - served[c][1]) <= 4 and got[c][1] + got[c][2] == sum(served[c][1:]), c
    built.window(2.0, str(tmp_path))
    state = json.loads(next(l for l in capsys.readouterr().out.splitlines()
                            if "window_state" in l))["window_state"]
    assert len(state["decode_rows_by_third"]) == 3 and state["at_end"]["preempted"] == 0


def test_the_references_blocks_agree_with_its_unblocked_form():
    import jax
    import jax.numpy as jnp
    from benchmark import weights
    from benchmark.references import kanana2 as reference
    cfg = {k: v for k, v in CONFIG.items() if k not in ("engine", "limits")}
    tree = weights.make_params(3, reference.param_spec(cfg))
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
    assert float(np.max(np.abs(logits - whole))) < 2e-4 and 0 <= ties < 0.2


# -- the work functions against a hand count ---------------------------------------

PUBLISHED = {"num_hidden_layers": 12, "num_attention_heads": 32, "kv_lora_rank": 512,
             "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128}


def test_attention_work_at_two_rows_by_hand():
    """A decode row at context 8,192: 8,192 x 1,152 B = 9,437,184 B of latent
    rows a layer plus q and o of 32 x (192 + 128) x 2 B = 20,480 B; 32 x 8,192
    pairs x 1,088 x 2 = 570,425,344 operations a layer absorbed, which is the
    lesser (materialised: 8,192 x 512 x 8,192 x 2 to up-project alone).
    Memory-bound: 138.4 us over 12 layers at 819 GB/s. A 512-token chunk
    ending at 8,192: 512 x 8,192 - 512 x 511 / 2 = 4,063,488 pairs;
    absorbed 32 x pairs x 1,088 x 2 = 282.9 G, materialised 2 x 32 x (8,192 x
    512 x 256 + pairs x 320) = 151.9 G a layer, the lesser; compute-bound."""
    assert work.latent_token_bytes(PUBLISHED) == 1152
    assert work.mla_attn_bytes(PUBLISHED, 1, 8192) == 12 * (9_437_184 + 20_480)
    assert work.pairs(1, 8192) == 8192
    assert work.mla_attn_flops_absorbed(PUBLISHED, 1, 8192) == 12 * 570_425_344
    assert work.mla_attn_flops(PUBLISHED, 1, 8192) == 12 * 570_425_344
    v5e = peaks.peaks_for("TPU v5e")
    least, bound = peaks.roofline_seconds(work.mla_attn_flops(PUBLISHED, 1, 8192),
                                          work.mla_attn_bytes(PUBLISHED, 1, 8192), v5e)
    assert bound == "memory" and least == pytest.approx(12 * 9_457_664 / 819e9)
    assert work.pairs(512, 8192) == 4_063_488
    assert work.mla_attn_flops_absorbed(PUBLISHED, 512, 8192) == 12 * 32 * 4_063_488 * 1088 * 2
    by_hand = 12 * 2 * 32 * (8192 * 512 * 256 + 4_063_488 * 320)
    assert work.mla_attn_flops_materialised(PUBLISHED, 512, 8192) == by_hand
    assert work.mla_attn_flops(PUBLISHED, 512, 8192) == by_hand < \
        work.mla_attn_flops_absorbed(PUBLISHED, 512, 8192)
    least, bound = peaks.roofline_seconds(by_hand, work.mla_attn_bytes(PUBLISHED, 512, 8192), v5e)
    assert bound == "compute" and 0.0090 < least < 0.0095


# -- the reader on a made-up device line ---------------------------------------------

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
    mla = "%paged_mla.3 = bf16[64,1,32,512]{3,2,1,0} custom-call("
    absorb = "%fusion.4 = bf16[64,1,32,512]{3,2,1,0} fusion("
    gmm = "%gmm.7 = f32[512,768]{1,0} custom-call("
    sort = "%sort.9 = (f32[64,128256]{1,0}, s32[64,128256]{1,0}) sort("
    other = "%fusion.1 = bf16[64,128256]{1,0} fusion("
    events = [(mla, 0, 12 * ms), (mla, 20 * ms, 32 * ms), (absorb, 40 * ms, 46 * ms),
              (gmm, 50 * ms, 60 * ms), (sort, 70 * ms, 110 * ms), (other, 120 * ms, 200 * ms)]
    scopes = {mla: "jit(ragged_forward)/jit(_layer)/mla_attn/mla_read/paged_mla/pallas_call",
              absorb: "jit(ragged_forward)/jit(_layer)/mla_attn/mla_q/dot_general",
              gmm: "jit(ragged_forward)/jit(_layer)/moe_ffn/moe_ffn_gmm/jit(gmm)/pallas_call",
              sort: "jit(sample)/sort", other: "jit(ragged_forward)/dot_general"}
    monkeypatch.setattr(xplane_scopes, "op_names", lambda path: scopes)
    rounds = [{"attn_rows": [(1, 8192)] * 64, "decode_rows": 64}] * 2
    builds = [{"real_tokens": 64, "latent_pages": 8200, "seqs": 64}] * 2
    ctx = _ctx(events, rounds, builds)
    got = kanana2_kernels.read(ctx, {"match": "^%?paged_mla", "scope": "/mla_attn/mla_read/",
                                     "work": "mla_attn"})
    least = 2 * 64 * work.mla_attn_bytes(PUBLISHED, 1, 8192) / 819e9
    assert got == pytest.approx(100 * least / 0.024) and 50 < got < 100
    assert "128 decode rows at contexts of 8192-8192" in ctx["notes"][-1]
    got = kanana2_kernels.read(ctx, {"scope": "/mla_attn/(mla_q|mla_latent_write|mla_read|mla_out)?",
                                     "work": "mla_share"})
    assert got == pytest.approx(100 * 0.030 / 0.5) and got < 100
    assert "mla_read 0.0240, mla_q 0.0060" in ctx["notes"][-1]
    assert "moe_ffn 0.0100 s = 2.00 %" in ctx["notes"][-1]
    assert "sort 0.0400 s = 8.00 %" in ctx["notes"][-1] and "8200-8200" in ctx["notes"][-1]
    # the layout changes around the kernel, under mla_read, are the read's time too
    up = "%fusion.8 = bf16[1,32,512,640]{3,2,1,0} fusion("
    scopes[up] = "jit(ragged_forward)/jit(_layer)/mla_attn/mla_read/transpose"
    ctx = _ctx(events + [(up, 300 * ms, 324 * ms)], rounds, builds)
    got = kanana2_kernels.read(ctx, {"match": "^%?paged_mla", "scope": "/mla_attn/mla_read/",
                                     "work": "mla_attn"})
    assert got == pytest.approx(100 * least / 0.048)


def test_readers_give_none_for_a_program_without_the_events_or_scopes(monkeypatch):
    monkeypatch.setattr(xplane_scopes, "op_names", lambda path: {})
    ctx = _ctx([("%fusion.1 = bf16[8]{0} fusion(", 0, 1000)], [{"decode_rows": 4}],
               [{"real_tokens": 4}])
    for params in ({"match": "^%?paged_mla", "scope": "/mla_attn/mla_read/", "work": "mla_attn"},
                   {"scope": "/mla_attn/()?", "work": "mla_share"}):
        assert kanana2_kernels.read(ctx, params) is None
    ctx["trace"] = None
    assert kanana2_kernels.read(ctx, {"match": "x", "scope": "y", "work": "mla_attn"}) is None
