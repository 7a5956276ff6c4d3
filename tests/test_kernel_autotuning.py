"""Kernel autotuning subsystem tests (docs/AUTOTUNING.md).

Covers the persistent tuning table (round-trip, deterministic resolution,
fallback semantics + telemetry reason codes), the DS_FLASH_* env override
contract, the chip-free kernel tuner (fast, injectable compile_fn), the
chip-free config autotuner, and the checked-in v5e table's validity. The
real-AOT sweeps are marked ``slow``.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu import telemetry
from deepspeed_tpu.autotuning import kernel_table, kernel_tuner
from deepspeed_tpu.autotuning.kernel_table import BlockConfig
from deepspeed_tpu.ops import registry
from deepspeed_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(autouse=True)
def _fresh_table_state(monkeypatch):
    """Isolate every test from the checked-in table and each other."""
    monkeypatch.delenv("DS_TPU_KERNEL_TABLE", raising=False)
    monkeypatch.delenv("DS_TPU_KERNEL_TABLE_DEVICE", raising=False)
    monkeypatch.delenv("DS_FLASH_BQ", raising=False)
    monkeypatch.delenv("DS_FLASH_BK", raising=False)
    kernel_table.clear_cache()
    yield
    kernel_table.clear_cache()


def _write_table(path, entries, device="tpu_v5e"):
    return kernel_table.save_table(str(path), device, entries, "test")


# ---------------------------------------------------------------------------
# BlockConfig + bucket keys
# ---------------------------------------------------------------------------

def test_block_config_make_validates():
    cfg = BlockConfig.make("flash_mha", block_q=256, block_k=128)
    assert cfg.get("block_q") == 256 and cfg.get("block_k") == 128
    assert cfg.as_dict() == {"block_q": 256, "block_k": 128}
    assert cfg.source == "ladder"
    with pytest.raises(ValueError, match="unknown kernel"):
        BlockConfig.make("nope", x=1)
    with pytest.raises(ValueError, match="unknown knob"):
        BlockConfig.make("flash_mha", block_q=256, block_z=1)
    with pytest.raises(ValueError, match="missing knob"):
        BlockConfig.make("flash_mha", block_q=256)
    with pytest.raises(ValueError, match="positive"):
        BlockConfig.make("flash_mha", block_q=256, block_k=-8)
    # knob-free kernels build empty configs
    assert BlockConfig.make("paged_mha").as_dict() == {}


def test_bucket_key_pow2_on_data_dims_exact_on_structural():
    # tq/tk round up to pow2; dh stays exact
    k1 = kernel_table.bucket_key("flash_mha",
                                 {"tq": 1000, "tk": 513, "dh": 64},
                                 "bfloat16")
    assert k1 == "flash_mha|tq1024,tk1024,dh64|bfloat16"
    # structural dims are exact: g=96 is NOT bucketed
    k2 = kernel_table.bucket_key(
        "quantized_matmul", {"m": 17, "k": 512, "n": 256, "g": 96}, "int8")
    assert k2 == "quantized_matmul|m32,k512,n256,g96|int8"
    with pytest.raises(ValueError, match="missing dim"):
        kernel_table.bucket_key("flash_mha", {"tq": 8}, "bf16")


def test_normalize_device_kind_aliases():
    assert kernel_table.normalize_device_kind("TPU v5 lite") == "tpu_v5e"
    assert kernel_table.normalize_device_kind("tpu v4") == "tpu_v4"
    # unknown kinds slugify instead of erroring
    assert kernel_table.normalize_device_kind("My Accel-2") == "my_accel_2"
    assert kernel_table.normalize_device_kind("") == "unknown"


# ---------------------------------------------------------------------------
# table round-trip + deterministic resolution (satellite c)
# ---------------------------------------------------------------------------

def test_table_round_trip_deterministic(tmp_path, monkeypatch):
    path = tmp_path / "tpu_v5e.json"
    key = kernel_table.bucket_key("flash_mha",
                                  {"tq": 1024, "tk": 1024, "dh": 64},
                                  "bfloat16")
    _write_table(path, {key: {"blocks": {"block_q": 512, "block_k": 256}}})
    monkeypatch.setenv("DS_TPU_KERNEL_TABLE", str(path))
    kernel_table.clear_cache()

    picks = [kernel_table.resolve("flash_mha",
                                  {"tq": 1024, "tk": 1024, "dh": 64},
                                  "bfloat16") for _ in range(3)]
    for cfg, reason in picks:
        assert reason == "tuned"
        assert cfg.source == "table"
        assert cfg.as_dict() == {"block_q": 512, "block_k": 256}
    # same bucket (tq=1000 -> 1024): same deterministic pick
    cfg, reason = kernel_table.resolve(
        "flash_mha", {"tq": 1000, "tk": 1024, "dh": 64}, "bfloat16")
    assert reason == "tuned" and cfg.as_dict() == {"block_q": 512,
                                                   "block_k": 256}


def test_bucket_miss_and_unknown_device_fall_back(tmp_path, monkeypatch):
    path = tmp_path / "tpu_v5e.json"
    key = kernel_table.bucket_key("flash_mha",
                                  {"tq": 1024, "tk": 1024, "dh": 64},
                                  "bfloat16")
    _write_table(path, {key: {"blocks": {"block_q": 512, "block_k": 256}}})
    monkeypatch.setenv("DS_TPU_KERNEL_TABLE", str(path))
    kernel_table.clear_cache()
    # bucket miss: different dh
    cfg, reason = kernel_table.resolve(
        "flash_mha", {"tq": 1024, "tk": 1024, "dh": 128}, "bfloat16")
    assert cfg is None and reason == "ladder_fallback"
    # unknown device kind -> no table file at all
    monkeypatch.delenv("DS_TPU_KERNEL_TABLE")
    monkeypatch.setenv("DS_TPU_KERNEL_TABLE_DEVICE", "weird_chip_9000")
    kernel_table.clear_cache()
    cfg, reason = kernel_table.resolve(
        "flash_mha", {"tq": 1024, "tk": 1024, "dh": 64}, "bfloat16")
    assert cfg is None and reason == "ladder_fallback"


def test_resolve_validate_hook_rejects_unfitting_entry(tmp_path, monkeypatch):
    """A tuned pick that doesn't fit the EXACT shape falls back to ladder:
    bucketing can land e.g. tq=1000 in the tq1024 bucket whose blocks don't
    divide 1000."""
    path = tmp_path / "t.json"
    key = kernel_table.bucket_key("flash_mha",
                                  {"tq": 1000, "tk": 1024, "dh": 64},
                                  "bfloat16")
    _write_table(path, {key: {"blocks": {"block_q": 512, "block_k": 512}}})
    monkeypatch.setenv("DS_TPU_KERNEL_TABLE", str(path))
    kernel_table.clear_cache()

    def validate(blocks, dims):
        return dims["tq"] % blocks["block_q"] == 0

    cfg, reason = kernel_table.resolve(
        "flash_mha", {"tq": 1000, "tk": 1024, "dh": 64}, "bfloat16",
        validate=validate)
    assert cfg is None and reason == "ladder_fallback"


def test_broken_table_never_breaks_dispatch(tmp_path, monkeypatch):
    path = tmp_path / "broken.json"
    path.write_text("{not json")
    monkeypatch.setenv("DS_TPU_KERNEL_TABLE", str(path))
    kernel_table.clear_cache()
    assert kernel_table.load_table() is None
    cfg, reason = kernel_table.resolve(
        "flash_mha", {"tq": 256, "tk": 256, "dh": 64}, "bfloat16")
    assert cfg is None and reason == "ladder_fallback"
    # schema-invalid (wrong knob set) is also a clean miss
    path.write_text(json.dumps({
        "format_version": 1, "device_kind": "tpu_v5e",
        "entries": {"flash_mha|tq256,tk256,dh64|bfloat16":
                    {"blocks": {"wrong": 1}}}}))
    kernel_table.clear_cache()
    assert kernel_table.load_table() is None


def test_validate_table_error_messages():
    errs = kernel_table.validate_table({"format_version": 99})
    assert any("format_version" in e for e in errs)
    errs = kernel_table.validate_table(
        {"format_version": 1, "device_kind": "x",
         "entries": {"bogus_kernel|a|b": {"blocks": {}}}})
    assert any("unknown kernel" in e for e in errs)
    errs = kernel_table.validate_table(
        {"format_version": 1, "device_kind": "x",
         "entries": {"flash_mha|tq8,tk8,dh8|f32":
                     {"blocks": {"block_q": 0, "block_k": 8}}}})
    assert any("positive" in e for e in errs)


def test_save_table_refuses_invalid(tmp_path):
    with pytest.raises(ValueError, match="refusing to write"):
        kernel_table.save_table(
            str(tmp_path / "t.json"), "tpu_v5e",
            {"flash_mha|x|y": {"blocks": {"block_q": 1}}}, "test")
    assert not (tmp_path / "t.json").exists()


# ---------------------------------------------------------------------------
# dispatch integration: table -> kernel, telemetry reason codes
# ---------------------------------------------------------------------------

def _flash_inputs(tq=256, tk=256, dh=64):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, tq, 2, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, tk, 2, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, tk, 2, dh)), jnp.float32)
    return q, k, v


def test_flash_dispatch_uses_table_and_records_tuned(tmp_path, monkeypatch):
    q, k, v = _flash_inputs()
    path = tmp_path / "t.json"
    key = kernel_table.bucket_key("flash_mha",
                                  {"tq": 256, "tk": 256, "dh": 64},
                                  str(q.dtype))
    _write_table(path, {key: {"blocks": {"block_q": 128, "block_k": 128}}})
    monkeypatch.setenv("DS_TPU_KERNEL_TABLE", str(path))
    kernel_table.clear_cache()
    telemetry.configure(enabled=True)
    try:
        ref = fa.flash_mha(q, k, v, causal=True, interpret=True)
        active = registry.active_kernel_configs()["flash_mha"]
        assert active["source"] == "table"
        assert active["block_q"] == 128 and active["block_k"] == 128
        disp = telemetry.summary()["dispatch"]["flash_mha"]
        assert disp["tuning"].get("tuned", 0) >= 1
    finally:
        telemetry.configure(enabled=False)
    # numerics match the ladder pick (blocks change scheduling, not math)
    monkeypatch.delenv("DS_TPU_KERNEL_TABLE")
    kernel_table.clear_cache()
    out = fa.flash_mha(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)
    assert registry.active_kernel_configs()["flash_mha"]["source"] == "ladder"


def test_flash_dispatch_fallback_records_reason(monkeypatch):
    monkeypatch.setenv("DS_TPU_KERNEL_TABLE_DEVICE", "no_such_chip")
    kernel_table.clear_cache()
    q, k, v = _flash_inputs()
    telemetry.configure(enabled=True)
    try:
        fa.flash_mha(q, k, v, causal=False, interpret=True)
        disp = telemetry.summary()["dispatch"]["flash_mha"]
        assert disp["tuning"].get("ladder_fallback", 0) >= 1
    finally:
        telemetry.configure(enabled=False)


def test_pinned_block_config_wins(tmp_path, monkeypatch):
    """The tuner sweep path: an explicit block_config bypasses the table."""
    q, k, v = _flash_inputs()
    out = fa.flash_mha(q, k, v, causal=True, interpret=True,
                       block_config={"block_q": 64, "block_k": 128})
    active = registry.active_kernel_configs()["flash_mha"]
    assert active["block_q"] == 64 and active["source"] == "sweep"
    ref = fa.flash_mha(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="do not divide"):
        fa.flash_mha(q, k, v, interpret=True,
                     block_config={"block_q": 100, "block_k": 128})


# ---------------------------------------------------------------------------
# env override contract (satellite a)
# ---------------------------------------------------------------------------

def test_env_override_beats_table(tmp_path, monkeypatch):
    q, k, v = _flash_inputs()
    path = tmp_path / "t.json"
    key = kernel_table.bucket_key("flash_mha",
                                  {"tq": 256, "tk": 256, "dh": 64},
                                  str(q.dtype))
    _write_table(path, {key: {"blocks": {"block_q": 256, "block_k": 256}}})
    monkeypatch.setenv("DS_TPU_KERNEL_TABLE", str(path))
    monkeypatch.setenv("DS_FLASH_BQ", "128")
    monkeypatch.setenv("DS_FLASH_BK", "64")
    kernel_table.clear_cache()
    fa.flash_mha(q, k, v, causal=True, interpret=True)
    active = registry.active_kernel_configs()["flash_mha"]
    assert active == {"block_q": 128, "block_k": 64, "source": "env",
                      "visible_share": 0.75}   # 3 of 4 blocks of [128, 64]


@pytest.mark.parametrize("var,val,msg", [
    ("DS_FLASH_BQ", "abc", "not an integer"),
    ("DS_FLASH_BQ", "3.5", "not an integer"),
    ("DS_FLASH_BQ", "-128", "positive"),
    ("DS_FLASH_BQ", "100", "does not divide the query"),
    ("DS_FLASH_BK", "100", "does not divide the key"),
])
def test_env_override_errors_name_the_variable(monkeypatch, var, val, msg):
    q, k, v = _flash_inputs()
    monkeypatch.setenv(var, val)
    with pytest.raises(ValueError) as ei:
        fa.flash_mha(q, k, v, interpret=True)
    assert var in str(ei.value) and msg in str(ei.value)


def test_env_override_zero_means_off(monkeypatch):
    monkeypatch.setenv("DS_FLASH_BQ", "0")
    q, k, v = _flash_inputs()
    fa.flash_mha(q, k, v, interpret=True)  # no raise; ladder applies
    assert registry.active_kernel_configs()["flash_mha"]["source"] == "ladder"


# ---------------------------------------------------------------------------
# chip-free kernel tuner (fast path: injectable compile_fn)
# ---------------------------------------------------------------------------

def _fake_compile_fn(score_of=None):
    """compile_fn stub: scores by -(bq*bk)-style preference via score_of,
    records what got compiled."""
    calls = []

    class Mem:
        temp_size_in_bytes = 1024
        output_size_in_bytes = 2048

    def fn(f, abstract):
        calls.append(abstract)
        flops = score_of(len(calls)) if score_of else 1e9
        return {"flops": flops, "bytes accessed": 1e6}, Mem()

    fn.calls = calls
    return fn


def test_candidate_space_respects_divisibility():
    cands = kernel_tuner.candidate_space(
        "flash_mha", {"tq": 512, "tk": 256, "dh": 64}, "bfloat16")
    assert {"block_q": 512, "block_k": 256} in cands
    assert all(512 % c["block_q"] == 0 and 256 % c["block_k"] == 0
               for c in cands)
    # knob-free kernels sweep the single empty candidate
    assert kernel_tuner.candidate_space("paged_mha", {"bs": 16, "dh": 64},
                                        "bfloat16") == [{}]


@pytest.mark.parametrize("d,f", [(2304, 896), (128, 256), (4096, 14336)],
                         ids=["mellum2", "bench-shape", "mixtral"])
def test_moe_candidates_and_grid_steps_go_by_gemm(d, f):
    """The grouped GEMM's candidates are enumerated a GEMM (the up GEMMs by
    (d, f), the down GEMM by (f, d)); the rule's pick comes first, megablox's
    128^3 is among them, and the proxy's grid steps count the group visits."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    dims = {"rows": 512, "d": d, "f": f}
    cands = kernel_tuner.candidate_space("moe_ffn_gmm", dims, "bfloat16")
    assert cands[0] == gg.ffn_blocks(gg.gmm_tiling(d, f, 2),
                                     gg.gmm_tiling(f, d, 2))
    assert gg.ffn_blocks((128,) * 3, (128,) * 3) in cands
    assert len(cands) <= (kernel_tuner.GMM_CANDIDATES_A_GEMM + 1) ** 2
    for c in cands:
        assert gg._tiling_fits(c, d, f)
        assert (kernel_tuner.vmem_bytes("moe_ffn_gmm", dims, "bfloat16", c)
                <= kernel_tuner.VMEM_BUDGET)
        BlockConfig.make("moe_ffn_gmm", **c)        # exactly the five knobs
        up, down = gg.ffn_tilings(c)
        visits = 512 // 128 + kernel_tuner.GMM_TUNING_EXPERTS - 1
        assert kernel_tuner.grid_steps("moe_ffn_gmm", dims, c) == visits * (
            2 * (d // up[1]) * (f // up[2]) + (f // down[1]) * (d // down[2]))
    steps = [kernel_tuner.grid_steps("moe_ffn_gmm", dims, c) for c in cands]
    assert steps[0] == min(steps)


def test_flash_score_counts_the_visible_share(monkeypatch):
    """The causal tuning program's grid steps and products are those of the
    logits its blocks and tiles compute: a block that skips nothing no longer
    scores like one that skips, and with the walk inside it the largest block
    wins on its grid steps, as the chip's timing says."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    dims = {"tq": 1024, "tk": 1024, "dh": 64}
    steps = lambda b: kernel_tuner.grid_steps(
        "flash_mha", dims, {"block_q": b, "block_k": b})
    assert [steps(b) for b in (1024, 512, 256)] == [8, 8 * 3, 8 * 10]

    def score(b):
        return kernel_tuner.proxy_score(
            "flash_mha", dims, "bfloat16", {"block_q": b, "block_k": b},
            {"flops": 1e6, "bytes accessed": 1e6}, "tpu_v5e")

    products = 9 * 2 * 1024 * 1024 * 64 * kernel_tuner.FLASH_TUNING_BH
    fixed = 1e6 / 197e12 + 1e6 / 819e9
    monkeypatch.setattr(fa, "_TILES", {k: (256, 256) for k in fa._TILES})
    assert fa.visible_share(1024, 1024, 1024, 1024, True, None) == 0.625
    assert score(1024) == pytest.approx(
        fixed + products * 0.625 / 197e12
        + 8 * kernel_tuner.GRID_STEP_SECONDS, rel=1e-9)
    assert score(1024) < score(512) < score(256) < score(128)
    # a kernel that walked nothing: the whole-sequence block computes the
    # whole square and loses to the block that skips a quarter of it
    monkeypatch.setattr(fa, "_TILES", {k: (1024, 1024) for k in fa._TILES})
    assert score(1024) == pytest.approx(
        fixed + products / 197e12 + 8 * kernel_tuner.GRID_STEP_SECONDS,
        rel=1e-9)
    assert score(512) < score(1024)


def test_chip_free_rank_orders_by_proxy_score():
    fake = _fake_compile_fn(score_of=lambda i: 1e9 * i)  # later = worse
    ranking, device = kernel_tuner.chip_free_rank(
        "flash_mha", {"tq": 512, "tk": 512, "dh": 64}, "bfloat16",
        compile_fn=fake, device_kind="tpu v5 lite")
    assert device == "tpu v5 lite"
    feasible = [r for r in ranking if r["feasible"]]
    assert feasible and len(fake.calls) == len(ranking)
    scores = [r["score"] for r in feasible]
    assert scores == sorted(scores)  # best-first


def test_chip_free_rank_marks_compile_failures_infeasible():
    def bomb(f, abstract):
        raise RuntimeError("mosaic says no")
    ranking, _ = kernel_tuner.chip_free_rank(
        "flash_mha", {"tq": 256, "tk": 256, "dh": 64}, "bfloat16",
        compile_fn=bomb, device_kind="tpu_v5e")
    assert ranking and all(not r["feasible"] for r in ranking)
    assert all("mosaic says no" in r["error"] for r in ranking)


def test_tune_writes_loadable_table(tmp_path, monkeypatch):
    fake = _fake_compile_fn()
    entries, report = kernel_tuner.tune(
        mode="chip-free", kernels=["flash_mha", "paged_mha"],
        compile_fn=fake, topology_name="v5e:2x2")
    assert report["mode"] == "chip-free"
    assert {s["kernel"] for s in report["sweeps"]} == {"flash_mha",
                                                       "paged_mha"}
    path = tmp_path / "tpu_v5e.json"
    doc = kernel_table.save_table(str(path), report["device_kind"], entries,
                                  "test")
    assert not kernel_table.validate_table(doc)
    monkeypatch.setenv("DS_TPU_KERNEL_TABLE", str(path))
    kernel_table.clear_cache()
    for dims, dtype in kernel_table.BENCH_SHAPES["flash_mha"]:
        cfg, reason = kernel_table.resolve("flash_mha", dims, dtype)
        assert reason == "tuned" and cfg.source == "table"


def test_sweep_of_some_kernels_keeps_the_other_entries(tmp_path):
    """``tune_kernels.py --kernels flash_mha`` replaces flash_mha's entries
    and rankings; every other entry stays byte for byte, in its place."""
    import importlib.util
    import json
    import shutil
    spec = importlib.util.spec_from_file_location(
        "tune_kernels", os.path.join(os.path.dirname(__file__), "..",
                                     "scripts", "tune_kernels.py"))
    tune_kernels = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tune_kernels)
    checked_in = kernel_table.table_path("tpu_v5e")
    table, ranking = tmp_path / "tpu_v5e.json", tmp_path / "ranking.json"
    shutil.copy(checked_in, table)
    ranking.write_text(json.dumps({"mode": "chip-free", "sweeps": [
        {"kernel": "paged_mha", "candidates": []},
        {"kernel": "flash_mha", "candidates": ["old"]}]}))
    old = kernel_table.load_table(path=str(table))
    new = {k: dict(v, score=1.0) for k, v in old["entries"].items()
           if k.startswith("flash_mha|")}
    entries, report, generated_by = tune_kernels.keep_other_kernels(
        ["flash_mha"], new, {"mode": "on-chip", "sweeps": [
            {"kernel": "flash_mha", "candidates": ["new"]}]}, "this sweep",
        str(table), str(ranking))
    assert generated_by == old["generated_by"]
    assert [(s["kernel"], s["mode"], s["candidates"])
            for s in report["sweeps"]] == [
        ("paged_mha", "chip-free", []), ("flash_mha", "on-chip", ["new"])]
    kernel_table.save_table(str(table), "tpu_v5e", entries, generated_by,
                            sort=False)
    want = open(checked_in).read().splitlines()
    got = table.read_text().splitlines()
    assert len(got) == len(want)
    changed = [b for a, b in zip(want, got) if a != b]
    assert len(changed) == len(new) and all('"score": 1.0' in b
                                            for b in changed)


def test_onchip_rank_requires_tpu():
    if jax.default_backend() == "tpu":
        pytest.skip("live accelerator present")
    with pytest.raises(RuntimeError, match="on-chip"):
        kernel_tuner.onchip_rank("flash_mha",
                                 {"tq": 256, "tk": 256, "dh": 64},
                                 "bfloat16")


# ---------------------------------------------------------------------------
# checked-in v5e table (the artifact the default dispatch path reads)
# ---------------------------------------------------------------------------

def test_checked_in_v5e_table_is_valid_and_covers_bench_shapes():
    doc = kernel_table.load_table(device_kind="tpu_v5e")
    assert doc is not None, "checked-in tables/tpu_v5e.json missing or invalid"
    assert doc["device_kind"] == "tpu_v5e"
    assert not kernel_table.validate_table(doc)
    for kernel, shapes in kernel_table.BENCH_SHAPES.items():
        for dims, dtype in shapes:
            key = kernel_table.bucket_key(kernel, dims, dtype)
            assert key in doc["entries"], f"bench shape uncovered: {key}"


def test_checked_in_table_resolves_on_forced_device(monkeypatch):
    monkeypatch.setenv("DS_TPU_KERNEL_TABLE_DEVICE", "tpu_v5e")
    kernel_table.clear_cache()
    cfg, reason = kernel_table.resolve(
        "flash_mha", {"tq": 1024, "tk": 1024, "dh": 64}, "bfloat16")
    assert reason == "tuned"
    assert cfg.get("block_q") >= 128 and cfg.get("block_k") >= 128


# ---------------------------------------------------------------------------
# chip-free config autotuner (satellite b)
# ---------------------------------------------------------------------------

def _make_config_tuner():
    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from tests.simple_model import SimpleModel, random_batches
    model = SimpleModel()
    batch = random_batches(1, 8)[0]
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    return Autotuner(
        model, params, {"train_batch_size": 8},
        lambda mbs: random_batches(1, max(mbs, 1))[0],
        tuning_space={"zero_stage": [0, 1],
                      "remat_policy": ["nothing", "everything"]})


def test_config_autotuner_chip_free_fast(monkeypatch):
    """Injectable compile_fn: no AOT compiles, ranking still complete."""
    tuner = _make_config_tuner()

    class Mem:
        temp_size_in_bytes = 1 << 20
        output_size_in_bytes = 1 << 20

    def fake(fn, abstract):
        return {"flops": 1e9, "bytes accessed": 1e8}, Mem()

    cfg, ranking = tuner.tune_chip_free(compile_fn=fake,
                                        device_kind="tpu v5 lite")
    assert cfg["zero_optimization"]["stage"] in (0, 1)
    assert any(e["feasible"] for e in ranking)
    # largest mbs wins on the per-sample proxy when cost is flat
    best = ranking[0]
    assert best["feasible"] and best["score"] is not None
    assert best["micro_batch_size"] == max(e["micro_batch_size"]
                                           for e in ranking)


def test_config_autotuner_chip_free_infeasible_raises():
    tuner = _make_config_tuner()

    def bomb(fn, abstract):
        raise RuntimeError("xla oom")

    with pytest.raises(RuntimeError, match="no candidate compiles"):
        tuner.tune_chip_free(compile_fn=bomb, device_kind="tpu_v5e")


@pytest.mark.slow
def test_config_autotuner_chip_free_real_aot_v5e():
    """Real AOT compile of the SimpleModel fwd+bwd against the v5e:2x2
    topology from a CPU host — the zero-TPU workflow end to end."""
    tuner = _make_config_tuner()
    cfg, ranking = tuner.tune_chip_free(topology_name="v5e:2x2")
    assert any(e["feasible"] for e in ranking)
    assert cfg["train_micro_batch_size_per_gpu"] >= 1


@pytest.mark.slow
def test_kernel_tuner_chip_free_real_aot_v5e():
    """Real Mosaic AOT sweep for one flash shape against v5e:2x2."""
    ranking, device = kernel_tuner.chip_free_rank(
        "flash_mha", {"tq": 512, "tk": 512, "dh": 64}, "bfloat16",
        topology_name="v5e:2x2")
    assert kernel_table.normalize_device_kind(device) == "tpu_v5e"
    assert any(r["feasible"] for r in ranking)
    best = next(r for r in ranking if r["feasible"])
    assert 512 % best["blocks"]["block_q"] == 0
