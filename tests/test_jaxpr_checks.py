"""graftlint Layer B — jaxpr checks over synthetic fixtures AND the real
traced programs (engine micro-step, qgZ scheduled exchange, serving decode
forward). This is the ``lint`` lane (``pytest -m lint``): everything here
traces with ``jax.make_jaxpr`` — no compile, no execution — so the whole
file stays cheap enough for the fast lane too.

The acceptance bar (ISSUE 12): the real programs pass ``check_program``
clean, and the overlap-plan drift check fails LOUDLY when the plan's
collective inventory is perturbed away from what the program traces.
"""

from functools import partial

import numpy as np
import pytest

pytest.importorskip("jax")
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.analysis import jaxpr_checks as jc

pytestmark = pytest.mark.lint


# ---------------------------------------------------------------------------
# JX001 — bf16 -> f32 upcasts
# ---------------------------------------------------------------------------

def test_upcast_feeding_math_is_flagged():
    def f(x):
        return x.astype(jnp.float32) * 2.0  # re-widened activation math

    closed = jax.make_jaxpr(f)(jnp.zeros((8192,), jnp.bfloat16))
    findings = jc.check_upcasts(closed)
    assert len(findings) == 1
    assert findings[0]["check"] == "JX001"
    assert "8192" in findings[0]["message"]


def test_accumulation_upcast_is_exempt():
    # bf16.sum() MUST accumulate in f32 — convert consumed only by reduce
    def f(x):
        return jnp.sum(x.astype(jnp.float32))

    closed = jax.make_jaxpr(f)(jnp.zeros((8192,), jnp.bfloat16))
    assert jc.check_upcasts(closed) == []


def test_tiny_upcast_below_min_elems_is_noise():
    def f(x):
        return x.astype(jnp.float32) * 2.0

    closed = jax.make_jaxpr(f)(jnp.zeros((8,), jnp.bfloat16))
    assert jc.check_upcasts(closed) == []
    # the threshold is a knob, not a constant
    assert jc.check_upcasts(closed, min_elems=4) != []


# ---------------------------------------------------------------------------
# JX002 — collectives vs shard_map bindings
# ---------------------------------------------------------------------------

def test_unbound_collective_is_flagged():
    def f(x):
        return jax.lax.psum(x, "dp")

    closed = jax.make_jaxpr(f, axis_env=[("dp", 8)])(jnp.zeros((4,)))
    findings = jc.check_collectives(closed)
    assert len(findings) == 1
    assert findings[0]["check"] == "JX002"
    assert "dp" in findings[0]["message"]
    # the caller can vouch for axes bound outside the traced fragment
    assert jc.check_collectives(closed, extra_bound=("dp",)) == []


def test_collective_inside_shard_map_is_bound():
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = jax.make_mesh((8,), ("dp",))

    def body(x):
        return jax.lax.psum(x, "dp")

    f = jax.shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P(),
                      check_vma=False)
    closed = jax.make_jaxpr(f)(jnp.zeros((8,), jnp.float32))
    assert jc.check_collectives(closed) == []


# ---------------------------------------------------------------------------
# JX003 — host callbacks in hot programs
# ---------------------------------------------------------------------------

def _echo(a):
    return np.asarray(a)


def test_callback_is_flagged_and_allowlistable():
    def f(x):
        return jax.pure_callback(
            _echo, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    closed = jax.make_jaxpr(f)(jnp.zeros((4,), jnp.float32))
    findings = jc.check_callbacks(closed)
    assert len(findings) == 1
    assert findings[0]["check"] == "JX003"
    assert jc.check_callbacks(closed, allow=("_echo",)) == []


def test_check_program_composes_all_three():
    def f(x):
        y = x.astype(jnp.float32) * 2.0
        return jax.lax.psum(y, "dp")

    closed = jax.make_jaxpr(f, axis_env=[("dp", 8)])(
        jnp.zeros((8192,), jnp.bfloat16))
    checks = {f["check"] for f in jc.check_program(closed)}
    assert checks == {"JX001", "JX002"}
    # f32 program: JX001 is not meaningful and must be gated off
    checks32 = {f["check"] for f in jc.check_program(closed, dtype="float32")}
    assert checks32 == {"JX002"}


# ---------------------------------------------------------------------------
# plan classes + drift (synthetic)
# ---------------------------------------------------------------------------

def test_op_class_mirrors_overlap_schedule():
    # jaxpr_checks hand-copies the prefetch/bucket/tail/moe mapping so the
    # stdlib CLI never imports the runtime; this is the sync guard
    from deepspeed_tpu.runtime.zero.overlap_schedule import _op_class
    for op in ("all_gather", "gather", "reduce_scatter", "psum_scatter",
               "all_to_all", "exchange", "all_reduce", "ppermute",
               "halo", "send", "a2a_dispatch", "a2a_combine"):
        assert jc.op_class(op) == _op_class(op), op
    # and the moe ops must NOT fall into the generic bucket class
    assert jc.op_class("a2a_dispatch") == "moe_dispatch"
    assert jc.op_class("a2a_combine") == "moe_combine"


def test_merge_inventories_sums_ops_and_classes():
    a = {"ops": {"all_gather": 4}, "classes": {"prefetch": 4}}
    b = {"ops": {"all_gather": 2, "all_to_all": 3},
         "classes": {"prefetch": 2, "bucket": 3}}
    m = jc.merge_inventories(a, b)
    assert m["ops"] == {"all_gather": 6, "all_to_all": 3}
    assert m["classes"] == {"bucket": 3, "prefetch": 6}


def test_plan_drift_synthetic_ok_and_perturbed():
    inv = {"ops": {"all_gather": 4, "reduce_scatter": 2},
           "classes": {"prefetch": 4, "bucket": 2}}
    plan = {"comm_ops": [{"op": "all_gather", "count": 4},
                         {"op": "reduce_scatter", "count": 2}]}
    assert jc.check_plan_drift(plan, inv)["ok"]

    # plan prices a class that never traces -> claims overlap for nothing
    ghost = {"comm_ops": plan["comm_ops"] + [{"op": "all_reduce", "count": 1}]}
    res = jc.check_plan_drift(ghost, inv)
    assert not res["ok"] and res["missing_in_trace"] == ["tail"]

    # traced class the plan omits -> unpriced comm the model never saw
    blind = {"comm_ops": [{"op": "all_gather", "count": 4}]}
    res = jc.check_plan_drift(blind, inv)
    assert not res["ok"] and res["missing_in_plan"] == ["bucket"]


# ---------------------------------------------------------------------------
# real programs
# ---------------------------------------------------------------------------

def _build_scheduled_engine():
    import deepspeed_tpu
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    VOCAB, HID, LAYERS, B, T = 256, 64, 4, 8, 16
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=VOCAB, hidden_size=HID, intermediate_size=2 * HID,
        num_hidden_layers=LAYERS, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=T))
    rng = np.random.RandomState(1)
    ids = rng.randint(0, VOCAB, size=(B, T)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config={
            "train_batch_size": B,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "zero_optimization": {"stage": 3,
                                  "zero_quantized_gradients": True},
            "overlap": {"schedule": True, "prefetch_depth": 1,
                        "grad_buckets": 2},
        })
    engine._compiled()  # builds the jitted step fns without running a step
    return engine, batch


@pytest.fixture(scope="module")
def scheduled_traces():
    """(micro_jaxpr, apply_jaxpr) of the overlap-scheduled qgZ engine —
    make_jaxpr only, nothing compiles or runs."""
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh")
    engine, batch = _build_scheduled_engine()
    micro = jax.make_jaxpr(engine._micro_step_fn)(engine.state, batch)
    apply = jax.make_jaxpr(engine._apply_step_fn)(engine.state, 0.01)
    return micro, apply


def test_scheduled_micro_step_is_clean(scheduled_traces):
    micro, _ = scheduled_traces
    # fp32 run: JX001 gated off; every collective must be shard_map-bound;
    # and nothing may have traced a host callback into the step
    assert jc.check_program(micro, dtype="float32") == []


def test_qgz_apply_step_traces_bucket_exchange(scheduled_traces):
    _, apply = scheduled_traces
    assert jc.check_program(apply, dtype="float32") == []
    inv = jc.collective_inventory(apply)
    # the qgZ quantized gradient exchange lowers to all_to_all inside the
    # shard_map — the bucket class the overlap plan prices
    assert inv["ops"].get("all_to_all", 0) > 0
    assert inv["classes"].get("bucket", 0) > 0


def test_plan_drift_against_traced_inventory(scheduled_traces):
    micro, apply = scheduled_traces
    merged = jc.merge_inventories(jc.collective_inventory(micro),
                                  jc.collective_inventory(apply))
    assert merged["classes"], "scheduled round traced no collectives at all"

    # a plan priced from the traced reality agrees with it
    honest = {"comm_ops": [{"op": op, "count": n}
                           for op, n in merged["ops"].items()]}
    res = jc.check_plan_drift(honest, merged)
    assert res["ok"], res

    # perturb the plan inventory -> the gate fails LOUDLY (acceptance bar):
    # (a) a priced class the program never traces
    ghost_op = "all_gather" if "prefetch" not in merged["classes"] else "halo"
    ghost = {"comm_ops": honest["comm_ops"] + [{"op": ghost_op, "count": 8}]}
    res = jc.check_plan_drift(ghost, merged)
    assert not res["ok"] and res["missing_in_trace"], res
    # (b) the plan drops a traced class entirely
    blind = {"comm_ops": [{"op": ghost_op, "count": 8}]}
    res = jc.check_plan_drift(blind, merged)
    assert not res["ok"] and res["missing_in_plan"], res


# ---------------------------------------------------------------------------
# MoE micro-step (ISSUE 15): bound a2a + wire precision
# ---------------------------------------------------------------------------

def _trace_moe_shard(bits):
    """jaxpr of the dropless ep micro-step (shard_map'd _moe_gmm_ep_shard),
    exactly as _gmm_ep_forward wires it — make_jaxpr only."""
    from deepspeed_tpu.moe.sharded_moe import _moe_gmm_ep_shard

    mesh = jax.make_mesh((4, 2), ("dp", "ep"))
    S, D, F, E, k = 32, 256, 256, 4, 2

    def body(xl, gl, el, w1l, w2l, w3l):
        return _moe_gmm_ep_shard(xl, gl, el, w1l, w2l, w3l, n_experts=E,
                                 ep_axis="ep", bits=bits, dtype=jnp.float32,
                                 interpret=True)

    tok = P(("dp", "ep"), None)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(tok, tok, tok, P("ep"), P("ep"), P("ep")),
                       out_specs=tok, check_vma=False)
    return jax.make_jaxpr(fn)(
        jnp.zeros((S, D), jnp.float32), jnp.zeros((S, k), jnp.float32),
        jnp.zeros((S, k), jnp.int32), jnp.zeros((E, D, F), jnp.float32),
        jnp.zeros((E, F, D), jnp.float32), jnp.zeros((E, D, F), jnp.float32))


def test_moe_micro_step_a2a_is_bound_and_clean():
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh")
    closed = _trace_moe_shard(bits=None)
    # every dispatch/combine all_to_all is shard_map-bound, no callbacks
    assert jc.check_program(closed, dtype="float32") == []
    inv = jc.collective_inventory(closed)
    assert inv["ops"].get("all_to_all", 0) >= 3  # x out, ids, y back


def test_moe_unsharded_a2a_is_flagged():
    # the same body traced WITHOUT a shard_map binding 'ep' — the unbound
    # dispatch/combine a2a the lint lane must catch
    from deepspeed_tpu.moe.sharded_moe import _moe_gmm_ep_shard

    S, D, F, E, k = 16, 128, 128, 4, 2

    def body(xl, gl, el, w1l, w2l, w3l):
        return _moe_gmm_ep_shard(xl, gl, el, w1l, w2l, w3l, n_experts=E,
                                 ep_axis="ep", bits=None, dtype=jnp.float32,
                                 interpret=True)

    closed = jax.make_jaxpr(body, axis_env=[("ep", 2)])(
        jnp.zeros((S, D), jnp.float32), jnp.zeros((S, k), jnp.float32),
        jnp.zeros((S, k), jnp.int32),
        jnp.zeros((E // 2, D, F), jnp.float32),
        jnp.zeros((E // 2, F, D), jnp.float32),
        jnp.zeros((E // 2, D, F), jnp.float32))
    findings = jc.check_collectives(closed)
    assert findings and all(f["check"] == "JX002" for f in findings)
    assert any("all_to_all" in f["eqn"] for f in findings)
    # vouching for the externally-bound axis silences it
    assert jc.check_collectives(closed, extra_bound=("ep",)) == []


def test_moe_wire_quantized_vs_fp_leg():
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh")
    # int8 configured AND traced -> clean
    assert jc.check_moe_wire(_trace_moe_shard(bits=8), wire_bits=8) == []
    # int8 configured but the trace ships fp -> JX004, loudly
    findings = jc.check_moe_wire(_trace_moe_shard(bits=None), wire_bits=8)
    assert len(findings) == 1 and findings[0]["check"] == "JX004"
    assert "never materialized" in findings[0]["message"]
    # no bits configured -> nothing to check
    assert jc.check_moe_wire(_trace_moe_shard(bits=None), wire_bits=None) == []


def test_moe_hierarchical_wire_int8_rides_dcn_only():
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh")
    from deepspeed_tpu.runtime.comm.coalesced_collectives import (
        moe_hierarchical_a2a)

    mesh = jax.make_mesh((4, 2), ("dpr", "ep"))

    def trace(inter_bits):
        fn = jax.shard_map(
            lambda x: moe_hierarchical_a2a(x, intra_axis="ep",
                                           inter_axis="dpr",
                                           inter_bits=inter_bits),
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)
        return jax.make_jaxpr(fn)(
            jnp.zeros((4, 2, 16, 2048), jnp.float32))

    closed = trace(8)
    assert jc.check_program(closed, dtype="float32") == []
    assert jc.check_moe_wire(closed, wire_bits=8, inter_axis="dpr") == []
    # fp over DCN where int8 was configured -> the (b) finding
    findings = jc.check_moe_wire(trace(None), wire_bits=8, inter_axis="dpr")
    assert len(findings) == 1 and findings[0]["check"] == "JX004"


@pytest.fixture(scope="module")
def serving_decode_trace():
    """jaxpr of the v2 ragged decode forward, traced exactly as
    ``_forward_device`` calls it (static model_config partial'd in)."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import \
        RaggedBatchWrapper
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
    model = LlamaForCausalLM(cfg)
    ids = np.zeros((1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    engine = InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 32,
                          "max_context": 64, "num_kv_blocks": 16},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})

    seq = engine._state.get_or_create_sequence(1)
    engine._state.ensure_capacity(seq, 4)
    sm = engine._config.state_manager
    wrapper = RaggedBatchWrapper(sm.max_ragged_sequence_count,
                                 sm.max_ragged_batch_size,
                                 engine._max_blocks_per_seq,
                                 engine._state.kv_cache.trash_block)
    wrapper.insert_sequence(1, np.array([2, 3, 4, 5], np.int32), 0,
                            seq.kv_blocks)
    arrays = wrapper.build()
    kv = engine._state.kv_cache
    return jax.make_jaxpr(
        partial(engine._ragged_forward, engine._model_config))(
            engine._params, {"kv": (kv.k_pool, kv.v_pool)},
            jnp.asarray(arrays["tokens"]), jnp.asarray(arrays["q_len"]),
            jnp.asarray(arrays["seen"]),
            {"kv": jnp.asarray(arrays["block_tables"])})


def test_serving_decode_step_is_clean(serving_decode_trace):
    # the decode hot path must trace zero host callbacks (each would be a
    # per-token stall the host_sync audit could never see) and no
    # unbound collectives
    assert jc.check_program(serving_decode_trace, dtype="float32") == []


# ---------------------------------------------------------------------------
# JX005 — the speculative verify forward rides the prefill scan (ISSUE 16)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def verify_parity_traces():
    """(plain ragged_forward jaxpr, ragged_forward_verify jaxpr) over the
    same tiny engine and the same padded batch shapes — make_jaxpr only."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import \
        RaggedBatchWrapper
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
    model = LlamaForCausalLM(cfg)
    ids = np.zeros((1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    engine = InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 32,
                          "max_context": 64, "num_kv_blocks": 16},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})
    assert engine.verify_supported

    seq = engine._state.get_or_create_sequence(1)
    engine._state.ensure_capacity(seq, 4)
    sm = engine._config.state_manager
    wrapper = RaggedBatchWrapper(sm.max_ragged_sequence_count,
                                 sm.max_ragged_batch_size,
                                 engine._max_blocks_per_seq,
                                 engine._state.kv_cache.trash_block)
    wrapper.insert_sequence(1, np.array([2, 3, 4, 5], np.int32), 0,
                            seq.kv_blocks)
    arrays = wrapper.build()
    kv = engine._state.kv_cache
    args = (engine._params, {"kv": (kv.k_pool, kv.v_pool)},
            jnp.asarray(arrays["tokens"]), jnp.asarray(arrays["q_len"]),
            jnp.asarray(arrays["seen"]),
            {"kv": jnp.asarray(arrays["block_tables"])})
    mc = engine._model_config
    plain = jax.make_jaxpr(partial(engine._ragged_forward, mc))(*args)
    verify = jax.make_jaxpr(
        lambda *a: engine._verify_forward(mc, *a, 4))(*args)
    return plain, verify


def test_verify_forward_shares_prefill_scan(verify_parity_traces):
    # the bit-exactness oracle's structural half: draft verification lowers
    # through the IDENTICAL layer scan as plain ragged prefill — no trunk
    # fork, no dense-decode fallback — and the program is itself clean
    plain, verify = verify_parity_traces
    assert jc.check_verify_prefill_parity(plain, verify) == []
    assert jc.check_program(verify, dtype="float32") == []


def test_verify_parity_flags_fork_and_fallback():
    def stacked(x):
        return jax.lax.scan(lambda c, t: (c + t, c), x[0], x)[0]

    def forked(x):
        return jax.lax.scan(lambda c, t: (c * t, c), x[0], x)[0]

    ja = jax.make_jaxpr(stacked)(jnp.arange(4.0))
    jb = jax.make_jaxpr(forked)(jnp.arange(4.0))
    assert jc.check_verify_prefill_parity(ja, ja) == []
    findings = jc.check_verify_prefill_parity(ja, jb)
    assert len(findings) == 1 and findings[0]["check"] == "JX005"
    assert "diverges" in findings[0]["message"]
    # a verify program with no scan at all is the dense-decode fallback
    dense = jax.make_jaxpr(lambda x: x * 2)(jnp.arange(4.0))
    findings = jc.check_verify_prefill_parity(ja, dense)
    assert findings and findings[0]["check"] == "JX005"
    assert "no layer scan" in findings[0]["message"]
