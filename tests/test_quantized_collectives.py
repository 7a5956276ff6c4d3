"""ZeRO++ finished: fused quant kernels, error feedback, wire-byte telemetry.

Covers the ``ops/pallas/quant_collective`` kernel pair (wire format, packing,
non-divisible tails, interpret-vs-jnp parity), ``exchange_reduce`` error
feedback (the residual is exactly what the wire lost), engine-level loss
parity of qgZ against the fp32 psum baseline (feedback must tighten it), and
the wire-byte telemetry acceptance bound: quantized DCN traffic at or below
0.3x the logical fp32 bytes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

import deepspeed_tpu
from deepspeed_tpu import telemetry
from deepspeed_tpu.ops.pallas.quant_collective import (
    block_dequantize, block_dequantize_reduce, block_quantize, wire_nbytes)
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.runtime.comm.coalesced_collectives import exchange_reduce
from tests.simple_model import SimpleModel, random_batches


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")
    yield
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")


# ---------------------------------------------------------------- kernels

@pytest.mark.parametrize("bits", [8, 4])
def test_block_quantize_roundtrip_nondivisible_tail(bits):
    """M=5000 with group 512: 10 groups per row, 120-element padded tail."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 5000)).astype(np.float32))
    q, s = block_quantize(x, num_bits=bits, group_size=512)
    if bits == 8:
        assert q.dtype == jnp.int8 and q.shape == (16, 5120)
    else:
        assert q.dtype == jnp.uint8 and q.shape == (16, 2560)
    assert s.shape == (16, 10)
    back = block_dequantize(q, s, num_bits=bits, group_size=512, out_len=5000)
    assert back.shape == x.shape
    err = np.abs(np.asarray(back - x))
    # symmetric round-to-nearest: error <= scale/2 per group (margin 0.6)
    bound = np.asarray(s).max() * (0.51 if bits == 8 else 0.6)
    assert err.max() <= bound + 1e-6


@pytest.mark.parametrize("bits", [8, 4])
def test_interpret_kernel_matches_jnp_twin(bits):
    """Pallas interpret path and the jnp fallback share one wire format."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 4096)).astype(np.float32))
    q_ref, s_ref = block_quantize(x, num_bits=bits, group_size=2048,
                                  interpret=False)      # jnp twin on CPU
    q_k, s_k = block_quantize(x, num_bits=bits, group_size=2048,
                              interpret=True)           # Pallas interpret
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref), rtol=1e-6)
    d_ref = block_dequantize(q_ref, s_ref, num_bits=bits, group_size=2048,
                             interpret=False)
    d_k = block_dequantize(q_k, s_k, num_bits=bits, group_size=2048,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_ref), atol=1e-5)


def test_int4_half_split_packing():
    """Byte j carries element j in the low nibble and element j + gs/2 in
    the high nibble (contiguous lane-aligned halves, not interleaved)."""
    vals = (np.arange(256) % 15 - 7).astype(np.float32)  # amax 7 -> scale 1
    q, s = block_quantize(jnp.asarray(vals), num_bits=4, group_size=256)
    assert float(s[0]) == pytest.approx(1.0)
    iv = vals.astype(np.int64)
    expected = ((iv[:128] & 0xF) | ((iv[128:] & 0xF) << 4)).astype(np.uint8)
    np.testing.assert_array_equal(np.asarray(q), expected)


@pytest.mark.parametrize("bits", [8, 4])
def test_dequantize_reduce_sums_peers(bits):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(4, 1000)).astype(np.float32))  # 4 peers
    q, s = block_quantize(x, num_bits=bits, group_size=256)
    out = block_dequantize_reduce(q, s, num_bits=bits, group_size=256,
                                  out_len=1000)
    per_peer = block_dequantize(q, s, num_bits=bits, group_size=256,
                                out_len=1000)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(per_peer).sum(axis=0), atol=1e-5)
    # and it approximates the fp32 sum within the quantization budget
    np.testing.assert_allclose(np.asarray(out), np.asarray(x).sum(axis=0),
                               atol=(0.1 if bits == 8 else 1.0))


def test_wire_nbytes():
    assert wire_nbytes(2048, 8, 2048) == 2048 + 4          # 1 group
    assert wire_nbytes(2048, 4, 2048) == 1024 + 4          # packed half
    assert wire_nbytes(2049, 8, 2048) == 2 * 2048 + 8      # padded tail
    assert wire_nbytes(100, 4, 2048) == 1024 + 4


# ---------------------------------------------------------------- feedback

def _mesh2d(eight_devices):
    dev = np.asarray(eight_devices).reshape(4, 2)
    return jax.sharding.Mesh(dev, ("dpr", "dp"))


def test_exchange_reduce_error_is_wire_loss(eight_devices):
    """``err`` must be exactly input minus what the peers reconstruct: the
    all-to-all of ``blocks - err`` re-summed matches the quantized output."""
    mesh = _mesh2d(eight_devices)
    rng = np.random.default_rng(3)
    m = 256
    g_all = rng.normal(size=(4, 2, 2, m)).astype(np.float32)  # [dpr,dp,P,m]

    def body(g):
        blocks = g[0, 0]                               # [2, m]
        out, err = exchange_reduce(blocks, "dp", 4, group_size=256,
                                   return_error=True)
        out_plain = exchange_reduce(blocks, "dp", 4, group_size=256)
        deq = blocks - err                             # what crossed the wire
        recv = jax.lax.all_to_all(deq, "dp", split_axis=0, concat_axis=0)
        return (out[None, None], out_plain[None, None],
                err[None, None], recv.sum(axis=0)[None, None])

    f = shard_map(body, mesh=mesh, in_specs=P("dpr", "dp"),
                  out_specs=(P("dpr", "dp"),) * 4, check_vma=False)
    out, out_plain, err, resum = (np.asarray(a) for a in
                                  f(jnp.asarray(g_all)))
    # return_error must not change the reduction itself
    np.testing.assert_allclose(out, out_plain, atol=1e-6)
    # residual identity: dequantized sends re-sum to the fused reduce output
    np.testing.assert_allclose(resum, out, atol=1e-5)
    # int4 rounding: |err| <= scale/2 = amax/14 per group (margin to amax/7)
    assert np.abs(err).max() <= np.abs(g_all).max() / 7.0
    # and the quantized sum tracks the fp32 sum: device (e, i) reduces the
    # chunks destined to dp-rank i within replica group e
    np.testing.assert_allclose(out, g_all.sum(axis=1), atol=1.0, rtol=0.1)


# ---------------------------------------------------------------- engine

def _train(config, steps=3, seed=0):
    model = SimpleModel(hidden_dim=64)
    batches = random_batches(steps, batch_size=8, seed=seed + 1)
    params = model.init(jax.random.PRNGKey(seed), batches[0])["params"]
    engine, _, _, _ = deepspeed_tpu.initialize(model=model,
                                               model_parameters=params,
                                               config=config)
    losses = []
    for b in batches:
        loss = engine(b)
        engine.backward(loss)
        engine.step()
        losses.append(float(jax.device_get(loss)))
    return engine, losses


_BASE = {
    "train_batch_size": 8,
    "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
    "bf16": {"enabled": True},
}

_Z3 = {"stage": 3, "stage3_param_persistence_threshold": 0}


def test_qgz_loss_parity_feedback_tightens():
    """qgZ tracks the fp32 psum baseline within its two documented bounds
    (measured 0.033 without feedback, 0.034 with: int4 intra stage on the
    8-way dp world), and error feedback tightens what it promises to: the
    ACCUMULATED quantization error. The loss cannot show that (six bf16 AdamW
    steps on fresh batches leave the two runs 0.001 apart in either order,
    and no nearer over 30 steps or other seeds), so no order between the two
    losses is claimed. The sums can: the same local gradients reduced eight
    times drift from eight times their fp32 sum by eight single-step errors
    without the carry, and with it stay within one step's error however many
    steps are summed (the carries telescope)."""
    _, l_ref = _train(dict(_BASE, zero_optimization=dict(_Z3)), steps=6)
    groups.reset()
    _, l_q = _train(dict(_BASE, zero_optimization=dict(
        _Z3, zero_quantized_gradients=True)), steps=6)
    groups.reset()
    eng, l_fb = _train(dict(_BASE, zero_optimization=dict(
        _Z3, zero_quantized_gradients=True,
        zero_quantized_gradients_error_feedback=True)), steps=6)

    div_q = max(abs(a - b) for a, b in zip(l_q, l_ref))
    div_fb = max(abs(a - b) for a, b in zip(l_fb, l_ref))
    assert div_q <= 0.2, (l_q, l_ref)
    assert div_fb <= 0.1, (l_fb, l_ref)          # the tighter documented bound
    # the carry is real: residual leaves are populated after stepping
    res = jax.tree.leaves(eng.state.qgz_residual)
    assert res and any(float(jnp.abs(r).max()) > 0 for r in res)

    # the engine's own reduction, over gradients that do not change
    plan, steps = eng._qgz_plan, 8
    rng = np.random.default_rng(0)
    acc = jax.tree.map(lambda a: jax.device_put(
        rng.normal(size=a.shape).astype(a.dtype), a.sharding), eng.state.grad_acc)
    want = [np.asarray(a, np.float32).sum(0) for a in jax.tree.leaves(acc)]
    worst = lambda got, n: max(float(np.abs(np.asarray(g) - n * w).max())
                               for g, w in zip(got, want))
    one_step = worst(jax.tree.leaves(jax.jit(plan.reduce)(acc)), 1)
    reduce_fb = jax.jit(lambda a, r: plan.reduce(a, residual=r,
                                                 return_residual=True))
    carry = jax.tree.map(jnp.zeros_like, eng.state.qgz_residual)
    total = [np.zeros_like(w) for w in want]
    for _ in range(steps):
        out, carry = reduce_fb(acc, carry)
        total = [t + np.asarray(o) for t, o in zip(total, jax.tree.leaves(out))]
    assert one_step > 0.1                        # int4 does lose something
    assert worst(total, steps) <= 1.5 * one_step, (worst(total, steps), one_step)


def test_qgz_feedback_requires_quantized_gradients():
    cfg = dict(_BASE, zero_optimization=dict(_Z3, zero_quantized_gradients=True,
                                             zero_quantized_gradients_error_feedback=True))
    eng, _ = _train(cfg, steps=1)
    assert eng.state.qgz_residual is not None
    groups.reset()
    eng2, _ = _train(dict(_BASE, zero_optimization=dict(_Z3)), steps=1)
    assert eng2.state.qgz_residual is None


# ---------------------------------------------------------------- telemetry

def test_qgz_dcn_wire_ratio_bound(eight_devices):
    """The acceptance bound: at realistic payload (>= one full quant group
    per chunk) the DCN (dpr, int8) leg moves <= 0.3x the fp32 bytes and the
    ICI (dp, int4) leg less still. Trace-only — the lowering itself fires
    the traced record_comm calls."""
    from deepspeed_tpu.runtime.comm.coalesced_collectives import (
        all_to_all_quant_reduce)
    telemetry.configure(enabled=True)
    mesh = _mesh2d(eight_devices)
    grad = jax.ShapeDtypeStruct((8, 8192), jnp.float32)
    fn = shard_map(lambda g: all_to_all_quant_reduce(
        g, intra_axis="dp", inter_axis="dpr"),
        mesh=mesh, in_specs=P(), out_specs=P(("dpr", "dp")), check_vma=False)
    jax.jit(fn).lower(grad)
    a2a = telemetry.summary()["comm"]["ops"]["all_to_all_quant"]
    assert "dpr" in a2a and "dp" in a2a, sorted(a2a)
    for axis in ("dpr", "dp"):
        st = a2a[axis]
        assert 0 < st["wire_bytes"] <= 0.3 * st["bytes"], (axis, st)
    assert a2a["dp"]["wire_bytes"] / a2a["dp"]["bytes"] \
        < a2a["dpr"]["wire_bytes"] / a2a["dpr"]["bytes"]  # int4 < int8


def test_qgz_hpz_wire_bytes_telemetry():
    """Composed qwZ+qgZ+hpZ engine run: quantized collectives report true
    wire bytes on both hierarchy axes, and the hpZ primary exchange crosses
    DCN quantized. (The toy model's chunks are smaller than one quant group,
    so padding dominates here — the 0.3x ratio bound lives in
    test_qgz_dcn_wire_ratio_bound and scripts/perf_gate.py at real sizes.)"""
    telemetry.configure(enabled=True)
    cfg = dict(_BASE, zero_optimization=dict(
        _Z3, zero_hpz_partition_size=2, zero_quantized_gradients=True,
        zero_quantized_weights=True))
    _train(cfg, steps=1)
    s = telemetry.summary()
    ops = s["comm"]["ops"]
    a2a = ops["all_to_all_quant"]
    assert "dpr" in a2a and "dp" in a2a, sorted(a2a)
    for axis in ("dpr", "dp"):
        assert a2a[axis]["wire_bytes"] > 0, (axis, a2a[axis])
        assert a2a[axis]["wire_bytes"] != a2a[axis]["bytes"]
    hpz = ops["hpz_primary_exchange"]["dpr"]
    assert 0 < hpz["wire_bytes"] < hpz["bytes"], hpz
    assert s["comm"]["total_wire_bytes"] != s["comm"]["total_bytes"]
