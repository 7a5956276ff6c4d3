"""Long-context KV capacity tiering: int8 KV pages + the host-DRAM spill
tier.

Pins the two capacity axes end to end: (a) int8 paged KV — the fused
dequant-on-read Pallas kernel against its dense twin on identical quantized
pages, write-side quantization through the jitted forwards, bit-exact
generated-token parity int8 vs fp (greedy and seeded sampling) on the
8-device CPU mesh, and the >= 2x blocks-per-budget capacity claim; (b) the
host tier — prefix blocks spilled under pressure revive with their contents
intact (generation parity through a spill/restore round trip), live
sequences are never swapped while parked blocks can pay instead
(``swap_outs_live == 0``), the double-buffered ``HostKVSwapper`` bounds
in-flight landings, and every landing routes through the engine's accounted
``host_fetch``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
    _paged_attention_dense)
from deepspeed_tpu.inference.v2.ragged.ragged_manager import DSStateManager
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from deepspeed_tpu.ops.pallas.paged_attention import paged_mha
from deepspeed_tpu.ops.pallas.quant_collective import _quantize_rows_ref
from deepspeed_tpu.runtime.swap_tensor.kv_swapper import HostKVSwapper


@pytest.fixture(scope="module")
def served():
    cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
    model = LlamaForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 8)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    return cfg, model, params


def make_engine(cfg, model, params, kv_dtype="fp", host_kv_blocks=0,
                prefix_caching=False, num_kv_blocks=64, max_tokens=16,
                max_context=128):
    return InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": max_tokens,
                          "max_context": max_context,
                          "num_kv_blocks": num_kv_blocks,
                          "kv_dtype": kv_dtype,
                          "host_kv_blocks": host_kv_blocks},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"},
        "prefix_caching": prefix_caching})


# ---------------------------------------------------------------------------
# fused dequant-on-read kernel vs dense twin
# ---------------------------------------------------------------------------

def _quantize_pool(pool):
    """fp pool [NB, KV, bs, Dh] -> (int8 pool, fp32 scales [NB, KV, 1, bs])
    in the cache's per-token-row wire format."""
    NB, KV, bs, Dh = pool.shape
    q, scale = _quantize_rows_ref(pool.reshape(-1, Dh), 8)
    return (q.reshape(pool.shape),
            scale.reshape(NB, KV, bs)[:, :, None, :].astype(jnp.float32))


def make_int8_case(S=3, Q=1, H=4, KV=2, Dh=64, NB=10, bs=16, MB=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (S, Q, H, Dh), jnp.float32)
    kq, kscale = _quantize_pool(
        jax.random.normal(ks[1], (NB, KV, bs, Dh), jnp.float32))
    vq, vscale = _quantize_pool(
        jax.random.normal(ks[2], (NB, KV, bs, Dh), jnp.float32))
    rng = np.random.default_rng(seed)
    bt = rng.permutation((NB - 1) * MB)[: S * MB].reshape(S, MB) % (NB - 1)
    block_tables = jnp.asarray(bt, jnp.int32)
    seen = jnp.asarray(rng.integers(0, MB * bs - Q, size=S), jnp.int32)
    q_len = jnp.full((S,), Q, jnp.int32)
    return q, (kq, kscale), (vq, vscale), block_tables, seen, q_len


def valid_rows(out, q_len):
    S, Q = out.shape[:2]
    mask = np.arange(Q)[None, :] < np.asarray(q_len)[:, None]
    return np.asarray(out)[mask]


@pytest.mark.parametrize("Q", [1, 4])
def test_int8_kernel_matches_dense_dequant(Q):
    """The kernel's in-VMEM dequant (int8 pages + [1, bs] scale rows folded
    into score/probability columns) must match the dense gather-then-
    dequantize twin on identical quantized pages."""
    q, (kq, ks), (vq, vs), bt, seen, q_len = make_int8_case(Q=Q)
    out_k = paged_mha(q, kq, vq, bt, seen, q_len, k_scale=ks, v_scale=vs,
                      interpret=True)
    out_d = _paged_attention_dense(q, (kq, ks), (vq, vs), bt, seen,
                                   kq.shape[2])
    np.testing.assert_allclose(valid_rows(out_k, q_len),
                               valid_rows(out_d, q_len),
                               atol=2e-4, rtol=1e-3)


def test_int8_kernel_tracks_fp_reference():
    """Dequantized attention must stay close to attention over the
    dequantized fp pools — int8 costs precision, not correctness."""
    q, (kq, ks), (vq, vs), bt, seen, q_len = make_int8_case(seed=3)
    out_k = paged_mha(q, kq, vq, bt, seen, q_len, k_scale=ks, v_scale=vs,
                      interpret=True)
    # reconstruct the fp pools the quantizer saw (scale rows broadcast back)
    k_fp = kq.astype(jnp.float32) * jnp.swapaxes(ks, -1, -2)
    v_fp = vq.astype(jnp.float32) * jnp.swapaxes(vs, -1, -2)
    out_ref = _paged_attention_dense(q, k_fp, v_fp, bt, seen, kq.shape[2])
    np.testing.assert_allclose(valid_rows(out_k, q_len),
                               valid_rows(out_ref, q_len),
                               atol=2e-4, rtol=1e-3)


def test_int8_kernel_sliding_window():
    q, (kq, ks), (vq, vs), bt, seen, q_len = make_int8_case(S=2, Q=2, seed=5)
    out_k = paged_mha(q, kq, vq, bt, seen, q_len, k_scale=ks, v_scale=vs,
                      window=16, interpret=True)
    out_d = _paged_attention_dense(q, (kq, ks), (vq, vs), bt, seen,
                                   kq.shape[2], window=16)
    np.testing.assert_allclose(valid_rows(out_k, q_len),
                               valid_rows(out_d, q_len),
                               atol=2e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# int8 vs fp generation parity (the ISSUE's bit-parity generation gate)
# ---------------------------------------------------------------------------

def _prompts(cfg):
    rng = np.random.default_rng(31)
    prefix = rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
    return {uid: np.concatenate([prefix, rng.integers(
        0, cfg.vocab_size, 6 + 5 * uid).astype(np.int32)]) for uid in range(3)}


def _drive(cfg, model, params, kv_dtype, kw_fn, **engine_kw):
    engine = make_engine(cfg, model, params, kv_dtype=kv_dtype, **engine_kw)
    sched = SplitFuseScheduler(engine, token_budget=16)
    for uid, prompt in _prompts(cfg).items():
        sched.submit(uid, prompt, **kw_fn(uid))
    got = sched.run_to_completion()
    return {u: got[u].tolist() for u in got}, engine


def test_generation_parity_int8_vs_fp_greedy(served, eight_devices):
    """Greedy decode, int8 KV vs fp KV: generated token ids must match
    exactly — the parity gate for the quantized tier."""
    cfg, model, params = served
    kw = lambda u: {"max_new_tokens": 5}  # noqa: E731
    fp, _ = _drive(cfg, model, params, "fp", kw)
    q, engine = _drive(cfg, model, params, "int8", kw)
    assert q == fp
    assert engine._state.kv_cache.quantized


def _draw(logits, temperature, top_k, seed, position, gap):
    """``sampling._row_sample``'s draw from one row of host logits (top-k,
    then the argmax of scaled logits plus the Gumbel noise of ``(seed,
    position)``), and whether it is DECIDED by more than ``gap``: no move of
    every logit by at most ``gap`` can change it, because fewer than
    ``top_k`` other tokens can pass the drawn one, and no token that can be
    among the ``top_k`` can reach its perturbed score."""
    scaled = logits / temperature
    slack = 2 * gap / temperature
    kth = np.sort(scaled)[-top_k]
    noise = np.asarray(jax.random.gumbel(jax.random.fold_in(
        jax.random.PRNGKey(seed), position), scaled.shape, jnp.float32))
    score = np.where(scaled >= kth, scaled + noise, -np.inf)
    drawn = int(np.argmax(score))
    stays = np.sum(scaled > scaled[drawn] - slack) - 1 < top_k
    rivals = scaled >= kth - slack
    rivals[drawn] = False
    reach = np.max(np.where(rivals, scaled + noise, -np.inf))
    return drawn, bool(stays and score[drawn] - reach > slack)


def test_generation_parity_int8_vs_fp_sampled(served, eight_devices):
    """Seeded per-request sampling, int8 KV beside fp KV. int8 pages move
    every logit a little, so a draw that lies nearer one of the sampler's
    thresholds than that (the k-th largest logit, the runner-up's score) can
    fall the other way: identical ids at seeds picked blind were luck, and
    one request of three lost it from its first token on. What the pages can
    promise under sampling: along the fp stream the int8 logits stay within
    a bound of the fp ones (0.03 here, of a range of 1.0 over the
    vocabulary), and a draw decided by more than their gap is the same draw.
    So the test reads the fp logits, finds for each request a seed none of
    whose draws lies within the gap of a threshold, and holds the
    scheduler's sampled ids at THOSE seeds to be identical (the greedy test
    above holds at any)."""
    cfg, model, params = served
    temperature, top_k, new_tokens = 0.7, 8, 5
    engines = [make_engine(cfg, model, params, kv_dtype=d) for d in ("fp", "int8")]

    def feed(uid, tokens):          # -> the last token's logits, fp and int8
        tokens = np.asarray(tokens, np.int32)
        for start in range(0, len(tokens), 16):
            got = [np.asarray(e.put([uid], [tokens[start:start + 16]])[0], np.float32)
                   for e in engines]
        return got

    seeds, streams, draws, decided_draws = {}, {}, 0, 0
    for uid, prompt in _prompts(cfg).items():
        for tried, seed in enumerate(range(400 + 17 * uid, 700 + 17 * uid)):
            fp, q = feed(1000 * uid + tried, prompt)
            stream = []
            for position in range(new_tokens):
                gap = float(np.abs(q - fp).max())
                assert gap <= 0.03
                drawn, decided = _draw(fp, temperature, top_k, seed, position, gap + 1e-4)
                draws, decided_draws = draws + 1, decided_draws + decided
                if not decided:
                    break
                assert _draw(q, temperature, top_k, seed, position, 0.0)[0] == drawn
                stream.append(drawn)
                fp, q = feed(1000 * uid + tried, [drawn])
            for e in engines:
                e.flush(1000 * uid + tried)
            if len(stream) == new_tokens:
                seeds[uid], streams[uid] = seed, stream
                break
    assert len(seeds) == 3, "a seed with five decided draws within 300, a request"
    assert 0.25 < decided_draws / draws < 0.75      # the gap is no formality

    def kw(uid):
        return {"max_new_tokens": new_tokens, "temperature": temperature,
                "top_k": top_k, "seed": seeds[uid]}

    fp, _ = _drive(cfg, model, params, "fp", kw)
    q, _ = _drive(cfg, model, params, "int8", kw)
    assert fp == streams        # the draws read above ARE the scheduler's
    assert q == fp


def test_int8_pool_capacity_multiplier(served):
    """At equal HBM budget int8 pages (+ scales) hold >= 2x the blocks of
    the fp pool — measured on the REAL pool arrays, not the formula."""
    cfg, model, params = served
    fp_eng = make_engine(cfg, model, params, kv_dtype="fp")
    q_eng = make_engine(cfg, model, params, kv_dtype="int8")

    def pool_bytes(kv):
        total = kv.k_pool.nbytes + kv.v_pool.nbytes
        if kv.quantized:
            total += kv.k_scale.nbytes + kv.v_scale.nbytes
        return total

    fp_bytes = pool_bytes(fp_eng._state.kv_cache)
    q_bytes = pool_bytes(q_eng._state.kv_cache)
    assert fp_bytes / q_bytes >= 2.0, \
        f"int8 pages must at least halve KV bytes/block ({fp_bytes}/{q_bytes})"
    # and the budget-derived block count reflects it
    kv_cfg = fp_eng._config.kv_cache
    fp_blocks = DSStateManager._blocks_from_memory_budget(
        2, 2, 64, kv_cfg, kv_dtype="fp")
    q_blocks = DSStateManager._blocks_from_memory_budget(
        2, 2, 64, kv_cfg, kv_dtype="int8")
    assert q_blocks >= 2 * fp_blocks


# ---------------------------------------------------------------------------
# host-DRAM tier at the engine level
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_prefix_blocks_spill_and_revive_without_live_swaps(served, kv_dtype):
    """Under pool pressure parked prefix blocks spill to the host tier and a
    later shared-prefix request revives them — with the restored generation
    bit-identical to an unpressured engine's and ``swap_outs_live == 0``
    (no live sequence ever paid the preemption path)."""
    cfg, model, params = served
    rng = np.random.default_rng(47)
    warm = rng.integers(0, cfg.vocab_size, 40).astype(np.int32)
    filler = rng.integers(0, cfg.vocab_size, 60).astype(np.int32)
    reuse = np.concatenate(
        [warm, rng.integers(0, cfg.vocab_size, 6).astype(np.int32)])

    engine = make_engine(cfg, model, params, kv_dtype=kv_dtype,
                         prefix_caching=True, num_kv_blocks=12,
                         host_kv_blocks=16)
    sched = SplitFuseScheduler(engine, token_budget=16)
    sched.submit(0, warm, max_new_tokens=2)
    sched.run_to_completion()   # parks warm's full blocks
    sched.submit(1, filler, max_new_tokens=2)
    sched.run_to_completion()   # pressure: parked blocks spill to host
    stats = engine.kv_stats()
    assert stats["kv_spilled"] >= 1, "pressure must spill parked blocks"
    assert stats["host_kv_blocks"] >= 1
    # host-resident blocks hold no HBM: total/occupancy/occupied stay the
    # DEVICE census, so spilling can't inflate the ratcheted occupancy gauge
    alloc = engine._state.kv_cache.allocator
    assert stats["total_blocks"] == alloc.num_blocks
    assert stats["occupied_blocks"] == alloc.live_blocks
    assert stats["occupancy"] == pytest.approx(
        alloc.live_blocks / alloc.num_blocks)
    assert 0.0 <= stats["peak_occupancy"] <= 1.0
    sched.submit(2, reuse, max_new_tokens=4)
    out = sched.run_to_completion()[2].tolist()
    stats = engine.kv_stats()
    assert stats["kv_restored"] >= 1, "the shared prefix must restore"
    assert stats["swap_outs_live"] == 0, \
        "parked blocks must pay for pressure before any live swap"
    assert stats["kv_spilled"] == stats["kv_restored"] + \
        stats["kv_dropped"] + stats["host_kv_blocks"]
    assert sched.prefill_tokens_saved > 0

    # parity: an unpressured engine generates the same tokens for uid 2 —
    # the spill/restore round trip preserved the KV bytes exactly
    ref_engine = make_engine(cfg, model, params, kv_dtype=kv_dtype,
                             num_kv_blocks=64)
    ref = SplitFuseScheduler(ref_engine, token_budget=16)
    ref.submit(2, reuse, max_new_tokens=4)
    assert ref.run_to_completion()[2].tolist() == out


def test_spill_landings_route_through_accounted_host_fetch(served):
    """Every device->host landing of spill traffic goes through the
    engine's ``host_fetch`` — the host-sync ratchet and graftlint see KV
    swaps like any other boundary."""
    cfg, model, params = served
    engine = make_engine(cfg, model, params, prefix_caching=True,
                         num_kv_blocks=12, host_kv_blocks=16)
    sched = SplitFuseScheduler(engine, token_budget=16)
    rng = np.random.default_rng(48)
    sched.submit(0, rng.integers(0, cfg.vocab_size, 40).astype(np.int32),
                 max_new_tokens=2)
    sched.run_to_completion()
    base = engine.host_sync_count
    sched.submit(1, rng.integers(0, cfg.vocab_size, 60).astype(np.int32),
                 max_new_tokens=2)
    sched.run_to_completion()
    assert engine.kv_stats()["kv_spilled"] >= 1
    # force the pending double-buffered landings through
    engine._state.kv_cache.swapper.drain()
    assert engine._state.kv_cache.swapper.landings >= 1
    assert engine.host_sync_count > base + 2, \
        "spill landings must be accounted (not bare device_get)"


def test_host_kv_stats_fields(served):
    cfg, model, params = served
    engine = make_engine(cfg, model, params, host_kv_blocks=8)
    stats = engine.kv_stats()
    assert stats["host_kv_capacity"] == 8
    assert stats["host_kv_blocks"] == 0
    assert stats["host_kv_occupancy"] == 0.0
    assert stats["swap_outs_live"] == 0
    assert stats["kv_spilled"] == stats["kv_restored"] == \
        stats["kv_dropped"] == 0


# ---------------------------------------------------------------------------
# HostKVSwapper double buffering
# ---------------------------------------------------------------------------

def test_swapper_bounds_pending_and_preserves_payloads():
    landed = []

    def fetch(arrays, what):
        landed.append(what)
        return tuple(np.asarray(a) for a in arrays)

    sw = HostKVSwapper(fetch, buffer_count=2)
    p1 = sw.submit((np.ones(4),))
    p2 = sw.submit((np.full(4, 2.0),))
    assert sw.pending == 2 and not landed    # within the buffer: deferred
    p3 = sw.submit((np.full(4, 3.0),))
    assert sw.pending == 2 and len(landed) == 1  # oldest landed to make room
    out = sw.land(p1)                         # already landed: cached
    assert np.all(out[0] == 1.0) and len(landed) == 1
    out = sw.land(p3)                         # jump the queue: force-land
    assert np.all(out[0] == 3.0) and len(landed) == 2
    sw.drain()
    assert sw.pending == 0 and len(landed) == 3
    assert sw.landings == 3
    out = sw.land(p2)                         # landed by drain
    assert np.all(out[0] == 2.0)


def test_swapper_uses_accounted_fetch_tag():
    tags = []

    def fetch(arrays, what):
        tags.append(what)
        return arrays

    sw = HostKVSwapper(fetch, buffer_count=1)
    sw.submit((np.zeros(2),))
    sw.drain()
    assert tags == ["kv_cache/spill"]
