"""SplitFuse scheduler: chunked prefill + fused decode must produce exactly
the greedy continuation of an unchunked run (FastGen SplitFuse invariant)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM


@pytest.fixture(scope="module")
def served():
    cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
    model = LlamaForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 8)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    return cfg, model, params


def make_engine(cfg, model, params, max_tokens=16):
    return InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": max_tokens,
                          "max_context": 128,
                          "num_kv_blocks": 64},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})


def greedy_reference(model, params, prompt, n_new):
    """Full-recompute greedy decode through the training forward."""
    cur = np.asarray(prompt, np.int32)[None, :]
    out = []
    for _ in range(n_new):
        logits = model.apply({"params": params}, {"input_ids": jnp.asarray(cur)})
        tok = int(np.argmax(np.asarray(logits[0, -1], np.float32)))
        out.append(tok)
        cur = np.concatenate([cur, [[tok]]], axis=1)
    return np.asarray(out, np.int32)


def assert_near_greedy(got, model, params, prompt, margin=1e-2):
    """Every engine-emitted token must be (near-)argmax of the full-recompute
    distribution over the engine's own context. Incremental-KV and
    full-recompute forwards differ by ~1e-4 in reduction order, so exact
    token equality is only required when the top-2 margin exceeds ``margin``
    (random tiny models hit genuine near-ties)."""
    cur = np.asarray(prompt, np.int32)[None, :]
    for i, tok in enumerate(np.asarray(got).tolist()):
        logits = model.apply({"params": params}, {"input_ids": jnp.asarray(cur)})
        l = np.asarray(logits[0, -1], np.float32)
        best = int(np.argmax(l))
        assert tok == best or l[best] - l[tok] < margin, (
            f"step {i}: engine chose {tok} but argmax {best} leads by "
            f"{l[best] - l[tok]:.5f}")
        cur = np.concatenate([cur, [[tok]]], axis=1)  # follow engine context


def test_single_long_prompt_chunked(served):
    cfg, model, params = served
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, 37).astype(np.int32)  # > budget
    engine = make_engine(cfg, model, params, max_tokens=16)
    sched = SplitFuseScheduler(engine, token_budget=16)
    sched.submit(0, prompt, max_new_tokens=5)
    got = sched.run_to_completion()[0]
    assert len(got) == 5
    assert_near_greedy(got, model, params, prompt)


def test_mixed_prefill_and_decode(served):
    """Three staggered requests: long/short prompts chunk and fuse with
    running decodes; every output must equal its unbatched greedy run."""
    cfg, model, params = served
    rng = np.random.default_rng(2)
    prompts = {0: rng.integers(0, cfg.vocab_size, 29).astype(np.int32),
               1: rng.integers(0, cfg.vocab_size, 5).astype(np.int32),
               2: rng.integers(0, cfg.vocab_size, 18).astype(np.int32)}
    engine = make_engine(cfg, model, params, max_tokens=12)
    sched = SplitFuseScheduler(engine, token_budget=12)
    for uid, p in prompts.items():
        sched.submit(uid, p, max_new_tokens=4)
    got = sched.run_to_completion()
    for uid, p in prompts.items():
        assert len(got[uid]) == 4, f"uid {uid} incomplete"
        assert_near_greedy(got[uid], model, params, p)


def test_eos_stops_early(served):
    cfg, model, params = served
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    # find what greedy emits first, then use it as the eos token
    first = int(greedy_reference(model, params, prompt, 1)[0])
    engine = make_engine(cfg, model, params)
    sched = SplitFuseScheduler(engine)
    sched.submit(0, prompt, max_new_tokens=8, eos_token_id=first)
    got = sched.run_to_completion()[0]
    assert got.tolist() == [first]


def test_budget_respected(served):
    cfg, model, params = served
    engine = make_engine(cfg, model, params, max_tokens=8)
    sched = SplitFuseScheduler(engine, token_budget=8)
    rng = np.random.default_rng(4)
    sched.submit(0, rng.integers(0, cfg.vocab_size, 21).astype(np.int32),
                 max_new_tokens=2)
    sched.submit(1, rng.integers(0, cfg.vocab_size, 20).astype(np.int32),
                 max_new_tokens=2)
    # intercept the shared forward (put and put_sampled both route through
    # it) to check per-round token totals
    orig_fwd = engine._forward_device
    totals = []

    def spy(uids, chunks, **kw):
        totals.append(sum(len(c) for c in chunks))
        return orig_fwd(uids, chunks, **kw)

    engine._forward_device = spy
    sched.run_to_completion()
    assert totals and all(t <= 8 for t in totals)


def test_context_capacity_retires_request(served):
    """A request that hits max_context is retired with what it has instead of
    wedging the scheduler (and oversized prompts are rejected at submit)."""
    cfg, model, params = served
    engine = InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 2,
                          "max_ragged_batch_size": 16,
                          "max_context": 16, "num_kv_blocks": 8},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})
    sched = SplitFuseScheduler(engine)
    with pytest.raises(ValueError, match="cannot fit max_context"):
        sched.submit(9, np.arange(16, dtype=np.int32))
    rng = np.random.default_rng(5)
    sched.submit(0, rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                 max_new_tokens=10)
    got = sched.run_to_completion()[0]
    # 12 prompt + 4 generated fills the 16-token context; retired early
    assert 1 <= len(got) <= 4


def test_sampled_decode_reproducible_and_valid(served):
    """Per-request temperature sampling: deterministic per seed, tokens in
    vocab, different seeds may diverge."""
    cfg, model, params = served
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, 7).astype(np.int32)

    def run(seed):
        engine = make_engine(cfg, model, params)
        sched = SplitFuseScheduler(engine)
        sched.submit(0, prompt, max_new_tokens=6, temperature=0.8,
                     top_k=20, seed=seed)
        return sched.run_to_completion()[0].tolist()

    a1, a2, b = run(1), run(1), run(2)
    assert a1 == a2, "same seed must reproduce"
    assert all(0 <= t < cfg.vocab_size for t in a1 + b)
    assert len(a1) == 6 and len(b) == 6


def test_sampling_param_validation(served):
    cfg, model, params = served
    engine = make_engine(cfg, model, params)
    sched = SplitFuseScheduler(engine)
    p = np.arange(5, dtype=np.int32) + 1
    with pytest.raises(ValueError, match="temperature"):
        sched.submit(0, p, temperature=-0.5)
    with pytest.raises(ValueError, match="top_p"):
        sched.submit(1, p, temperature=0.5, top_p=0.0)
    with pytest.raises(ValueError, match="top_k"):
        sched.submit(2, p, temperature=0.5, top_k=-1)


# ---------------------------------------------------------------- KV swap

def test_kv_cache_swap_roundtrip():
    """Host swap tier (ZeRO-Inference KV offload analog): block contents
    survive a swap_out → swap_in cycle bit-exactly, and the ids are reusable
    by others in between."""
    from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
    kv = BlockedKVCache(num_layers=2, num_blocks=6, block_size=4,
                        num_kv_heads=2, head_dim=8, dtype="fp32")
    rng = np.random.default_rng(0)
    blocks = kv.reserve(3)
    fill_k = rng.standard_normal((2, 3, 2, 4, 8)).astype(np.float32)
    fill_v = rng.standard_normal((2, 3, 2, 4, 8)).astype(np.float32)
    idx = jnp.asarray(blocks)
    kv.update(kv.k_pool.at[:, idx].set(fill_k), kv.v_pool.at[:, idx].set(fill_v))
    free_before = kv.free_blocks
    handle = kv.swap_out(blocks)
    assert kv.free_blocks == free_before + 3
    # someone else takes (and dirties) the freed ids
    other = kv.reserve(3)
    kv.update(kv.k_pool.at[:, jnp.asarray(other)].set(-1.0), kv.v_pool)
    new_blocks = kv.swap_in(handle)
    np.testing.assert_array_equal(
        np.asarray(kv.k_pool[:, jnp.asarray(new_blocks)]), fill_k)
    np.testing.assert_array_equal(
        np.asarray(kv.v_pool[:, jnp.asarray(new_blocks)]), fill_v)


def test_scheduler_preempts_under_kv_pressure(served):
    """A KV pool too small for all requests at once: the scheduler host-swaps
    a decode's cache instead of starving, resumes it later, and every
    completion still matches its unbatched greedy run."""
    cfg, model, params = served
    rng = np.random.default_rng(7)
    prompts = {0: rng.integers(0, cfg.vocab_size, 44).astype(np.int32),
               1: rng.integers(0, cfg.vocab_size, 44).astype(np.int32)}
    # 10 blocks x 8 tokens: each request needs 44 + 6 = 50 tokens = 7 blocks.
    # Request 0 prefills to 6 blocks, request 1 stalls at the 4 remaining;
    # when 0's decode crosses into its 7th block nothing can schedule — the
    # deadlock the host-swap preemption exists to break (pre-swap behavior:
    # starvation RuntimeError after 3 rounds)
    engine = InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 16,
                          "max_context": 128,
                          "num_kv_blocks": 10},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})
    sched = SplitFuseScheduler(engine, token_budget=16)
    for uid, p in prompts.items():
        sched.submit(uid, p, max_new_tokens=6)
    outs = sched.run_to_completion()
    assert all(len(outs[u]) == 6 for u in prompts)
    stats = engine.swap_stats
    assert stats["swap_outs"] >= 1 and stats["swap_ins"] >= 1, stats
    for uid, p in prompts.items():
        assert_near_greedy(outs[uid], model, params, p)


def test_engine_rejects_swapped_sequence(served):
    """The ENGINE owns the swap invariant: a swapped-out sequence cannot be
    scheduled (attention over zeroed blocks) until resume()."""
    cfg, model, params = served
    engine = make_engine(cfg, model, params)
    prompt = np.arange(10, dtype=np.int32)
    engine.put([7], [prompt])
    engine.preempt(7)
    verdict = engine.can_schedule([7], [1])
    assert not verdict.success and "swapped" in verdict.reason
    with pytest.raises(RuntimeError, match="swapped"):
        engine.put([7], [np.asarray([1], np.int32)])
    engine.resume(7)
    assert engine.can_schedule([7], [1]).success
