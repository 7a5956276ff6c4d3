"""Speculative decoding on the shared paged KV pool (draft-then-verify).

The non-negotiable oracle is BIT-EXACTNESS: greedy speculative decode must
reproduce the non-speculative token stream token for token, and seeded
sampling must share the exact ``(seed, position)`` stream — speculation may
only change how many forwards the stream costs, never its content. Around
that: the verify forward's last column equals the plain forward's logits
bit-for-bit (the per-column matmul + optimization_barrier contract in
``llama.ragged_forward_verify``), rollback of rejected drafts never frees a
block another chain holds and never crosses the committed prefix-cache
boundary, the ``DraftPageAllocator`` sub-page class preserves the parent
census invariant, the n-gram drafter's lookup rules, and the SLO router
preferring a speculating replica once its accept-rate EWMA says it retires
more than one token per round.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.ragged.blocked_allocator import (
    BlockedAllocator, DraftPageAllocator)
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
from deepspeed_tpu.inference.v2.speculative import NgramDrafter
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM


@pytest.fixture(scope="module")
def served():
    cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
    model = LlamaForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 8)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    return cfg, model, params


def make_engine(cfg, model, params, spec=False, prefix_caching=False,
                num_kv_blocks=64, max_tokens=16, max_context=128,
                host_kv_blocks=0, max_drafts=4, draft_page_divisor=0):
    config = {
        "state_manager": {"max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": max_tokens,
                          "max_context": max_context,
                          "num_kv_blocks": num_kv_blocks,
                          "host_kv_blocks": host_kv_blocks},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"},
        "prefix_caching": prefix_caching,
    }
    if spec:
        config["speculative"] = {"enabled": True,
                                 "max_draft_tokens": max_drafts,
                                 "draft_page_divisor": draft_page_divisor}
    return InferenceEngineV2(model, params, config=config)


def _census(engine):
    cnt = engine._state.kv_cache.allocator.counts()
    assert cnt["free"] + cnt["live"] + cnt["cached"] == \
        cnt["total"] - cnt["host"], cnt
    return cnt


def _repetitive_prompts(cfg, n=3, seed=0, max_len=40):
    """Template-heavy prompts (tiled short patterns) — the workload class
    prompt-lookup speculation exists for: the greedy continuation of a tiny
    model over a periodic context tends to continue the period, so the
    n-gram drafter lands accepts deterministically (fixed seeds)."""
    rng = np.random.default_rng(seed)
    out = {}
    for uid in range(n):
        pat = rng.integers(0, cfg.vocab_size,
                           int(rng.integers(2, 5))).astype(np.int32)
        reps = int(rng.integers(4, 8))
        out[uid] = np.tile(pat, reps)[:max_len]
    return out


# ---------------------------------------------------------------------------
# drafter
# ---------------------------------------------------------------------------

def test_ngram_drafter_longest_suffix_wins():
    d = NgramDrafter(ngram_max=3)
    # the 3-gram suffix (1,2,3) recurs at position 0; propose what followed
    assert d.draft([1, 2, 3, 9, 1, 2, 3], 2) == [9, 1]
    assert d.draft([1, 2, 3, 9, 1, 2, 3], 4) == [9, 1, 2, 3]


def test_ngram_drafter_falls_back_to_shorter_ngrams():
    d = NgramDrafter(ngram_max=3)
    # no 3- or 2-gram recurs; the 1-gram (7) does, then chains to fill k
    assert d.draft([5, 6, 7, 7], 3) == [7, 7, 7]
    # nothing recurs at all -> no drafts, the round degrades to plain decode
    assert d.draft([1, 2, 3, 4], 3) == []


def test_ngram_drafter_chains_past_short_follow_window():
    """A cyclic tail's most recent match sits one period back, so a single
    lookup can never draft more than the period — chaining the draft into
    the lookup context must fill the full k budget."""
    d = NgramDrafter(ngram_max=3)
    ctx = [1, 2, 3, 4] * 3
    assert d.draft(ctx, 7) == [1, 2, 3, 4, 1, 2, 3]
    assert d.draft(ctx, 2) == [1, 2]


def test_ngram_drafter_most_recent_occurrence_wins():
    d = NgramDrafter(ngram_max=2)
    # (1,2) occurs at 0 (followed by 8) and at 3 (followed by 9): recency
    assert d.draft([1, 2, 8, 1, 2, 9, 1, 2], 1) == [9]


def test_ngram_drafter_edges():
    d = NgramDrafter(ngram_max=3)
    assert d.draft([1, 2, 1], 0) == []
    assert d.draft([1], 4) == []
    assert d.draft([], 4) == []
    with pytest.raises(ValueError, match="ngram_max"):
        NgramDrafter(ngram_max=0)


# ---------------------------------------------------------------------------
# verify forward bit-exactness (the oracle's numeric half)
# ---------------------------------------------------------------------------

def test_verify_forward_last_column_bit_exact(served, eight_devices):
    """``ragged_forward_verify``'s last column must equal plain
    ``ragged_forward``'s logits BIT-FOR-BIT over the same pools — the
    per-column-gather + optimization_barrier contract. Any drift here and
    greedy speculative decode diverges from the plain stream at near-argmax
    ties."""
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import \
        RaggedBatchWrapper

    cfg, model, params = served
    engine = make_engine(cfg, model, params)
    state = engine._state
    # two live rows with different chunk lengths: a 4-token (prefill-style)
    # chunk and a 1-token decode chunk, exercising the q_len-dependent
    # column clip on both sides
    chunks = {1: np.array([2, 3, 4, 5], np.int32),
              2: np.array([7], np.int32)}
    for uid, c in chunks.items():
        seq = state.get_or_create_sequence(uid)
        state.ensure_capacity(seq, len(c))
    sm = engine._config.state_manager
    wrapper = RaggedBatchWrapper(sm.max_ragged_sequence_count,
                                 sm.max_ragged_batch_size,
                                 engine._max_blocks_per_seq,
                                 state.kv_cache.trash_block)
    for uid, c in chunks.items():
        wrapper.insert_sequence(uid, c, 0,
                                state.get_sequence(uid).kv_blocks)
    arrays = wrapper.build()
    kv = state.kv_cache
    mc = engine._model_config

    def args():
        # fresh pool copies per call: both forwards donate their pools
        return (engine._params,
                {"kv": (jnp.array(kv.k_pool), jnp.array(kv.v_pool))},
                jnp.asarray(arrays["tokens"]), jnp.asarray(arrays["q_len"]),
                jnp.asarray(arrays["seen"]),
                {"kv": jnp.asarray(arrays["block_tables"])})

    plain, _ = engine._ragged_forward(mc, *args())
    for k_max in (2, 4, 8):
        ver, _ = engine._verify_forward(mc, *args(), k_max)
        assert ver.shape[1] == k_max
        for row in range(len(chunks)):
            np.testing.assert_array_equal(
                np.asarray(ver[row, -1]), np.asarray(plain[row]),
                err_msg=f"k_max={k_max} row={row}: verify last column must "
                        f"be bit-identical to the plain forward")


# ---------------------------------------------------------------------------
# scheduler parity: greedy + seeded sampling (the oracle)
# ---------------------------------------------------------------------------

def _run_sched(cfg, model, params, prompts, spec, kw_fn=None, **eng_kw):
    engine = make_engine(cfg, model, params, spec=spec, **eng_kw)
    sched = SplitFuseScheduler(engine, token_budget=16)
    for uid, p in prompts.items():
        sched.submit(uid, p, **(kw_fn(uid) if kw_fn
                                else {"max_new_tokens": 10}))
    got = sched.run_to_completion()
    return {u: got[u].tolist() for u in got}, sched, engine


def test_greedy_parity_and_acceptance(served, eight_devices):
    """Greedy speculative decode reproduces the non-speculative stream token
    for token, actually accepts drafts on the template workload, and leaves
    the pool fully drained (census invariant)."""
    cfg, model, params = served
    prompts = _repetitive_prompts(cfg, n=3, seed=1)
    off, _, _ = _run_sched(cfg, model, params, prompts, spec=False)
    on, sched, engine = _run_sched(cfg, model, params, prompts, spec=True)
    assert on == off, "speculative greedy must be bit-exact with plain"
    assert sched.speculated_tokens > 0, "workload must actually draft"
    assert sched.accepted_tokens > 0, "template workload must accept drafts"
    assert sched.speculated_tokens == \
        sched.accepted_tokens + sched.rejected_tokens
    # accepts feed the router's live throughput signal
    assert sched.tokens_per_round() > 1.0
    cnt = _census(engine)
    assert cnt["live"] == 0, "finished requests must free every block"


def test_seeded_sampling_parity(served, eight_devices):
    """Seeded per-request sampling shares the (seed, position) stream: the
    speculative run emits exactly the plain run's tokens, over rounds in
    which verify chunks ran (an accepted draft is by construction the token
    plain decode would have drawn; where a draft is rejected, the token in
    its place is the verify sampler's draw at that column)."""
    cfg, model, params = served
    prompts = _repetitive_prompts(cfg, n=3, seed=2)

    def kw(uid):
        # low temperature: a random-weight tiny model rarely re-samples its
        # own context at high temp. And twelve tokens, not eight: the n-gram
        # drafter fires once a sampled token has occurred before, which in
        # eight tokens after these prompts it never did (the test then
        # compared two plain runs); in twelve it does on every prompt seed
        # 1-10 at temperatures 0.1-0.4 (here 10 drafted, 1 accepted)
        return {"max_new_tokens": 12, "temperature": 0.2, "top_k": 12,
                "seed": 500 + uid * 7}

    off, _, _ = _run_sched(cfg, model, params, prompts, spec=False, kw_fn=kw)
    on, sched, _ = _run_sched(cfg, model, params, prompts, spec=True,
                              kw_fn=kw)
    assert on == off, "speculative sampling must share the seeded stream"
    assert sched.speculated_tokens > 0, \
        "sampled rows must actually run verify chunks"
    assert sched.rejected_tokens > 0, \
        "a rejected draft is replaced by the verify sampler's own draw"
    assert sched.speculated_tokens == \
        sched.accepted_tokens + sched.rejected_tokens


def test_greedy_parity_mixed_random_prompts(served, eight_devices):
    """Random (non-template) prompts rarely draft well — parity must hold
    regardless, including rows where the drafter returns nothing and the
    round degrades to plain decode, mixed with mid-prefill rows."""
    cfg, model, params = served
    rng = np.random.default_rng(3)
    prompts = {0: rng.integers(0, cfg.vocab_size, 29).astype(np.int32),
               1: rng.integers(0, cfg.vocab_size, 5).astype(np.int32),
               2: np.tile(rng.integers(0, cfg.vocab_size, 3), 9)
                  .astype(np.int32)}
    kw = lambda uid: {"max_new_tokens": 6}  # noqa: E731
    off, _, _ = _run_sched(cfg, model, params, prompts, spec=False, kw_fn=kw)
    on, _, _ = _run_sched(cfg, model, params, prompts, spec=True, kw_fn=kw)
    assert on == off


def test_eos_inside_accepted_run_stops_exactly(served, eight_devices):
    """When the eos token lands mid-accepted-run the emission truncates AT
    eos — exactly where the plain stream stops — instead of emitting the
    accepted tail past it."""
    cfg, model, params = served
    prompts = _repetitive_prompts(cfg, n=1, seed=1)
    off, _, _ = _run_sched(cfg, model, params, prompts, spec=False)
    eos = off[0][2]  # third greedy token becomes the eos

    def kw(uid):
        return {"max_new_tokens": 10, "eos_token_id": eos}

    off_eos, _, _ = _run_sched(cfg, model, params, prompts, spec=False,
                               kw_fn=kw)
    on_eos, _, _ = _run_sched(cfg, model, params, prompts, spec=True,
                              kw_fn=kw)
    assert on_eos == off_eos
    assert on_eos[0][-1] == eos and eos not in on_eos[0][:-1]


# ---------------------------------------------------------------------------
# speculation x preemption / prefix cache / host spill interleavings
# ---------------------------------------------------------------------------

def test_spec_parity_under_preemption(served, eight_devices):
    """A pool too small for both requests forces host-swap preemption mid
    run; the speculative leg must still match the plain leg token for token
    (rolled-back cursors and swapped sequences never mix)."""
    cfg, model, params = served
    rng = np.random.default_rng(4)
    pat = rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
    prompts = {0: np.tile(pat, 11),  # 44 tokens
               1: np.tile(pat + 1, 11)}
    kw = lambda uid: {"max_new_tokens": 6}  # noqa: E731
    off, _, eng_off = _run_sched(cfg, model, params, prompts, spec=False,
                                 kw_fn=kw, num_kv_blocks=10)
    on, sched, eng_on = _run_sched(cfg, model, params, prompts, spec=True,
                                   kw_fn=kw, num_kv_blocks=10)
    assert on == off
    assert all(len(v) == 6 for v in on.values())
    assert eng_on.swap_stats["swap_outs"] >= 1, \
        "the tight pool must actually preempt the speculative leg"
    assert sched.speculated_tokens > 0
    _census(eng_on)


def _waves_run(cfg, model, params, waves, spec, caching, **eng_kw):
    """Staggered submit waves interleaved with steps (later requests arrive
    mid-generation of earlier ones) — the prefix-cache revive interleaving."""
    engine = make_engine(cfg, model, params, spec=spec,
                         prefix_caching=caching, **eng_kw)
    sched = SplitFuseScheduler(engine, token_budget=16)
    for wave in waves:
        for uid, prompt, kw in wave:
            sched.submit(uid, prompt, **kw)
        for _ in range(2):
            if sched.has_work:
                sched.step()
    got = sched.run_to_completion()
    return {u: got[u].tolist() for u in got}, sched, engine


def _template_waves(cfg, seed, kw_fn):
    """Three waves over two shared template prefixes: waves 2/3 reuse the
    wave-1 prefixes (prefix-cache hits) and the tiled structure drafts."""
    rng = np.random.default_rng(seed)
    pool_a = np.tile(rng.integers(0, cfg.vocab_size, 4), 6).astype(np.int32)
    pool_b = np.tile(rng.integers(0, cfg.vocab_size, 3), 6).astype(np.int32)

    def mk(pool, n_suffix):
        return np.concatenate(
            [pool, rng.integers(0, cfg.vocab_size,
                                n_suffix).astype(np.int32)])

    return [
        [(0, mk(pool_a, 5), kw_fn(0)), (1, mk(pool_b, 3), kw_fn(1))],
        [(2, mk(pool_a, 9), kw_fn(2))],
        [(3, mk(pool_b, 7), kw_fn(3)), (4, mk(pool_a, 2), kw_fn(4))],
    ]


def test_spec_parity_with_prefix_cache_interleaving(served, eight_devices):
    """All four legs of the (speculate x prefix-cache) square emit identical
    streams over staggered shared-prefix waves, the caching legs actually
    share blocks, and deferred commit keeps rejected drafts out of the
    chain-digest cache (the revived chains keep matching)."""
    cfg, model, params = served
    waves = _template_waves(cfg, 5, lambda u: {"max_new_tokens": 6})
    legs = {}
    for spec in (False, True):
        for caching in (False, True):
            out, sched, engine = _waves_run(cfg, model, params, waves,
                                            spec=spec, caching=caching)
            legs[(spec, caching)] = (out, sched, engine)
    base = legs[(False, False)][0]
    for key, (out, _, _) in legs.items():
        assert out == base, f"leg {key} diverged from plain uncached"
    _, sched_on, eng_on = legs[(True, True)]
    assert sched_on.speculated_tokens > 0
    assert eng_on._state.prefix_cache.hits >= 2, \
        "workload must actually exercise sharing under speculation"
    cnt = _census(eng_on)
    assert cnt["live"] == 0


def test_spec_parity_with_host_spill_and_revive(served, eight_devices):
    """Speculation over the full pressure ladder: parked prefix blocks spill
    to the host tier, an unrelated large request evicts, and a later shared
    prompt revives through a restore — parity with the plain leg holds and
    the spill/restore actually happened."""
    cfg, model, params = served
    rng = np.random.default_rng(6)
    warm = np.tile(rng.integers(0, cfg.vocab_size, 4), 10).astype(np.int32)
    big = rng.integers(0, cfg.vocab_size, 60).astype(np.int32)
    revive = np.concatenate(
        [warm, rng.integers(0, cfg.vocab_size, 6).astype(np.int32)])

    def run(spec):
        engine = make_engine(cfg, model, params, spec=spec,
                             prefix_caching=True, num_kv_blocks=12,
                             host_kv_blocks=16, max_context=256)
        sched = SplitFuseScheduler(engine, token_budget=16)
        out = {}
        for uid, prompt, new in ((0, warm, 4), (1, big, 2), (2, revive, 4)):
            sched.submit(uid, prompt, max_new_tokens=new)
            sched.run_to_completion()
        return ({u: v.tolist() for u, v in sched.results().items()},
                sched, engine)

    off, _, eng_off = run(False)
    on, sched, eng_on = run(True)
    assert on == off
    assert sched.speculated_tokens > 0
    assert eng_on.kv_stats()["kv_spilled"] >= 1
    assert eng_on.kv_stats()["kv_restored"] >= 1
    _census(eng_on)


# ---------------------------------------------------------------------------
# rollback semantics on the paged cursor
# ---------------------------------------------------------------------------

def test_rollback_frees_private_tail_and_census(served):
    cfg, model, params = served
    engine = make_engine(cfg, model, params, max_tokens=32)
    prompt = np.arange(20, dtype=np.int32)
    engine.put([1], [prompt])
    seq = engine._state.get_sequence(1)
    assert seq.seen_tokens == 20 and len(seq.kv_blocks) == 3
    free_before = engine.free_blocks
    engine.rollback(1, 5)  # 15 seen -> 2 blocks keep, 1 freed
    assert seq.seen_tokens == 15 and len(seq.kv_blocks) == 2
    assert engine.free_blocks == free_before + 1
    engine.rollback(1, 0)  # no-op
    assert seq.seen_tokens == 15
    with pytest.raises(ValueError, match="untracked"):
        engine.rollback(99, 1)
    engine.flush(1)
    cnt = _census(engine)
    assert cnt["free"] == cnt["total"]


def test_rollback_never_frees_shared_blocks_or_crosses_commit(served):
    """The COW boundary under rollback: a sequence sharing committed prefix
    blocks with another chain rolls back only its private tail — shared
    refcounts are untouched — and rolling past the committed boundary is an
    invariant violation, not a silent free."""
    cfg, model, params = served
    engine = make_engine(cfg, model, params, prefix_caching=True)
    state = engine._state
    alloc = state.kv_cache.allocator
    sched = SplitFuseScheduler(engine, token_budget=16)
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    sched.submit(0, prefix, max_new_tokens=2)
    sched.run_to_completion()  # parks the prompt's 3 full blocks

    tail2 = np.concatenate(
        [prefix[:16], rng.integers(0, cfg.vocab_size, 9).astype(np.int32)])
    assert engine.match_prefix(1, tail2) == 16
    assert engine.match_prefix(2, tail2) == 16  # second holder of the prefix
    seq = state.get_sequence(1)
    shared = list(seq.kv_blocks)
    assert all(alloc.refcount(b) == 2 for b in shared)

    # simulate a verify chunk's cursor advance past the shared prefix:
    # 9 more tokens -> seen 25, 4 blocks, digests still the 2 committed
    state.ensure_capacity(seq, 9)
    seq.seen_tokens += 9
    seq.tokens += [int(t) for t in tail2[16:25]]
    assert len(seq.kv_blocks) == 4 and len(seq.digests) == 2

    engine.rollback(1, 7)  # seen 18: private block 4 frees, block 3 stays
    assert seq.seen_tokens == 18 and len(seq.kv_blocks) == 3
    assert all(alloc.refcount(b) == 2 for b in shared), \
        "rollback must never free a block another chain holds"
    _census(engine)
    with pytest.raises(AssertionError, match="committed prefix-cache"):
        engine.rollback(1, 3)  # seen 15 would cross the 2-block boundary
    state.flush_sequence(1)
    state.flush_sequence(2)
    cnt = _census(engine)
    assert cnt["live"] == 0


# ---------------------------------------------------------------------------
# draft page-size class on the shared pool
# ---------------------------------------------------------------------------

def test_draft_page_allocator_lifecycle_and_parent_census():
    parent = BlockedAllocator(8)
    d = parent.draft_pages(4)
    assert isinstance(d, DraftPageAllocator) and d.pages_per_block == 4
    pages = d.allocate(6)  # 2 parent blocks, 8 pages, 6 live
    assert len(pages) == len(set(pages)) == 6
    assert d.counts() == {"free_pages": 2, "live_pages": 6,
                          "held_blocks": 2, "pages_per_block": 4}
    # draft pages are ordinary live tenants of the parent census
    cnt = parent.counts()
    assert cnt["live"] == 2 and cnt["free"] == 6
    assert all(p // 4 in {pages[0] // 4, pages[-1] // 4} for p in pages)
    d.free(pages[:3])
    assert d.free_pages == 5 and parent.counts()["live"] == 2
    d.free([pages[3]])  # last live page of its parent block -> block returns
    released = parent.counts()
    assert released["live"] + d.held_blocks * 0 <= 2
    assert d.live_pages == 2
    d.free(pages[4:])
    assert d.counts() == {"free_pages": 0, "live_pages": 0,
                          "held_blocks": 0, "pages_per_block": 4}
    assert parent.counts()["free"] == 8, \
        "all parent blocks must return when their sub-pages drain"
    with pytest.raises(ValueError, match="non-live draft page"):
        d.free([pages[0]])
    with pytest.raises(ValueError, match="pages_per_block"):
        parent.draft_pages(1)


def test_draft_page_allocator_all_or_nothing_and_random_census():
    parent = BlockedAllocator(4)
    d = parent.draft_pages(4)
    other = parent.allocate(3)  # only 1 parent block left = 4 pages
    with pytest.raises(ValueError, match="free"):
        d.allocate(5)
    assert d.counts()["held_blocks"] == 0, "failed allocate must not hold"
    parent.free(other)

    rng = np.random.default_rng(8)
    live = []
    for _ in range(300):
        if live and (rng.random() < 0.5 or parent.free_blocks == 0
                     and d.free_pages == 0):
            k = int(rng.integers(1, len(live) + 1))
            idx = rng.choice(len(live), size=k, replace=False)
            for i in sorted(idx, reverse=True):
                d.free([live.pop(i)])
        else:
            want = int(rng.integers(1, 6))
            if want > d.free_pages + parent.free_blocks * 4:
                continue
            live.extend(d.allocate(want))
        cnt = parent.counts()
        assert cnt["free"] + cnt["live"] + cnt["cached"] == cnt["total"]
        assert d.live_pages == len(live)
        assert d.free_pages + d.live_pages == d.held_blocks * 4
        assert cnt["live"] == d.held_blocks
    for p in live:
        d.free([p])
    assert parent.counts()["free"] == 4


def test_engine_wires_draft_page_class(served):
    cfg, model, params = served
    engine = make_engine(cfg, model, params, spec=True, draft_page_divisor=4)
    d = engine._state.draft_pages
    assert d is not None and d.pages_per_block == 4
    pages = d.allocate(3)
    cnt = _census(engine)
    assert cnt["live"] == 1  # one parent block carved for the draft class
    d.free(pages)
    assert _census(engine)["live"] == 0
    # divisor 0 (default) keeps the class off
    plain = make_engine(cfg, model, params, spec=True)
    assert plain._state.draft_pages is None


# ---------------------------------------------------------------------------
# config / guard rails
# ---------------------------------------------------------------------------

def test_spec_requires_device_sampling_and_verify_fn(served):
    cfg, model, params = served
    engine = make_engine(cfg, model, params, spec=True)
    with pytest.raises(ValueError, match="device_sampling"):
        SplitFuseScheduler(engine, device_sampling=False)
    # spec disabled: host sampling stays legal
    SplitFuseScheduler(make_engine(cfg, model, params),
                       device_sampling=False)
    assert engine.verify_supported


def test_spec_disabled_counters_stay_zero(served, eight_devices):
    cfg, model, params = served
    prompts = _repetitive_prompts(cfg, n=1, seed=9)
    _, sched, _ = _run_sched(cfg, model, params, prompts, spec=False)
    assert sched.speculated_tokens == 0
    assert sched.accepted_tokens == 0
    assert sched.rejected_tokens == 0
    assert sched.tokens_per_round() == 1.0


# ---------------------------------------------------------------------------
# SLO router: accept-rate EWMA wins placement
# ---------------------------------------------------------------------------

class _StubSched:
    """Router-target stand-in exposing exactly the load-signal surface."""

    def __init__(self, tokens_per_round=None):
        self.budget = 4
        self.max_context = 128
        if tokens_per_round is not None:
            self.tokens_per_round = lambda: tokens_per_round

    def kv_stats(self):
        return {"occupancy": 0.2}

    def peek_prefix(self, prompt):
        return 0

    def active_count(self):
        return 0


class _StubBackend:
    def __init__(self, targets):
        self._targets = targets
        self.placed = []

    def router_targets(self):
        return [(None, t) for t in self._targets]

    def submit(self, uid, prompt, replica=None, **kw):
        self.placed.append((uid, replica))

    def step(self):
        return []

    @property
    def has_work(self):
        return False

    def results(self):
        return {}


def test_router_prefers_speculating_backend_at_equal_occupancy():
    """The TTFT predictor bugfix: a backend whose accept-rate EWMA says it
    retires 3 tokens/round needs fewer rounds for the same backlog, so at
    equal occupancy and zero backlog it wins placement — and a legacy target
    without ``tokens_per_round`` still prices at 1/round (no crash)."""
    from deepspeed_tpu.inference.v2.fleet import RequestAdmitted, SLORouter

    plain, spec = _StubSched(), _StubSched(tokens_per_round=3.0)
    backend = _StubBackend([plain, spec])  # spec second: not a tie-break win
    router = SLORouter(backend, slo_ttft_s=60.0, prefix_affinity=False)
    # 16 owed tokens over budget 4: plain needs 4 rounds, spec ceil(16/12)=2
    assert router.predicted_ttft(0, 16) > router.predicted_ttft(1, 16)
    out = router.submit(0, np.arange(16, dtype=np.int32), max_new_tokens=1)
    assert isinstance(out, RequestAdmitted) and out.replica == 1
    assert backend.placed == [(0, 1)]
    # EWMA floor: a degenerate signal below 1.0 never inflates the estimate
    slow = _StubSched(tokens_per_round=0.25)
    router2 = SLORouter(_StubBackend([plain, slow]), slo_ttft_s=60.0,
                        prefix_affinity=False)
    assert router2.predicted_ttft(0, 16) == router2.predicted_ttft(1, 16)


def test_disagg_load_report_carries_tokens_per_round(served):
    if len(jax.devices()) < 3:
        pytest.skip("fleet needs >= 3 devices")
    from deepspeed_tpu.inference.v2.fleet import PrefillDecodeFleet
    cfg, model, params = served
    fleet = PrefillDecodeFleet(
        model, params, prefill_replicas=2, decode_replicas=1,
        engine_config={"state_manager": {"max_ragged_sequence_count": 9,
                                         "max_ragged_batch_size": 64,
                                         "max_context": 96,
                                         "num_kv_blocks": 96},
                       "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}},
        token_budget=48)
    rep = fleet.load_report()
    assert all(r["tokens_per_round"] == 1.0 for r in rep["replicas"]), \
        "non-speculating replicas report the 1 token/round baseline"
