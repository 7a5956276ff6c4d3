"""A serving round dispatched by chunk-length class
(``ragged_wrapper.dispatch_rows``): in a plain round the rows of ONE new
token together as ``[D, 1]`` and every other row alone as ``[1, C >= 16]``;
in a verify round the rows of at most ``max(8, k)`` tokens together as
``[D, max(8, k)]``. One ``[sequences x chunk]`` rectangle gave every row the
longest row's width.

The layout changes no result: every request emits what it emits when served
alone, and each dispatch's logits are those of ``ragged_forward`` on the
whole round padded into one rectangle. It bounds the programs: the shapes a
mixed run dispatches are those that the benchmark's warm-up calls reach.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2 import InferenceEngineV2, engine_v2
from deepspeed_tpu.inference.v2.engine_v2 import packed_forward
from deepspeed_tpu.inference.v2.model_implementations.llama import ragged_forward
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import (
    RaggedBatchWrapper, dispatch_rows, short_row_tokens, unpack)
from deepspeed_tpu.inference.v2.sampling import sample_rows_packed
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

MAX_SEQS, MAX_TOKENS = 8, 32
# (prompt tokens, new tokens, temperature, top_k, top_p, seed, submitted
# before round): lengths on both sides of 16 and of the 32-token budget, so
# that chunks of 2..32 tokens join 0..7 running decodes
REQUESTS = [(5, 12, 0.0, 0, 1.0, 0, 0), (40, 6, 0.8, 0, 1.0, 11, 0),
            (17, 9, 0.0, 0, 1.0, 0, 2), (9, 10, 1.1, 20, 0.9, 12, 2),
            (30, 5, 0.0, 0, 1.0, 0, 4), (3, 8, 0.7, 0, 0.95, 13, 4),
            (26, 7, 0.0, 0, 1.0, 0, 5), (12, 6, 0.9, 8, 1.0, 14, 7),
            (64, 4, 0.0, 0, 1.0, 0, 9)]


@pytest.fixture(scope="module")
def served():
    cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
    model = LlamaForCausalLM(cfg)
    ids = np.zeros((1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    rng = np.random.default_rng(28)
    prompts = [rng.integers(0, cfg.vocab_size, r[0]).astype(np.int32)
               for r in REQUESTS]
    return cfg, model, params, prompts


def _engine(model, params):
    return InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": MAX_SEQS,
                          "max_ragged_batch_size": MAX_TOKENS,
                          "max_context": 128, "num_kv_blocks": 96},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})


def _submit(sched, uid, prompt):
    _, n_new, temperature, top_k, top_p, seed, _ = REQUESTS[uid]
    sched.submit(uid, prompt, max_new_tokens=n_new, temperature=temperature,
                 top_k=top_k, top_p=top_p, seed=seed)


def _buckets(lo, hi):
    out, x = [], lo
    while x < hi:
        out.append(x)
        x *= 2
    return out + [hi]


def _family():
    """``{[D, 1]} + {[1, C >= 16]}`` at the test's limits."""
    return {(d, 1) for d in _buckets(4, MAX_SEQS)} | \
        {(1, c) for c in _buckets(16, MAX_TOKENS)}


def _warm_pass(engine):
    """The row recipe of the benchmark's warm-up (``_warm_shapes`` of
    benchmark/drivers/serve.py, copied): for every (s, q) one row of
    ``min(q, budget - (s - 1))`` tokens and s - 1 rows of one. Returns the
    set of batch shapes it dispatched."""
    shapes = set()
    for s in _buckets(4, MAX_SEQS):
        for q in _buckets(8, MAX_TOKENS):
            longest = min(q, MAX_TOKENS - (s - 1))
            if longest <= q // 2 and q > 8:
                continue
            uids = list(range(900_000, 900_000 + s))
            toks = [np.zeros(longest, np.int32)] + [np.zeros(1, np.int32)] * (s - 1)
            engine.put_sampled(uids, toks, temperatures=[0.0] * s, top_ks=[0] * s,
                               top_ps=[1.0] * s, seeds=[0] * s, positions=[0] * s)
            for u in uids:
                engine.flush(u)
            shapes.update(engine.last_batch_shapes)
    return shapes


class _Spy:
    """Stands in for the engine's program of a dispatch: keeps each
    dispatch's arrays, as its packed buffer holds them (a token the round
    before left on the device taken from there, as the program takes it),
    and logits, and a copy of the pools as they were when the round began
    (the program donates them)."""

    def __init__(self, engine):
        self.engine, self.rounds, self.device_tokens = engine, {}, 0

    def __call__(self, forward_fn, cfg, layout, params, cache, packed, kept, verify_k):
        rnd = self.rounds.setdefault(self.engine.round, {"dispatches": []})
        if not rnd["dispatches"]:
            rnd["pools"] = jax.tree.map(jnp.copy, cache)
        host = unpack(layout, np.asarray(packed))
        src, tokens = np.asarray(host["src"]), np.array(host["tokens"])
        tokens[:, 0] = np.where(src < 0, tokens[:, 0],
                                np.asarray(kept)[np.maximum(src, 0)])
        self.device_tokens += int(np.sum(src >= 0))
        out = packed_forward(forward_fn, cfg, layout, params, cache, packed, kept, verify_k)
        rnd["dispatches"].append(tuple(np.asarray(a) for a in (
            tokens, host["q_len"], host["seen"], host["kv"], out[0])))
        return out


@pytest.fixture(scope="module")
def mixed_run(served):
    """The requests served together, arriving while others decode: (engine,
    scheduler, spy, shapes of the warm pass, shapes of the run, programs
    compiled by the run)."""
    cfg, model, params, prompts = served
    engine = _engine(model, params)
    warm = _warm_pass(engine)
    compiled = lambda: (packed_forward._cache_size(), sample_rows_packed._cache_size())
    before = compiled()
    spy = _Spy(engine)
    sched = SplitFuseScheduler(engine)
    shapes, rnd = set(), 0
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(engine_v2, "packed_forward", spy)
        while rnd == 0 or sched.has_work:
            for uid, r in enumerate(REQUESTS):
                if r[6] == rnd:
                    _submit(sched, uid, prompts[uid])
            sched.step()
            shapes.update(engine.last_batch_shapes)
            rnd += 1
    assert rnd > max(r[6] for r in REQUESTS)
    new_programs = tuple(b - a for a, b in zip(before, compiled()))
    return engine, sched, spy, warm, shapes, new_programs


def test_every_request_emits_what_it_emits_alone(served, mixed_run):
    cfg, model, params, prompts = served
    together = mixed_run[1].results()
    engine = _engine(model, params)
    for uid, r in enumerate(REQUESTS):
        sched = SplitFuseScheduler(engine)
        _submit(sched, uid, prompts[uid])
        alone = sched.run_to_completion()[uid]
        assert len(alone) == r[1]
        assert together[uid].tolist() == alone.tolist(), uid


def test_each_dispatch_matches_the_round_as_one_rectangle(mixed_run):
    """``ragged_forward`` called directly on the round's rows padded into one
    [sequence bucket, chunk bucket] rectangle, as ``build()`` lays out any
    rows it is given, over the pools as the round found them."""
    engine, _, spy, _, _, _ = mixed_run
    sm = engine._config.state_manager
    split = compared = 0
    for rnd in spy.rounds.values():
        wrapper = RaggedBatchWrapper(sm.max_ragged_sequence_count,
                                     sm.max_ragged_batch_size,
                                     engine._max_blocks_per_seq,
                                     engine._state.kv_cache.trash_block)
        expected = []
        for tokens, q_len, seen, tables, logits in rnd["dispatches"]:
            for i in np.flatnonzero(q_len):
                wrapper.insert_sequence(len(expected), tokens[i, :q_len[i]],
                                        int(seen[i]), tables[i])
                expected.append(logits[i])
        rect = wrapper.build()
        assert rect["tokens"].shape[0] >= 4, "the rectangle pads to 4 rows"
        out, _ = ragged_forward(
            engine._model_config, engine._params, rnd["pools"],
            *(jnp.asarray(rect[k]) for k in ("tokens", "q_len", "seen")),
            {"kv": jnp.asarray(rect["block_tables"])})
        np.testing.assert_allclose(np.asarray(out)[:len(expected)], np.stack(expected),
                                   rtol=2e-4, atol=2e-4)
        split += len(rnd["dispatches"]) > 1
        compared += len(expected)
    assert split >= 5 and compared >= 50, (split, compared)
    assert spy.device_tokens > 0, "no round of this run was dispatched ahead"


def test_dispatched_shapes_are_the_ones_warm_up_reaches(mixed_run):
    _, sched, _, warm, shapes, new_programs = mixed_run
    assert warm == _family()
    assert shapes == warm, "the run is meant to reach every shape"
    assert new_programs == (0, 0), "the run compiled what the warm pass had not"
    assert sched.dispatches > sched.rounds
    assert 0 < sched.real_tokens <= sched.padded_slots


def test_the_recipe_leaves_one_forward_program_a_shape(served):
    """11 at the cells' limits (64 sequences, 512 tokens: ``{[D, 1]: 5} +
    {[1, C]: 6}``), scaled to this test's: the warm pass compiles one forward
    and one sampler program a shape and no other."""
    cfg, model, params, _ = served
    engine = _engine(model, params)
    jax.clear_caches()
    warm = _warm_pass(engine)
    assert warm == _family()
    assert packed_forward._cache_size() == len(warm)
    assert sample_rows_packed._cache_size() == len({s for s, _ in warm})


@pytest.mark.parametrize("lengths, short, expected", [
    # a plain round: the rows of one token together, one token wide
    ([1, 1, 1], 1, [([0, 1, 2], 4, 1)]),
    ([1, 300, 1, 8], 1, [([0, 2], 4, 1), ([1], 1, 16), ([3], 1, 16)]),
    ([9, 1, 200], 1, [([1], 4, 1), ([0], 1, 16), ([2], 1, 16)]),
    ([16], 1, [([0], 1, 16)]),
    ([2], 1, [([0], 1, 16)]),
    # a verify round: rows of [last] + drafts stay together
    ([1, 300, 1, 8], 8, [([0, 2, 3], 4, 8), ([1], 1, 16)]),
    ([5, 16, 17], 16, [([0, 1], 4, 16), ([2], 1, 16)]),
    ([], 1, []),
    ([], 8, []),
])
def test_dispatch_rows_by_class(lengths, short, expected):
    assert dispatch_rows(lengths, short) == expected


def test_a_plain_row_is_short_at_one_token_a_verify_row_whatever_the_width():
    assert short_row_tokens() == short_row_tokens(None) == short_row_tokens(0) == 1
    assert short_row_tokens(2) == short_row_tokens(4) == short_row_tokens(8) == 8
    assert short_row_tokens(16) == 16


def test_a_prompt_tail_of_five_tokens_goes_alone_beside_the_decode_rows(served):
    """Decode rows plus a prompt's last 5 tokens: the decode rows as [4, 1],
    the tail alone as [1, 16], each row's logits those of that row put
    alone."""
    cfg, model, params, prompts = served
    engine = _engine(model, params)
    uids = [0, 1, 2, 3]
    first = [prompts[1][:5], prompts[4][:3], prompts[6][:4], prompts[8][:11]]
    engine.put(uids, first)
    # rows 0..2 decode one token, row 3 ends its 16-token prompt 5 tokens on
    toks = [prompts[1][5:6], prompts[4][3:4], prompts[6][4:5], prompts[8][11:16]]
    together = engine.put(uids, toks)
    assert engine.last_batch_shapes == [(4, 1), (1, 16)]
    for u in uids:
        engine.flush(u)
    for u, a, b in zip(uids, first, toks):
        engine.put([u], [a])
        alone = engine.put([u], [b])
        engine.flush(u)
        np.testing.assert_allclose(together[u], alone[0], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("k_max, shapes", [
    (4, [(4, 8), (1, 16)]), (16, [(4, 16), (1, 32)])],
    ids=["k4", "k16"])
def test_a_verify_round_keeps_its_short_class(served, k_max, shapes):
    """Rows of ``[last] + drafts`` (1..max(8, k) tokens) go together as
    ``[D, max(8, k)]`` whatever a plain round does; a longer chunk alone."""
    cfg, model, params, prompts = served
    engine = _engine(model, params)
    uids = [0, 1, 2]
    toks = [prompts[1][:1], prompts[4][:min(k_max, 8)],
            prompts[8][:max(8, k_max) + 1]]
    n = len(uids)
    ids = engine.host_fetch(engine.put_verify_device(
        uids, toks, temperatures=[0.0] * n, top_ks=[0] * n, top_ps=[1.0] * n,
        seeds=[0] * n, positions=[0] * n, k_max=k_max), "test")
    assert engine.last_batch_shapes == shapes
    assert ids.shape == (n, k_max)


def test_put_returns_rows_in_the_order_given(served):
    """``put`` (host logits) over a round of five dispatches: every row's
    logits are those of that row put alone, in the order given."""
    cfg, model, params, prompts = served
    engine = _engine(model, params)
    uids = [0, 1, 2, 3, 4]
    toks = [prompts[4][:12], prompts[0][:3], prompts[1][:9], prompts[2][:1],
            prompts[3][:7]]
    base = engine.host_sync_count
    together = engine.put(uids, toks)
    assert engine.host_sync_count == base + 1
    assert sorted(engine.last_batch_shapes) == [(1, 16)] * 4 + [(4, 1)]
    assert together.shape == (5, cfg.vocab_size)
    for u in uids:
        engine.flush(u)
    for u, t in zip(uids, toks):
        alone = engine.put([u], [t])
        engine.flush(u)
        np.testing.assert_allclose(together[u], alone[0], rtol=2e-4, atol=2e-4)


# -- what a dispatch reports ------------------------------------------------------
# ``engine_v2.py`` and ``scheduler.py`` carry what the cache groups
# (``DSStateManager.dispatch_report``) and the family's module
# (``dispatch_report``, over ``moe_layer``) say of a dispatch, and read none of
# it (docs/SERVING.md, "What a dispatch reports")

@pytest.fixture
def build_spans():
    """``spans()``: the attributes of this test's ``serving/build`` spans."""
    telemetry.reset()
    telemetry.configure(enabled=True)
    yield lambda: [e["args"] for e in telemetry.get_telemetry().trace_events
                   if e["name"] == "serving/build"]
    telemetry.configure(enabled=False)
    telemetry.reset()


def _serve(sched, vocab_size, requests=((14, 6, 0.0), (21, 5, 0.8), (35, 6, 0.0))):
    rng = np.random.default_rng(3)
    for uid, (n, n_new, temperature) in enumerate(requests):
        sched.submit(uid, rng.integers(0, vocab_size, n).astype(np.int32),
                     max_new_tokens=n_new, temperature=temperature, seed=5)
    sched.run_to_completion()


def test_a_reporter_nobody_knows_reaches_the_spans_and_the_scheduler(served, build_spans):
    """A stand-in reporter adds a key no family has: it is on every
    ``serving/build`` span and its sum is the scheduler's, a key that only
    rides is on the spans and in no sum: ``engine_v2.py`` and
    ``scheduler.py`` carry keys they have never heard of."""
    cfg, model, params, _ = served
    asked = []

    def report(config, real_tokens, chunk):
        asked.append((config, real_tokens, chunk))
        return {"probe_pages": 3 + real_tokens}, {"probe_row_bytes": 96}

    engine = InferenceEngineV2(model, params, report_fn=report, config={
        "state_manager": {"max_ragged_sequence_count": MAX_SEQS,
                          "max_ragged_batch_size": MAX_TOKENS,
                          "max_context": 128, "num_kv_blocks": 96},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})
    sched = SplitFuseScheduler(engine)
    assert sched.probe_pages == 0
    _serve(sched, cfg.vocab_size)
    builds = build_spans()
    assert builds and len(builds) == sched.dispatches == len(asked) > sched.rounds > 3
    assert all(config is cfg for config, _, _ in asked)
    assert [(a["real_tokens"], a["chunk_bucket"]) for a in builds] \
        == [(n, chunk) for _, n, chunk in asked]
    assert all(a["probe_pages"] == 3 + a["real_tokens"] and a["probe_row_bytes"] == 96
               for a in builds)
    assert sched.probe_pages == sum(a["probe_pages"] for a in builds) \
        == 3 * sched.dispatches + sched.real_tokens
    assert type(sched.probe_pages) is int and "probe_row_bytes" not in sched.counts
    assert engine.last_counts["probe_pages"] == sum(
        a["probe_pages"] for a in builds if a["round"] == engine.round - 1)
    with pytest.raises(AttributeError):
        sched._no_such_private_name


def _served_family(name):
    """(model, params) of a served family's tiny preset, the expert families
    that the benchmark serves as one share of eight under ``experts_held``."""
    key = jax.random.PRNGKey(0)
    if name == "llama":
        model = LlamaForCausalLM(LlamaConfig.tiny(scan_layers=True, remat=False))
        return model, model.init(key, {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    if name == "phi4flash":
        from deepspeed_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM
        model = Phi4FlashForCausalLM(Phi4FlashConfig.tiny())
    elif name == "mellum2":
        from deepspeed_tpu.models.mellum2 import Mellum2Config, Mellum2ForCausalLM
        model = Mellum2ForCausalLM(Mellum2Config.tiny())
    elif name == "kanana2":
        from deepspeed_tpu.models.kanana2 import Kanana2Config, Kanana2ForCausalLM
        model = Kanana2ForCausalLM(Kanana2Config.tiny(experts_held=(4, 8)))
    elif name == "longcat_flash":
        from deepspeed_tpu.models.longcat_flash import (
            LongcatFlashConfig, LongcatFlashForCausalLM)
        model = LongcatFlashForCausalLM(LongcatFlashConfig.tiny(experts_held=(4, 8)))
    elif name == "kimi_linear":
        from deepspeed_tpu.models.kimi_linear import KimiLinearConfig, KimiLinearForCausalLM
        model = KimiLinearForCausalLM(KimiLinearConfig.tiny(experts_held=(4, 8)))
    else:
        from deepspeed_tpu.models.keye_vl2 import KeyeVL2Config, KeyeVL2ForCausalLM
        model = KeyeVL2ForCausalLM(KeyeVL2Config.tiny(experts_held=(4, 2)))
    return model, model.init_params(key)


_EVERY_BUILD = {"round", "dispatch", "seqs", "seq_bucket", "chunk_bucket", "real_tokens",
                "padded_slots", "context_tokens", "live_pages", "write_pages", "write_rows"}
_FURTHER_GROUPS = {"window_pages_freed", "state_slots", "global_pages", "window_pages",
                   "window_live_pages"}
_EXPERTS = {"expert_rows", "expert_rows_padded"}
_A_SHARE = {"experts_held", "experts_routed_over"}
#: which form a dispatch's tokens took through the latent read (``kanana2.latent_read_report``)
_LATENT_READ = {"latent_up_tokens", "latent_absorbed_tokens"}


@pytest.mark.parametrize("family,beyond,summed", [
    ("llama", set(), set()),
    ("phi4flash", _FURTHER_GROUPS, {"window_pages_freed", "state_slots"}),
    ("mellum2", _FURTHER_GROUPS | _EXPERTS, {"window_pages_freed", "state_slots"} | _EXPERTS),
    ("kanana2", _EXPERTS | _A_SHARE | _LATENT_READ | {"latent_pages", "latent_row_bytes"},
     _EXPERTS | _LATENT_READ | {"latent_pages"}),
    ("keye_vl2", _EXPERTS | _A_SHARE | {"index_pages", "index_row_bytes", "sparse_rows",
                                        "selected_tokens"},
     _EXPERTS | {"index_pages", "sparse_rows", "selected_tokens"}),
    # the counter group adds no name: what it counts is never on a span
    ("longcat_flash", _EXPERTS | _A_SHARE | _LATENT_READ | {
        "latent_pages", "latent_row_bytes", "zero_experts", "kv_planes"},
     _EXPERTS | _LATENT_READ | {"latent_pages"}),
    # a slot group beside the one-leaf pages: the state manager's further-group
    # names (no further PAGED group, so no ``<name>_live_pages``) and the KDA
    # layers' own
    ("kimi_linear", _EXPERTS | _A_SHARE | _LATENT_READ | {
        "latent_pages", "latent_row_bytes", "window_pages_freed", "state_slots",
        "global_pages", "window_pages", "kda_step_rows", "kda_chunk_tokens", "kda_layers"},
     _EXPERTS | _LATENT_READ | {"latent_pages", "window_pages_freed", "state_slots",
                                "kda_step_rows", "kda_chunk_tokens"})])
def test_the_build_spans_attributes_are_the_ones_the_benchmark_reads(
        family, beyond, summed, build_spans):
    """The SET of attribute names on ``serving/build`` a served family, as
    the benchmark's readers take them from the trace (PERF.md section 3):
    none dropped, none new, every one a plain int, and those a reporter adds
    to the round's counts summed by the scheduler under the same name."""
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    model, params = _served_family(family)
    sched = SplitFuseScheduler(build_engine(model, params, {
        "state_manager": {"max_ragged_sequence_count": 4, "max_ragged_batch_size": 16,
                          "max_context": 128, "num_kv_blocks": 64},
        "kv_cache": {"block_size": 4, "cache_dtype": "fp32"}}))
    _serve(sched, model.config.vocab_size)
    builds = build_spans()
    assert builds and all(set(a) == _EVERY_BUILD | beyond for a in builds)
    assert all(type(v) is int for a in builds for v in a.values())
    summed = summed | {"real_tokens", "padded_slots", "live_pages", "write_pages", "write_rows"}
    assert set(sched.counts) - {"ahead_rows_dropped"} == summed | {
        "rounds", "dispatches", "rounds_ahead", "ahead_rows", "dispatches_sorted"}
    for key in summed:
        assert getattr(sched, key) == sum(a[key] for a in builds), key
    assert sched.dispatches == len(builds) and sched.latent_pages == sched.counts["latent_pages"]


def test_a_mixed_rounds_write_is_counted_from_the_rows_lengths(served, build_spans):
    """A 449-token chunk behind 300 cached tokens beside 63 decode rows, at
    the cells' limits (64 sequences, 512 tokens a round, pages of 64): the
    chunk's dispatch writes page-wise the 8 table entries its tokens touch
    (``ceil(749 / 64) - 300 // 64``) and no slot row by row, the ``[64, 1]``
    dispatch its 63 token slots row by row and no page; the round's counts
    are their sums, and each dispatch reports the form the forward's own
    rule picks for its shapes."""
    from deepspeed_tpu.inference.v2.model_implementations import paged_layer
    cfg, model, params, _ = served
    engine = InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 64, "max_ragged_batch_size": 512,
                          "max_context": 1024, "num_kv_blocks": 96},
        "kv_cache": {"block_size": 64, "cache_dtype": "fp32"}})
    rng = np.random.default_rng(53)
    toks = lambda n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
    engine.put([0], [toks(300)])
    engine.put(list(range(1, 64)), [toks(1) for _ in range(63)])
    before = len(build_spans())
    engine.put(list(range(64)), [toks(449)] + [toks(1) for _ in range(63)])
    builds = {(a["seq_bucket"], a["chunk_bucket"]): a for a in build_spans()[before:]}
    assert set(builds) == {(64, 1), (1, 512)}
    assert (builds[1, 512]["write_pages"], builds[1, 512]["write_rows"]) == (8, 0)
    assert (builds[64, 1]["write_pages"], builds[64, 1]["write_rows"]) == (0, 63)
    assert (engine.last_counts["write_pages"], engine.last_counts["write_rows"]) == (8, 63)
    heads, bs = engine._state.kv_cache.k_pool.shape[2:4]
    for (_, Q), a in builds.items():
        assert paged_layer.writes_pages(Q, heads, bs) == (a["write_pages"] > 0)
