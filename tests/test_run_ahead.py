"""Run-ahead of depth one in ``SplitFuseScheduler.step()``: round n + 1 is
composed and dispatched before round n is fetched, wherever no request
submitted meanwhile could have joined it; a decode row's token is then read
on the device from the ids round n's sampler left there.

What is pinned: the rounds composed and the ids emitted are those of the
same requests stepped through ``step_begin`` / ``step_finish`` (the order
without run-ahead), and after every ``step()`` a caller sees the same
``prefill_pos`` and ``generated``; the rule (when a round is closed to
newcomers); what happens to a row that ended while it rode a round; which
schedulers never run ahead; the counters and the spans' ``round``.
"""

import jax
import numpy as np
import pytest

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler

SEQS, BUDGET = 4, 16
FAMILIES = ("llama", "phi4flash", "mellum2")    # dense; slots + a ring; experts + a ring


@pytest.fixture(autouse=True)
def _telemetry_on():
    """Enabled, so that every span lands in ``trace_events`` with the
    attributes it ended with."""
    telemetry.reset()
    telemetry.configure(enabled=True, jsonl_path="", chrome_trace_path="")
    yield
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")


_BUILT = {}


def _build(family, **engine):
    """(engine factory, vocabulary) of a tiny model of ``family``; the model
    and its weights are made once a family."""
    if family not in _BUILT:
        if family == "llama":
            from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
            cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
            model = LlamaForCausalLM(cfg)
            params = model.init(jax.random.PRNGKey(0),
                                {"input_ids": np.zeros((1, 8), np.int32)})["params"]
        else:
            if family == "phi4flash":
                from deepspeed_tpu.models.phi4flash import (
                    Phi4FlashConfig as Config, Phi4FlashForCausalLM as Model)
            else:
                from deepspeed_tpu.models.mellum2 import (
                    Mellum2Config as Config, Mellum2ForCausalLM as Model)
            cfg = Config.tiny()
            model = Model(cfg)
            params = model.init_params(jax.random.PRNGKey(0))
        _BUILT[family] = (model, params, cfg.vocab_size)
    model, params, vocab = _BUILT[family]
    config = {"state_manager": {"max_ragged_sequence_count": SEQS,
                                "max_ragged_batch_size": BUDGET,
                                "max_context": 128,
                                "num_kv_blocks": engine.pop("num_kv_blocks", 96)},
              "kv_cache": {"block_size": 4, "cache_dtype": "fp32"}, **engine}
    if family == "llama":
        return lambda: InferenceEngineV2(model, params, config=config), vocab
    return lambda: build_engine(model, params, config), vocab


def _spans(name):
    """The attributes of the ``name`` spans ended so far, in order."""
    return [e["args"] for e in telemetry.get_telemetry().trace_events
            if e.get("cat") == "span" and e["name"] == name]


def _composed():
    """(round, seqs, prefill_tokens, decode_rows) of every round composed
    AND dispatched: a look ahead that dispatched nothing has no sequences."""
    return [(c["round"], c["seqs"], c["prefill_tokens"], c["decode_rows"])
            for c in _spans("serving/compose") if c["seqs"]]


def _parent_step(sched):
    """One round in the order without run-ahead."""
    pending = sched.step_begin()
    return sched.step_finish(pending) if pending is not None else []


def _visible(sched):
    return {u: (r.prefill_pos, tuple(r.generated), r.done)
            for u, r in sched._requests.items()}


def _serve(sched, requests, ahead, arrivals=None, max_steps=400):
    """Serve ``requests`` = {uid: (prompt, submit kwargs)}; ``arrivals`` maps
    a step count to the uids submitted once that many steps have returned
    (default: all up front). Returns what a caller saw after every step:
    [(finished uids, {uid: (prefill_pos, generated, done)})]."""
    arrivals = arrivals or {0: list(requests)}
    seen, steps = [], 0
    while True:
        for uid in arrivals.get(steps, ()):
            prompt, kwargs = requests[uid]
            sched.submit(uid, prompt, **kwargs)
        if not sched.has_work and steps >= max(arrivals):
            return seen
        done = sched.step() if ahead else _parent_step(sched)
        seen.append((sorted(done), _visible(sched)))
        steps += 1
        assert steps < max_steps


def _requests(vocab, lengths, seed=5, sampling=True):
    """Prompts of ``lengths`` = [(prompt tokens, new tokens)], every other one
    sampling from a seed of its own (all greedy without ``sampling``)."""
    rng = np.random.default_rng(seed)
    out = {}
    for uid, (n, new) in enumerate(lengths):
        kwargs = {"max_new_tokens": new}
        if sampling and uid % 2:
            kwargs.update(temperature=0.9, top_k=12, top_p=0.95, seed=100 + uid)
        out[uid] = (rng.integers(0, vocab, n).astype(np.int32), kwargs)
    return out


def _empty(engine):
    """The pools' census, without the counters that only ever rise."""
    stats = engine.kv_stats()
    keep = ("free_blocks", "occupied_blocks", "tracked_sequences", "swapped_sequences")
    groups = {name: {k: v for k, v in g.items() if k in ("total", "free")}
              for name, g in stats.get("groups", {}).items()}
    return {k: stats[k] for k in keep}, groups


# -- (a) the same rounds, the same ids, the same view after every step ----------

# six requests over four slots and a 16-token budget: prompts on both sides
# of the budget, ends by count at different rounds, two requests waiting
MIXED = [(5, 9), (37, 6), (9, 12), (21, 5), (12, 7), (3, 8)]


@pytest.mark.parametrize("family", FAMILIES)
def test_the_rounds_and_the_ids_are_those_of_the_order_without_run_ahead(family):
    make_engine, vocab = _build(family)
    requests = _requests(vocab, MIXED)
    runs = {}
    for ahead in (False, True):
        telemetry.get_telemetry().trace_events.clear()
        engine = make_engine()
        before = _empty(engine)
        sched = SplitFuseScheduler(engine)
        seen = _serve(sched, requests, ahead)
        runs[ahead] = (seen, _composed(), sched)
        assert _empty(engine) == before
        assert engine.host_sync_count == sched.rounds == len(seen)
    (seen_p, rounds_p, parent), (seen_a, rounds_a, sched) = runs[False], runs[True]
    assert parent.rounds_ahead == parent.ahead_rows == 0
    assert sched.rounds_ahead > 3 and sched.ahead_rows > sched.rounds_ahead
    assert sched.ahead_rows_dropped == 0
    # every round holds the same rows doing the same work
    assert rounds_a == rounds_p
    # and after every step() a caller sees what it saw without run-ahead:
    # the round returned, nothing of the round in flight
    assert seen_a == seen_p
    for uid, (_, kwargs) in requests.items():
        assert len(sched.results()[uid]) == kwargs["max_new_tokens"]


# -- (b) the rule ----------------------------------------------------------------


def _llama_sched(**engine):
    make_engine, vocab = _build("llama", **engine)
    return SplitFuseScheduler(make_engine()), vocab


def _ahead_by_round():
    return {c["round"]: (c["ahead"], c["ahead_rows"])
            for c in _spans("serving/compose") if c["seqs"]}


def test_a_free_slot_and_an_empty_queue_never_run_ahead():
    """Two rows decode in four slots and nothing waits: a request submitted
    now would join the next round, so every round is composed after the one
    before it was retired."""
    sched, vocab = _llama_sched()
    _serve(sched, _requests(vocab, [(5, 9), (9, 12)]), ahead=True)
    assert sched.rounds > 10 and sched.rounds_ahead == 0
    assert all(c["ahead"] == 0 and c["ahead_rows"] == 0
               for c in _spans("serving/compose"))


def test_every_slot_taken_runs_ahead():
    """Four rows in four slots: no newcomer has a slot, so every decode
    round but the first is dispatched under the one before it, each row's
    token read on the device; the rounds in which a row ends by count are
    known, and the round behind them is composed without that row."""
    sched, vocab = _llama_sched()
    requests = _requests(vocab, [(3, 9), (4, 12), (5, 7), (4, 10)])
    _serve(sched, requests, ahead=True)
    rounds = _composed()
    ahead = _ahead_by_round()
    # round 0 prefills all four (16 tokens); rounds 1-6 decode four rows,
    # each dispatched ahead, the first on the ids the prompts' last chunks
    # sampled
    assert rounds[0][1:] == (4, 16, 0)
    assert [ahead[r[0]] for r in rounds[1:7]] == [(1, 4)] * 6
    # uid 2 ends by count in round 6 (its 7th token): the round dispatched
    # behind it holds the other three; it frees a slot with nothing waiting,
    # so that round is composed as ever
    assert rounds[7][1:] == (3, 0, 3) and ahead[rounds[7][0]] == (0, 0)
    assert sched.ahead_rows_dropped == 0


def test_a_budget_spent_by_a_prompt_mid_prefill_runs_ahead():
    """One request in four slots, its prompt 40 tokens under a budget of
    16: its second chunk spends the whole budget, so nothing submitted
    meanwhile could have joined that round; its third (8 tokens) does not."""
    sched, vocab = _llama_sched()
    _serve(sched, _requests(vocab, [(40, 3)]), ahead=True)
    rounds, ahead = _composed(), _ahead_by_round()
    assert [r[2] for r in rounds[:3]] == [16, 16, 8]
    assert [ahead[r[0]][0] for r in rounds] == [0, 1, 0, 0, 0]
    assert sched.ahead_rows == 0          # a chunk's tokens are the host's


@pytest.mark.parametrize("scene", ["slots_full", "free_slot", "budget_spent"])
def test_a_request_submitted_between_two_steps_is_admitted_in_the_parents_round(scene):
    """The guarantee: no request is admitted a round later than without
    run-ahead. A newcomer arrives after the fifth ``step()``: while four
    rows hold every slot (it waits for the first to end, in both orders),
    while two rows leave slots and budget free (it joins the very next
    round, so that round was not dispatched ahead), while a long prompt
    spends every round's budget (it waits for the budget, in both)."""
    lengths = {"slots_full": [(3, 9), (4, 12), (5, 7), (4, 10)],
               "free_slot": [(3, 9), (4, 12)],
               "budget_spent": [(3, 20), (90, 4)]}[scene] + [(6, 4)]
    admitted, results = {}, {}
    for ahead in (False, True):
        telemetry.get_telemetry().trace_events.clear()
        sched, vocab = _llama_sched()
        requests = _requests(vocab, lengths)
        late = len(lengths) - 1
        seen = _serve(sched, requests, ahead,
                      arrivals={0: list(range(late)), 5: [late]})
        admitted[ahead] = {a["uid"]: a["round"] for a in _spans("serving/admit")}
        results[ahead] = (seen, _composed())
        if ahead and scene != "free_slot":
            assert sched.rounds_ahead > 0
    assert admitted[True] == admitted[False]
    assert results[True] == results[False]
    if scene == "free_slot":
        assert admitted[True][2] == 5         # the round after the five returned


# -- (c) a row that ended while it rode a round -------------------------------


def _eos_scene(family):
    """Four rows in four slots (every decode round runs ahead) and, from a
    run without eos, uid 1's fifth id where it is the first of its kind."""
    make_engine, vocab = _build(family)
    for seed in range(5, 40):
        requests = _requests(vocab, [(3, 14), (4, 14), (5, 14), (4, 14)], seed,
                             sampling=False)   # all greedy: eos is a known id
        sched = SplitFuseScheduler(make_engine())
        _serve(sched, requests, ahead=False)
        plain = {u: ids.tolist() for u, ids in sched.results().items()}
        if plain[1][4] not in plain[1][:4]:
            return make_engine, requests, plain
    raise AssertionError("no seed gives uid 1 a fresh id at its fifth token")


@pytest.mark.parametrize("family", FAMILIES)
def test_an_eos_sampled_under_a_round_in_flight_ends_the_row_there(family):
    """uid 1 samples its eos in round n while round n + 1, which carries it,
    is already dispatched: its id of n + 1 is dropped, ``generated`` ends
    with the eos, the other rows emit what they emit without it, and its
    pages, ring and slot are back once the queue has drained."""
    make_engine, requests, plain = _eos_scene(family)
    requests[1] = (requests[1][0], {"max_new_tokens": 14,
                                    "eos_token_id": plain[1][4]})
    engine = make_engine()
    before = _empty(engine)
    sched = SplitFuseScheduler(engine)
    seen = _serve(sched, requests, ahead=True)
    got = {u: ids.tolist() for u, ids in sched.results().items()}
    assert got[1] == plain[1][:5]
    assert all(got[u] == plain[u] for u in (0, 2, 3))
    assert sched.ahead_rows_dropped == 1
    assert _empty(engine) == before
    # at no step did a caller see a token past the end
    assert all(len(view[1][1]) <= 5 for _, view in seen)
    assert engine.host_sync_count == sched.rounds == len(seen)
    # the same requests in the order without run-ahead emit the same ids
    parent = SplitFuseScheduler(make_engine())
    _serve(parent, requests, ahead=False)
    assert {u: ids.tolist() for u, ids in parent.results().items()} == got
    # the row rode one round for nothing, and it was no round more: the
    # other three rows were in it
    assert parent.ahead_rows_dropped == 0 and parent.rounds == sched.rounds


@pytest.mark.parametrize("family", FAMILIES)
def test_a_cancel_of_a_row_in_flight_drops_its_id(family):
    make_engine, vocab = _build(family)
    requests = _requests(vocab, [(3, 14), (4, 14), (5, 14), (4, 14)])
    engine = make_engine()
    before = _empty(engine)
    sched = SplitFuseScheduler(engine)
    for uid, (prompt, kwargs) in requests.items():
        sched.submit(uid, prompt, **kwargs)
    for _ in range(4):
        sched.step()
    assert sched._flying is not None and 2 in sched._flying.uids
    held = len(sched._requests[2].generated)
    assert sched.cancel(2)
    sched.run_to_completion()
    assert len(sched.results()[2]) == held
    assert sched.ahead_rows_dropped == 1
    assert _empty(engine) == before
    assert all(len(sched.results()[u]) == 14 for u in (0, 1, 3))


def test_the_queue_drains_a_round_left_in_flight():
    """The only row ends by eos under a round in flight (a budget of one
    token is spent by its one row, so every round runs ahead): ``has_work``
    stays true until that round has been fetched and its id dropped."""
    make_engine, requests, plain = _eos_scene("llama")
    engine = make_engine()
    sched = SplitFuseScheduler(engine, token_budget=1)
    sched.submit(1, requests[1][0], max_new_tokens=14, eos_token_id=plain[1][4])
    steps = 0
    while sched.has_work:
        sched.step()
        steps += 1
    assert sched.results()[1].tolist() == plain[1][:5]
    assert sched._flying is None and sched.ahead_rows_dropped == 1
    assert engine.host_sync_count == sched.rounds == steps
    assert sched.rounds_ahead == sched.rounds - 1


# -- (d) schedulers and rounds that never run ahead ----------------------------


@pytest.mark.parametrize("why", ["speculation", "host_sampling", "on_finish",
                                 "prefix_caching"])
def test_schedulers_that_never_run_ahead(why):
    """Each decided by what the scheduler can see: the accept walk decides a
    speculating row's next chunk; host-side sampling has no id on the
    device; ``on_finish`` hands a sequence off at retire; a cached block's
    digest needs the token ids. Four rows hold four slots throughout."""
    engine = {"speculation": {"speculative": {"enabled": True, "max_draft_tokens": 3}},
              "prefix_caching": {"prefix_caching": True}}.get(why, {})
    make_engine, vocab = _build("llama", **engine)
    sched = SplitFuseScheduler(make_engine(), device_sampling=why != "host_sampling")
    if why == "on_finish":
        sched.on_finish = lambda sched_, request: False
    requests = _requests(vocab, [(3, 9), (4, 12), (5, 7), (4, 10)], sampling=False)
    _serve(sched, requests, ahead=True)
    assert sched.rounds > 5 and sched.rounds_ahead == sched.ahead_rows == 0
    assert sched._flying is None
    assert all(c["ahead"] == 0 for c in _spans("serving/compose"))
    assert all(len(sched.results()[u]) == kw["max_new_tokens"]
               for u, (_, kw) in requests.items())


def test_a_round_that_shrinks_or_preempts_is_composed_as_ever():
    """A pool too small for both requests (tests/test_splitfuse_scheduler's
    deadlock): rounds shrink, a sequence goes to the host and comes back.
    None of those rounds, and no round while a sequence waits on the host,
    is dispatched ahead; the ids are those of the order without run-ahead."""
    results = {}
    for ahead in (False, True):
        telemetry.get_telemetry().trace_events.clear()
        make_engine, vocab = _build("llama", num_kv_blocks=20)
        rng = np.random.default_rng(7)
        requests = {u: (rng.integers(0, vocab, 44).astype(np.int32),
                        {"max_new_tokens": 6}) for u in (0, 1)}
        engine = make_engine()
        sched = SplitFuseScheduler(engine)
        seen = _serve(sched, requests, ahead)
        composes = _spans("serving/compose")
        assert engine.swap_stats["swap_outs"] >= 1 <= engine.swap_stats["swap_ins"]
        assert sum(c["shrunk"] for c in composes) >= 1
        assert all(not c["ahead"] for c in composes if c["shrunk"] or c["preempted"])
        results[ahead] = (seen, _composed())
    assert results[True] == results[False]


# -- (e) counters, spans, the one fetch a round ---------------------------------


@pytest.mark.parametrize("family", FAMILIES)
def test_counters_equal_the_spans_sums_and_every_span_names_its_round(family):
    make_engine, vocab = _build(family)
    engine = make_engine()
    sched = SplitFuseScheduler(engine)
    requests = _requests(vocab, MIXED)
    for uid, (prompt, kwargs) in requests.items():
        sched.submit(uid, prompt, **kwargs)
    syncs = []
    while sched.has_work:
        before = engine.host_sync_count
        sched.step()
        syncs.append(engine.host_sync_count - before)
    assert set(syncs) == {1}                     # ONE fetch a round, ahead or not
    composes = _spans("serving/compose")
    assert sched.rounds_ahead == sum(c["ahead"] for c in composes) > 0
    assert sched.ahead_rows == sum(c["ahead_rows"] for c in composes) > 0
    assert all(c["ahead_rows"] <= c["decode_rows"] for c in composes)
    assert all(c["ahead"] for c in composes if c["ahead_rows"])
    # ``serving/round``, ``fetch`` and ``retire`` name the round returned:
    # one a step(), in order; compose .. post_forward the round dispatched
    rounds = [r["round"] for r in _spans("serving/round")]
    assert rounds == list(range(len(rounds))) == \
        [f["round"] for f in _spans("serving/fetch")] == \
        [r["round"] for r in _spans("serving/retire")]
    dispatched = [c["round"] for c in composes if c["seqs"]]
    assert dispatched == rounds
    for name in ("serving/build", "serving/dispatch", "serving/post_forward"):
        assert sorted({s["round"] for s in _spans(name)}) == rounds
    builds = _spans("serving/build")
    for c in composes:
        if c["seqs"]:
            assert c["seqs"] == sum(b["seqs"] for b in builds if b["round"] == c["round"])
