"""Phi-4-mini-flash-reasoning on the normal serving path at a tiny size:
``InferenceEngineV2`` built by ``engine_factory.build_engine`` over the one
``DSStateManager`` with its three kinds of per-sequence state (full-layer
pages, window pages that are freed behind the window, a slot of recurrent
state), against the plain reference's full forward
(``benchmark/references/phi4flash.py``) in LOGITS, on seeded weights.

Float32 throughout (``Phi4FlashConfig.tiny``), window 8, block 4.
"""

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import phi4flash as reference
from deepspeed_tpu.inference.v2.engine_factory import (
    build_engine, resolve_cache_groups, resolve_forward_fn, resolve_verify_fn)
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
from deepspeed_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM

#: |logit - reference logit|. Both sides are float32 and differ in the order
#: of sums only (cache and chunks against one full pass): the program reads
#: 4e-7 at logits of ~0.7. With the recurrent state kept in bfloat16 the
#: reference itself moves by ~2e-3, which this limit has to refuse.
TOLERANCE = 2e-5

ENGINE = {"state_manager": {"max_ragged_sequence_count": 4, "max_ragged_batch_size": 16,
                            "max_context": 128, "num_kv_blocks": 64},
          "kv_cache": {"block_size": 4, "cache_dtype": "fp32"}}


@pytest.fixture(scope="module")
def served():
    cfg = Phi4FlashConfig.tiny()
    model = Phi4FlashForCausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    # a state that remembers tens of tokens, and lambdas away from zero
    for block in (params["front"]["mamba"], params["middle_mamba"]):
        block["mixer"]["dt_proj"]["bias"] = jnp.full_like(
            block["mixer"]["dt_proj"]["bias"], -2.5)
    ref_cfg = {k: getattr(cfg, k) for k in (
        "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "sliding_window", "layer_norm_eps")}
    ref_cfg["assumed"] = {"sizes": {"mamba_d_state": cfg.mamba_d_state}}
    rng = np.random.default_rng(0)
    ids = {uid: rng.integers(0, cfg.vocab_size, 60).astype(np.int32) for uid in range(4)}
    want = {uid: np.asarray(reference.full_logits(ref_cfg, params, jnp.asarray(t)))
            for uid, t in ids.items()}
    return cfg, model, params, ref_cfg, ids, want


def _engine(served, **over):
    _, model, params = served[:3]
    return build_engine(model, params, {**ENGINE, **over})


def _feed(engine, uid, tokens, chunks, start=0):
    """Put ``tokens`` of ``uid`` in ``chunks``; {position: logits after it}."""
    pos, got = start, {}
    for n in chunks:
        got[pos + n - 1] = engine.put([uid], [tokens[pos:pos + n]])[0]
        pos += n
    return got


def _worst(got, want):
    return max(float(np.max(np.abs(row - want[p]))) for p, row in got.items())


def test_the_factory_resolves_the_family(served):
    _, model = served[:2]
    assert resolve_forward_fn(model).__module__.endswith("model_implementations.phi4flash")
    assert resolve_verify_fn(model) is None
    names = [(type(g).__name__, g.name) for g in resolve_cache_groups(model)]
    assert names == [("PagedGroup", "kv"), ("PagedGroup", "window"), ("SlotGroup", "state")]
    engine = _engine(served)
    assert isinstance(engine, InferenceEngineV2) and not engine.verify_supported
    groups = engine.kv_stats()["groups"]
    assert set(groups) == {"kv", "window", "state"} and groups["state"]["total"] == 4


@pytest.mark.parametrize("chunks", [
    (16,),                              # a prompt in one chunk
    (16, 16, 9),                        # in several: state and pages carried over
    (16, 16, 5) + (1,) * 20,            # then decode through the cache
    (3, 1, 7, 2, 16, 1, 1, 8, 1),       # ragged lengths across the 8-token class
], ids=["one-chunk", "chunks", "chunks-then-decode", "ragged"])
def test_logits_agree_with_the_reference(served, chunks):
    ids, want = served[4][0], served[5][0]
    got = _feed(_engine(served), 0, ids, chunks)
    assert _worst(got, want) < TOLERANCE


def test_a_bfloat16_state_or_a_dropped_state_fails_the_tolerance(served):
    _, _, params, ref_cfg, ids, want = served
    low = np.asarray(reference.full_logits(ref_cfg, params, jnp.asarray(ids[0]),
                                           state_dtype=jnp.bfloat16))
    assert np.max(np.abs(low - want[0])) > 10 * TOLERANCE
    # the state dropped between two chunks: the second chunk run as a
    # sequence's first (seen 0 zeroes it) but over the first's pages
    engine = _engine(served)
    _feed(engine, 0, ids[0], (16,))
    seq = engine._state.get_sequence(0)
    engine._state.slot_pools = jax.tree.map(jnp.zeros_like, engine._state.slot_pools)
    got = _feed(engine, 0, ids[0], (16,), start=16)
    assert seq.seen_tokens == 32 and _worst(got, want[0]) > 10 * TOLERANCE


@pytest.mark.parametrize("rectangle", [False, True], ids=["by-class", "one-rectangle"])
def test_short_rows_of_one_to_eight_tokens_advance_each_row_by_its_own(
        served, monkeypatch, rectangle):
    """Rounds of four rows of 1-8 real tokens. As the engine dispatches them:
    the rows of one token together as [4, 1], every other row alone as
    [1, 16]. As ONE [4, 8] rectangle (the layout of a verify round, which the
    forward is written for): a padded position that advanced the state or
    shifted the convolution's columns would show in the row's next logits."""
    from deepspeed_tpu.inference.v2 import engine_v2
    if rectangle:
        monkeypatch.setattr(engine_v2, "dispatch_rows", lambda lengths, short:
                            [(list(range(len(lengths))), 4, 8)])
    ids, want = served[4], served[5]
    engine = _engine(served, state_manager=dict(ENGINE["state_manager"],
                                                max_ragged_batch_size=32))
    pos = {u: 0 for u in range(4)}
    worst = 0.0
    for lengths in [(8, 3, 1, 5), (1, 8, 2, 7), (4, 1, 8, 1), (2, 6, 1, 3), (1, 1, 1, 1)]:
        out = engine.put(list(range(4)), [ids[u][pos[u]:pos[u] + n]
                                          for u, n in enumerate(lengths)])
        ones = lengths.count(1)
        assert engine.last_batch_shapes == (
            [(4, 8)] if rectangle else [(4, 1)] + [(1, 16)] * (4 - ones))
        for u, n in enumerate(lengths):
            pos[u] += n
            worst = max(worst, float(np.max(np.abs(out[u] - want[u][pos[u] - 1]))))
    assert worst < TOLERANCE


@pytest.mark.parametrize("rows", [4, 3], ids=["full", "one-padded-row"])
def test_a_decode_round_of_one_token_rows_advances_every_kind_of_state(served, rows):
    """``rows`` sequences decode together as [4, 1], 30 rounds: each row's
    slot of state, its window ring (pages freed behind the window) and the
    full layer's shared pages advance by that one token; a padded row writes
    the trash slot and page."""
    ids, want = served[4], served[5]
    engine = _engine(served)
    uids = list(range(rows))
    pos = {}
    for u in uids:
        pos[u] = 7 + 3 * u                       # prompts of 7, 10, 13, 16 tokens
        engine.put([u], [ids[u][:pos[u]]])
    freed = engine._state.window_pages_freed
    worst = 0.0
    for _ in range(30):
        out = engine.put(uids, [ids[u][pos[u]:pos[u] + 1] for u in uids])
        assert engine.last_batch_shapes == [(4, 1)]
        for u in uids:
            pos[u] += 1
            worst = max(worst, float(np.max(np.abs(out[u] - want[u][pos[u] - 1]))))
    assert worst < TOLERANCE
    assert engine._state.window_pages_freed - freed >= 6 * rows
    for u in uids:
        seq = engine._state.get_sequence(u)
        assert seq.seen_tokens == pos[u]
        assert len(seq.kv_blocks) == -(-pos[u] // 4), "the full layer keeps every page"
        assert len(seq.group_blocks["window"]) <= 3, "the ring holds the window's pages"
    assert len({engine._state.get_sequence(u).slot for u in uids}) == rows


def test_the_ring_frees_pages_and_changes_no_logit(served):
    """Contexts of many windows: the window group holds a bounded number of
    pages and gives the logits that an engine keeping every page gives."""
    _, model, params, _, ids, want = served
    chunks = (16, 16, 7) + (1,) * 21
    ring = _engine(served)
    got_ring = _feed(ring, 0, ids[0], chunks)
    groups = resolve_cache_groups(model)
    kept_groups = (groups[0], dataclasses.replace(groups[1], window=None), groups[2])
    kept = InferenceEngineV2(model, params, ENGINE, cache_groups=kept_groups)
    got_kept = _feed(kept, 0, ids[0], chunks)
    for p in got_ring:
        np.testing.assert_allclose(got_ring[p], got_kept[p], atol=1e-6, rtol=0)
    assert _worst(got_ring, want[0]) < TOLERANCE
    seq_ring, seq_kept = ring._state.get_sequence(0), kept._state.get_sequence(0)
    assert len(seq_kept.group_blocks["window"]) == 15 == len(seq_kept.kv_blocks)
    # window 8 of block 4 at 60 tokens: position 60 sees 53..59, pages 13, 14
    assert len(seq_ring.group_blocks["window"]) == 2 and seq_ring.group_base["window"] == 13
    assert ring._state.window_pages_freed == 13
    assert ring._state.table_width["window"] == 8 // 4 + 16 // 4 + 1
    assert len(seq_ring.kv_blocks) == 15, "the full layer's pages grow with the context"
    ring.flush(0)
    stats = ring.kv_stats()["groups"]
    assert all(g["free"] == g["total"] for g in stats.values())


def test_a_slot_reused_after_flush_starts_from_zero_state(served):
    ids, want = served[4], served[5]
    engine = _engine(served)
    _feed(engine, 0, ids[0], (16, 16))
    slot = engine._state.get_sequence(0).slot
    engine.flush(0)
    got = _feed(engine, 1, ids[1], (16, 5, 1, 1))
    assert engine._state.get_sequence(1).slot == slot
    assert float(jnp.max(jnp.abs(engine._state.slot_pools["ssm"][:, slot]))) > 0
    assert _worst(got, want[1]) < TOLERANCE


def test_preempt_then_resume_reproduces_the_uninterrupted_logits(served):
    ids, want = served[4], served[5]
    engine = _engine(served)
    got = _feed(engine, 0, ids[0], (16, 16, 3))
    engine.preempt(0)
    seq = engine._state.get_sequence(0)
    assert seq.is_swapped and seq.slot is None and not seq.group_blocks["window"]
    assert not engine.can_schedule([0], [1]).success
    idle = engine.kv_stats()["groups"]
    assert all(g["free"] == g["total"] for g in idle.values())
    # someone else takes the slot and the pages meanwhile
    assert _worst(_feed(engine, 1, ids[1], (16, 9)), want[1]) < TOLERANCE
    assert engine.further_groups_fit_resume(0) and engine.blocks_to_resume(0) == 9
    engine.resume(0)
    assert seq.slot is not None and seq.slot != engine._state.get_sequence(1).slot
    got.update(_feed(engine, 0, ids[0], (1,) * 10, start=35))
    assert _worst(got, want[0]) < TOLERANCE
    engine.flush(1)
    engine.flush(0)
    assert all(g["free"] == g["total"] for g in engine.kv_stats()["groups"].values())
    assert engine.swap_stats == {"swap_outs": 1, "swap_ins": 1}


def test_admission_needs_a_slot_and_window_pages(served):
    engine = _engine(served, state_manager=dict(ENGINE["state_manager"],
                                                max_tracked_sequences=16))
    ids = served[4]
    for uid in range(4):
        engine.put([uid], [ids[uid][:5]])
    verdict = engine.can_schedule([9], [4])
    assert not verdict.success and verdict.reason == "no free state slot"
    engine.flush(2)
    assert engine.can_schedule([9], [4]).success
    window = engine._state.paged_groups["window"][1]
    held = window.reserve(window.free_blocks)
    verdict = engine.can_schedule([9], [4])
    assert not verdict.success and verdict.reason == "not enough window blocks"
    window.free(held)


def test_what_this_model_cannot_do_yet_is_refused(served):
    _, model, params = served[:3]
    with pytest.raises(ValueError, match="prefix_caching is not supported"):
        build_engine(model, params, {**ENGINE, "prefix_caching": True})
    with pytest.raises(ValueError, match="speculative.enabled"):
        build_engine(model, params, {**ENGINE, "speculative": {"enabled": True}})
    with pytest.raises(ValueError, match="kv_dtype int8"):
        build_engine(model, params, {**ENGINE, "state_manager": dict(
            ENGINE["state_manager"], kv_dtype="int8")})
    engine = _engine(served)
    engine.put([0], [served[4][0][:6]])
    with pytest.raises(ValueError, match="page export is not supported"):
        engine.export_pages(0)
    with pytest.raises(ValueError, match="page export is not supported"):
        engine.export_pages_many([0])
    with pytest.raises(ValueError, match="page import is not supported"):
        engine.import_pages_many({"n": 0, "k": None, "v": None, "seqs": []})
    with pytest.raises(ValueError, match="rollback is not supported"):
        engine.rollback(0, 1)


def _captured(trace_dir, run):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(trace_dir))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans += [(e.name[3:], e.start_ns, dict(e.stats))
                      for e in line.events if e.name.startswith("ds/")]
    return sorted(spans, key=lambda s: s[1])


def test_spans_carry_the_groups_and_their_sums_are_the_counters(served, tmp_path):
    cfg, _, _, _, ids, want = served
    sched = SplitFuseScheduler(_engine(served))
    sched.submit(50, ids[3][:5], max_new_tokens=2)
    sched.run_to_completion()                       # compile outside the capture
    before = (sched.window_pages_freed, sched.state_slots, sched.dispatches)

    def run():
        sched.submit(51, ids[0][:37], max_new_tokens=6)
        sched.submit(52, ids[1][:9], max_new_tokens=12)
        sched.run_to_completion()

    spans = _captured(tmp_path, run)
    builds = [a for n, _, a in spans if n == "serving/build"]
    assert len(builds) == sched.dispatches - before[2] > 0
    state = sched._engine._state
    # tokens, lengths, positions, the tokens' sources; the "kv" table; the
    # ring's table and base; the slot ids: every group's tables cross for a
    # dispatch, eight arrays in ONE transfer
    copies = [a for n, _, a in spans if n == "serving/dispatch/h2d"]
    assert [a["dispatch"] for a in copies] == [a["dispatch"] for a in builds]
    assert all(a["arrays"] == 1 for a in copies)
    width = sched._engine._max_blocks_per_seq + state.table_width["window"]
    for a, b in zip(copies, builds):
        rows, chunk = b["seq_bucket"], b["chunk_bucket"]
        assert a["bytes"] == 4 * (rows * chunk + 3 * rows + rows * width + 2 * rows)
    for a in builds:
        assert 1 <= a["state_slots"] <= 2 and a["global_pages"] > 0
        assert a["state_slots"] <= a["window_pages"] <= a["state_slots"] * 7
        # the ring's walk is its live pages, never its table's width
        assert a["seqs"] <= a["window_live_pages"] <= a["window_pages"]
        assert a["window_live_pages"] < a["seq_bucket"] * state.table_width["window"]
        assert a["seqs"] <= a["live_pages"] <= a["global_pages"]
    assert sum(a["window_pages_freed"] for a in builds) == \
        sched.window_pages_freed - before[0] > 0
    assert sum(a["state_slots"] for a in builds) == sched.state_slots - before[1]
    admits = {a["uid"]: a for n, _, a in spans if n == "serving/admit"}
    assert set(admits) == {51, 52} and admits[51]["slot"] != admits[52]["slot"]
    # greedy decode follows the reference's argmax through the scheduler too
    out = sched.results()[51]
    assert list(out[:1]) == [int(np.argmax(want[0][36]))]
    assert all(g["free"] == g["total"] for g in sched.kv_stats()["groups"].values())


def test_a_homogeneous_stack_is_the_one_group_case():
    from deepspeed_tpu.models.mistral import MistralForCausalLM, tiny_mistral_config
    model = MistralForCausalLM(tiny_mistral_config())
    group, = resolve_cache_groups(model)
    assert (group.name, group.layers, group.kv_heads, group.head_dim, group.window) == \
        ("kv", 2, 2, 16, None), "Mistral's window stays a mask: its pages are kept"
    params = model.init(jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    engine = build_engine(model, params, ENGINE)
    engine.put([1], [np.arange(5, dtype=np.int32)])
    assert set(engine._state.cache_view()) == {"kv"} and not engine._state.has_further_groups
    assert "groups" not in engine.kv_stats() and engine.state_slot(1) is None
    assert engine.further_groups_fit_resume(1)
