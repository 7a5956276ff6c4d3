"""The program's own spans, as a ``jax.profiler`` capture holds them.

``telemetry.span`` opens a ``TraceAnnotation`` named ``ds/<name>`` whether
telemetry is enabled or not, so a capture on the CPU holds the serving
round's and the train step's spans with their attributes: what
``benchmark/program_spans.py`` reads on the chip.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from deepspeed_tpu.models.mistral import MistralForCausalLM, tiny_mistral_config
from deepspeed_tpu.parallel import groups
from deepspeed_tpu.parallel.topology import MeshTopology

PHASES = ("compose", "build", "dispatch", "post_forward", "fetch", "retire")
CHILDREN = ("h2d", "forward", "sample")


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")
    yield
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")


def _captured(trace_dir, run):
    """Run ``run()`` inside a profiler session; the ``ds/`` events of the
    capture as [(name without the prefix, start_ns, end_ns, attrs)]."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(trace_dir))
    try:
        out = run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans += [(e.name[3:], e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats))
                      for e in line.events if e.name.startswith("ds/")]
    return sorted(spans, key=lambda s: s[1]), out


def _scheduler(max_context=64, speculative=False):
    cfg = tiny_mistral_config()
    model = MistralForCausalLM(cfg)
    ids = np.zeros((1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    engine = InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": 4,
                          "max_ragged_batch_size": 16,
                          "max_context": max_context, "num_kv_blocks": 48},
        "kv_cache": {"block_size": 8, "cache_dtype": "fp32"},
        "speculative": {"enabled": speculative, "max_draft_tokens": 4}})
    return cfg, SplitFuseScheduler(engine)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Three requests of different lengths, one arriving late, served to the
    end inside one capture: (spans, scheduler)."""
    cfg, sched = _scheduler()
    rng = np.random.default_rng(3)
    prompt = lambda n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
    sched.submit(7, prompt(5), max_new_tokens=2)
    sched.run_to_completion()         # compile outside the capture

    def run():
        sched.submit(11, prompt(37), max_new_tokens=4)
        sched.submit(12, prompt(9), max_new_tokens=6)
        for _ in range(3):
            sched.step()
        sched.submit(13, prompt(12), max_new_tokens=3)
        sched.run_to_completion()
        return sched
    before = (sched.rounds, sched.real_tokens, sched.padded_slots,
              sched.prefill_tokens_executed, sched.dispatches,
              sched.live_pages)
    spans, _ = _captured(tmp_path_factory.mktemp("serve"), run)
    return spans, sched, before


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_request_yields_admit_first_token_finish_with_one_uid(served):
    spans, sched, _ = served
    for uid, n_new in ((11, 4), (12, 6), (13, 3)):
        events = [s for s in spans if s[3].get("uid") == uid]
        assert [s[0] for s in events] == [
            "serving/admit", "serving/first_token", "serving/finish"]
        admit, first, finish = events
        assert admit[3]["prompt_tokens"] == len(sched._requests[uid].prompt)
        assert admit[3]["waited_us"] >= 0
        assert admit[3]["round"] <= first[3]["round"] <= finish[3]["round"]
        assert finish[3]["new_tokens"] == n_new
        assert finish[3]["reason"] == "done"
    # uid 13 arrived three rounds after the others were admitted
    admitted = {s[3]["uid"]: s[3]["round"] for s in _named(spans, "serving/admit")}
    assert admitted[13] >= admitted[11] + 3


def test_phase_spans_lie_inside_their_round_and_carry_it(served):
    """Every span names the round it works for: compose, build, dispatch and
    post_forward the round they DISPATCH, which is the ``step()``'s own or,
    run ahead, the next one; fetch and retire the round they fetch, which
    is the round ``serving/round`` names: the one whose result the
    ``step()`` returns."""
    spans, sched, _ = served
    rounds = _named(spans, "serving/round")
    assert len(rounds) >= 6
    numbers = [r[3]["round"] for r in rounds]
    assert numbers == list(range(numbers[0], numbers[0] + len(rounds))), \
        "one round number per step(), whatever the number of dispatches"
    assert sched.rounds_ahead > 0, "no round of this run was dispatched ahead"
    ahead = {s[3]["round"] for s in _named(spans, "serving/compose")
             if s[3]["ahead"]}
    for _, a, b, attrs in rounds:
        inside = [s for s in spans if a <= s[1] and s[2] <= b
                  and s[0] != "serving/round"]
        n = attrs["round"]
        assert {s[0] for s in inside} >= {"serving/fetch", "serving/retire"}
        for s in inside:
            if s[0] in ("serving/fetch", "serving/retire"):
                assert s[3]["round"] == n, s
            elif s[3]["round"] != n:
                # dispatched under round n: a round ahead, or a look ahead
                # that composed nothing
                assert s[3]["round"] == n + 1, s
                assert n + 1 in ahead or \
                    (s[0] == "serving/compose" and s[3]["seqs"] == 0), s
            else:
                assert n not in ahead, s
    # and no phase span lies outside every round
    for s in spans:
        if s[0].startswith("serving/") and s[0] != "serving/round":
            assert any(a <= s[1] and s[2] <= b for _, a, b, _ in rounds), s
    # the phases of a round do not overlap, in the order the round runs
    # them: compose, a build, a dispatch and the rows' bookkeeping per
    # dispatch, ONE fetch, retire (a look ahead that dispatched nothing
    # left a compose of no sequences before it)
    for n in numbers:
        order = [s for s in spans if s[0] != "serving/round"
                 and s[0][8:] in PHASES and s[3]["round"] == n
                 and not (s[0] == "serving/compose" and s[3]["seqs"] == 0)]
        assert all(x[2] <= y[1] for x, y in zip(order, order[1:]))
        names = [s[0][8:] for s in order]
        k = names.count("build")
        assert names == ["compose"] + \
            ["build", "dispatch", "post_forward"] * k + ["fetch", "retire"], names
    assert any(len([s for s in _named(spans, "serving/build")
                    if s[3]["round"] == n]) > 1 for n in numbers), \
        "no round of this run took more than one dispatch"


def test_build_counts_real_tokens_within_padded_slots(served):
    spans, _, _ = served
    builds = _named(spans, "serving/build")
    assert builds
    for _, _, _, a in builds:
        assert a["padded_slots"] == a["seq_bucket"] * a["chunk_bucket"]
        assert 0 < a["real_tokens"] <= a["padded_slots"]
        assert a["seqs"] <= a["seq_bucket"]
        assert a["context_tokens"] >= 0
    # a round's dispatches together carry what the round composed: the rows
    # of one token in one [D, 1] batch, every other row alone in a [1, C] one
    for _, _, _, c in _named(spans, "serving/compose"):
        if not c["seqs"]:
            continue                  # a look ahead that dispatched nothing
        mine = [a for _, _, _, a in builds if a["round"] == c["round"]]
        total = lambda key: sum(a[key] for a in mine)
        assert c["seqs"] == total("seqs")
        assert c["prefill_tokens"] + c["decode_rows"] == total("real_tokens")
        alone = [a for a in mine if a["seq_bucket"] == 1]
        assert c["long_rows"] == len(alone) == len(mine) - (c["seqs"] > len(alone))
        assert all(a["seqs"] == 1 and a["real_tokens"] > 1 and
                   a["chunk_bucket"] >= 16 for a in alone)
        assert all(a["chunk_bucket"] == 1 and a["real_tokens"] == a["seqs"]
                   for a in mine if a["seq_bucket"] > 1)
        assert c["shrunk"] == 0 and c["preempted"] == 0


def test_sums_over_spans_equal_the_schedulers_counters(served):
    spans, sched, before = served
    builds = _named(spans, "serving/build")
    total = lambda key: sum(s[3][key] for s in builds)
    assert sched.rounds - before[0] == len({s[3]["round"] for s in builds})
    assert sched.dispatches - before[4] == len(builds) > sched.rounds - before[0]
    assert sched.real_tokens - before[1] == total("real_tokens")
    assert sched.padded_slots - before[2] == total("padded_slots")
    prefill = sum(s[3]["prefill_tokens"] for s in _named(spans, "serving/compose"))
    assert sched.prefill_tokens_executed - before[3] == prefill == 37 + 9 + 12
    retired = _named(spans, "serving/retire")
    assert sum(s[3]["new_tokens"] for s in retired) == 4 + 6 + 3
    assert sum(s[3]["finished"] for s in retired) == 3
    assert 0 < sched.real_tokens <= sched.padded_slots


def test_live_pages_and_table_slots_sum_to_the_schedulers_counters(served):
    """What the paged kernel walks: a dispatch's rows reach
    ``ceil((seen + new) / block)`` pages each, none past its table (whose
    slots nothing counts any more: the kernel's grid stopped stepping over
    them in PR 31)."""
    spans, sched, before = served
    builds = _named(spans, "serving/build")
    total = lambda key: sum(s[3][key] for s in builds)
    assert sched.live_pages - before[5] == total("live_pages")
    width = sched._engine._max_blocks_per_seq
    for _, _, _, a in builds:
        assert "table_slots" not in a
        # every row reaches a page; none reaches past its table
        assert a["seqs"] <= a["live_pages"] <= a["seqs"] * width
        assert a["live_pages"] * sched._engine._state.kv_block_size >= \
            a["real_tokens"] + a["context_tokens"]
    assert sched.live_pages > 0


def test_dispatch_index_is_unique_and_rises_by_one(served):
    """``dispatch`` is the engine's count of forwards: one value a
    ``serving/dispatch``, the same on the build before it, on the spans
    inside it and on the rows' bookkeeping behind it."""
    spans, sched, _ = served
    dispatches = _named(spans, "serving/dispatch")
    numbers = [s[3]["dispatch"] for s in dispatches]
    assert numbers == list(range(numbers[0], numbers[0] + len(dispatches)))
    assert numbers[-1] == sched._engine.dispatch - 1
    for name in ("serving/build", "serving/post_forward", "serving/dispatch/h2d",
                 "serving/dispatch/forward", "serving/dispatch/sample"):
        assert [s[3]["dispatch"] for s in _named(spans, name)] == numbers, name
    builds = _named(spans, "serving/build")
    for build, disp, post in zip(builds, dispatches,
                                 _named(spans, "serving/post_forward")):
        assert build[3]["round"] == disp[3]["round"] == post[3]["round"]
        assert build[2] <= disp[1] and disp[2] <= post[1]
    # a round's fetch follows the bookkeeping of its last dispatch
    for fetch in _named(spans, "serving/fetch"):
        last = [s for s in _named(spans, "serving/post_forward")
                if s[3]["round"] == fetch[3]["round"]][-1]
        assert last[2] <= fetch[1]


def test_dispatch_children_lie_inside_it_in_order_and_carry_its_ids(served):
    spans, _, _ = served
    dispatches = _named(spans, "serving/dispatch")
    assert dispatches
    for _, a, b, attrs in dispatches:
        inside = [s for s in spans if s[0].startswith("serving/dispatch/")
                  and s[3]["dispatch"] == attrs["dispatch"]]
        assert [s[0][17:] for s in inside] == list(CHILDREN)
        assert all(a <= s[1] and s[2] <= b for s in inside)
        assert all(x[2] <= y[1] for x, y in zip(inside, inside[1:]))
        assert all(s[3]["round"] == attrs["round"] for s in inside)
    # and no child lies outside every dispatch
    for s in spans:
        if s[0].startswith("serving/dispatch/"):
            assert any(a <= s[1] and s[2] <= b for _, a, b, _ in dispatches), s


def test_h2d_counts_the_arrays_and_bytes_copied_for_a_dispatch(served):
    """ONE transfer a dispatch (``ragged_wrapper.pack``), holding tokens
    ``[S, C]``, lengths, positions and the tokens' sources ``[S]`` and the
    one group's block table ``[S, width]``, all int32."""
    spans, sched, _ = served
    width = sched._engine._max_blocks_per_seq
    shapes = {s[3]["dispatch"]: (s[3]["seq_bucket"], s[3]["chunk_bucket"])
              for s in _named(spans, "serving/build")}
    copies = _named(spans, "serving/dispatch/h2d")
    assert copies
    for _, _, _, a in copies:
        rows, chunk = shapes[a["dispatch"]]
        assert a["arrays"] == 1
        assert a["bytes"] == 4 * (rows * chunk + 3 * rows + rows * width)


@pytest.mark.parametrize("path, programs", [("device_sampler", 2), ("put", 1)])
def test_programs_counts_the_executables_a_dispatch_enqueued(path, programs, tmp_path):
    """The forward, and the sampler where one is dispatched behind it: what
    the device's ``XLA Modules`` line shows for the dispatch."""
    cfg, sched = _scheduler()
    rng = np.random.default_rng(11)
    prompt = lambda n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)

    def run():
        if path == "put":       # logits to the host: no sampler on the device
            sched._engine.put([1, 2], [prompt(9), prompt(1)])
        else:
            sched.submit(1, prompt(9), max_new_tokens=3)
            sched.run_to_completion()
    spans, _ = _captured(tmp_path, run)
    dispatches = _named(spans, "serving/dispatch")
    assert dispatches and all(s[3]["programs"] == programs for s in dispatches)
    sampled = _named(spans, "serving/dispatch/sample")
    assert len(sampled) == (len(dispatches) if programs == 2 else 0)
    assert ("sampled_rows" in dispatches[0][3]) == (programs == 2)
    assert len(_named(spans, "serving/dispatch/forward")) == len(dispatches)


@pytest.mark.parametrize("speculative", [False, True])
def test_first_seen_is_one_exactly_once_a_shape(speculative, tmp_path):
    """The dispatch that is first of its (sequence bucket, chunk bucket,
    verify_k) on this engine names itself: it traced, compiled or loaded the
    program. A verify forward is another program than the plain forward of
    the same buckets."""
    cfg, sched = _scheduler(speculative=speculative)
    rng = np.random.default_rng(13)
    prompt = lambda n: np.tile(rng.integers(0, cfg.vocab_size, 3), n)[:n].astype(np.int32)

    def run():
        if speculative:     # the plain forward at the buckets [1, 16]
            sched._engine.put([9], [prompt(16)])
            sched._engine.flush(9)
        sched.submit(1, prompt(21), max_new_tokens=4)
        sched.submit(2, prompt(9), max_new_tokens=6)
        sched.run_to_completion()
        known = len(sched._engine._shapes_seen)
        sched.submit(3, prompt(12), max_new_tokens=3)   # no shape it brings is new
        sched.run_to_completion()
        return known
    spans, known = _captured(tmp_path, run)
    shapes = {s[3]["dispatch"]: (s[3]["seq_bucket"], s[3]["chunk_bucket"])
              for s in _named(spans, "serving/build")}
    dispatches = _named(spans, "serving/dispatch")
    seen, verify = set(), 0
    for _, _, _, a in dispatches:
        if shapes[a["dispatch"]] in seen:
            verify += a["first_seen"]     # these buckets, with another verify_k
        else:
            assert a["first_seen"] == 1, a
        seen.add(shapes[a["dispatch"]])
    assert verify == speculative      # the verify forward at [1, 16]
    assert sum(a["first_seen"] for _, _, _, a in dispatches) == known \
        == len(sched._engine._shapes_seen) == len(seen) + verify >= 2
    assert known < len(dispatches)


def test_dispatches_sorted_counts_the_dispatch_spans_that_held_a_sampled_row(tmp_path):
    """``sampled_rows`` on a ``serving/dispatch`` span is the dispatch's rows
    of temperature > 0: 0 where the device sampler took the argmax. The
    scheduler's ``dispatches_sorted`` is the count of spans above 0: none
    over an all-greedy run, and in a mixed one the dispatches that held the
    sampled request: one in every round from its admission to its finish."""
    cfg, sched = _scheduler()
    rng = np.random.default_rng(7)
    prompt = lambda n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)

    def greedy():
        sched.submit(1, prompt(21), max_new_tokens=4)
        sched.submit(2, prompt(9), max_new_tokens=5, top_k=3, top_p=0.5)
        sched.run_to_completion()
    spans, _ = _captured(tmp_path / "greedy", greedy)
    dispatches = _named(spans, "serving/dispatch")
    assert len(dispatches) == sched.dispatches > 0
    assert all(s[3]["sampled_rows"] == 0 for s in dispatches)
    assert sched.dispatches_sorted == 0

    def mixed():
        sched.submit(21, prompt(21), max_new_tokens=6)
        sched.submit(22, prompt(9), max_new_tokens=5, temperature=0.8, seed=4)
        for _ in range(3):
            sched.step()
        sched.submit(23, prompt(20), max_new_tokens=3)
        sched.run_to_completion()
    before = sched.dispatches
    spans, _ = _captured(tmp_path / "mixed", mixed)
    dispatches = _named(spans, "serving/dispatch")
    assert len(dispatches) == sched.dispatches - before
    held = [s for s in dispatches if s[3]["sampled_rows"] > 0]
    assert sched.dispatches_sorted == len(held)
    first, = (s[3]["round"] for s in _named(spans, "serving/admit")
              if s[3]["uid"] == 22)
    last, = (s[3]["round"] for s in _named(spans, "serving/finish")
             if s[3]["uid"] == 22)
    assert [s[3]["round"] for s in held] == list(range(first, last + 1))
    assert all(s[3]["sampled_rows"] == 1 for s in held)
    # the greedy rows dispatched beside it, alone or after it, sorted nothing
    assert 0 < len(held) < len(dispatches)


def test_cancel_and_context_roof_mark_finish_with_their_reason(tmp_path):
    cfg, sched = _scheduler(max_context=16)
    rng = np.random.default_rng(5)
    prompt = lambda n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)

    def run():
        sched.submit(1, prompt(12), max_new_tokens=100)   # hits the roof
        sched.submit(2, prompt(6), max_new_tokens=100)
        sched.step()
        sched.cancel(2)
        sched.run_to_completion()
    spans, _ = _captured(tmp_path, run)
    reasons = {s[3]["uid"]: s[3]["reason"] for s in _named(spans, "serving/finish")}
    assert reasons == {1: "evicted", 2: "cancelled"}
    assert dict(sched.drain_terminal()) == {1: "evicted", 2: "cancelled"}


def test_train_step_yields_fwd_with_its_parts_inside(tmp_path):
    model = GPT2LMHeadModel(GPT2Config.tiny(dtype=jnp.float32))
    groups.reset()
    devices = jax.devices()[:1]
    engine = deepspeed_tpu.initialize(
        model=model, mesh=MeshTopology(dp=1, devices=devices),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})[0]
    ids = np.random.default_rng(0).integers(0, 512, (2, 16)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}

    def step():
        loss = engine(batch)
        engine.backward(loss)
        engine.step()
    step()                            # compile outside the capture

    def run():
        for _ in range(3):
            step()
        engine._host_fetch(jnp.zeros(2), "test/step")
    spans, _ = _captured(tmp_path, run)
    groups.reset()
    fwds = _named(spans, "fwd")
    assert [s[3]["step"] for s in fwds] == [1, 2, 3]
    for _, a, b, attrs in fwds:
        inside = [s for s in spans if a <= s[1] and s[2] <= b and s[0] != "fwd"]
        assert [s[0] for s in inside] == ["fwd/shard_batch", "fwd/dispatch"]
        assert all(s[3]["step"] == attrs["step"] for s in inside)
        assert attrs["fused"] in (0, 1)
    for name in ("bwd", "step"):
        assert [s[3]["step"] for s in _named(spans, name)] == [1, 2, 3]
    assert [s[3]["what"] for s in _named(spans, "host_fetch")] == ["test/step"]


def test_spans_without_a_session_or_telemetry_grow_no_state():
    """1,000 rounds' worth of spans with no profiler session and telemetry
    disabled: nothing is kept anywhere in the pipeline."""
    tm = telemetry.get_telemetry()
    sizes = lambda: {k: len(v) for k, v in vars(tm).items()
                     if isinstance(v, (list, dict))}
    before = sizes()
    for rnd in range(1000):
        with telemetry.span("serving/round", round=rnd):
            with telemetry.span("serving/compose", round=rnd) as sp:
                telemetry.span("serving/admit", uid=rnd, round=rnd,
                               waited_us=3, prompt_tokens=5).end()
                sp.set(seqs=1, prefill_tokens=5, decode_rows=0, long_rows=0)
            with telemetry.span("serving/build", round=rnd) as sp:
                sp.set(real_tokens=5, padded_slots=32)
            sp = telemetry.span_begin("serving/dispatch", round=rnd, dispatch=rnd)
            for child in CHILDREN:
                part = telemetry.span_begin("serving/dispatch/" + child,
                                            round=rnd, dispatch=rnd)
                part.set(arrays=4, bytes=1024)
                assert part._tm is None
                part.end()
            sp.set(programs=2, first_seen=0, sampled_rows=0)
            sp.end()
            telemetry.span_begin("serving/post_forward", round=rnd,
                                 dispatch=rnd, rows=1).end()
            with telemetry.span("serving/fetch", round=rnd, what="ids"):
                pass
            sp = telemetry.span_begin("serving/retire", round=rnd)
            sp.set(new_tokens=1, finished=0)
            assert sp._tm is None
            sp.end()
    assert sizes() == before
    assert telemetry.summary() == {"enabled": False}


@pytest.mark.parametrize("enabled", [False, True])
def test_no_span_ever_waits_for_the_device(enabled, monkeypatch):
    def _boom(*a, **k):
        raise AssertionError("a span must never wait for the device")
    monkeypatch.setattr(jax, "block_until_ready", _boom)
    telemetry.configure(enabled=enabled)
    cfg, sched = _scheduler()
    sched.submit(1, np.arange(9, dtype=np.int32), max_new_tokens=3)
    sched.run_to_completion()
    pending = jnp.ones(4) * 2
    with telemetry.span("fwd", step=0):
        pass
    assert telemetry.span_begin("step", step=0).end(token=pending) >= 0
    stats = telemetry.get_telemetry().span_stats
    if enabled:     # the same names in the pipeline's own sinks
        assert stats["serving/build"][0] == sched.rounds
        assert stats["serving/admit"][0] == 1
        assert {"serving/round", "serving/compose", "serving/dispatch",
                "serving/fetch", "serving/retire", "fwd", "step"} <= set(stats)
        for name in ("dispatch", "dispatch/h2d", "dispatch/forward",
                     "dispatch/sample", "post_forward"):
            assert stats["serving/" + name][0] == sched.dispatches, name
    else:
        assert stats == {}


def test_flash_kernels_carry_their_fixed_names(pallas_interpret):
    """The scope and the ``name=`` of each ``pallas_call`` reach the lowered
    program: that name is what a device trace lists the kernel under."""
    from deepspeed_tpu.ops.flash_attention import mha
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return mha(q, k, v, causal=True).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q) \
        .as_text(debug_info=True)
    for name in ("flash_mha_fwd", "flash_mha_bwd_dq", "flash_mha_bwd_dkv"):
        assert name in text, name
