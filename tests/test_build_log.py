"""The build ledger (``telemetry/buildlog.py``): one record a program jax
built, fed by ``jax.monitoring``, attributed to the innermost open span;
``built`` / ``build_ms`` on the spans of both engines; the compile sinks fed
when telemetry is enabled."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import telemetry
from deepspeed_tpu.telemetry import buildlog, flightrec

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(autouse=True)
def _clean():
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")
    buildlog.install()
    yield
    telemetry.reset()
    telemetry.configure(enabled=False, jsonl_path="", chrome_trace_path="")
    buildlog.install()      # whatever a case did to jax's lists: ours, once


def _fresh(tag):
    """A jitted function no other test has built, with a nested jit."""
    @jax.jit
    def inner(x):
        return x * 2.0 + tag

    def outer(x):
        return inner(x).sum() + jnp.ones((3,)).sum()
    outer.__name__ = f"outer_{tag}"
    return jax.jit(outer)


def _since(n):
    return telemetry.build_log()[-(telemetry.build_count() - n):] \
        if telemetry.build_count() > n else []


@pytest.mark.parametrize("check", ["one_record", "second_call", "nested", "no_span", "leaked"])
def test_a_jit_build_is_one_record_under_the_span_that_caused_it(check):
    fn, x = _fresh(len(check) * 31 + ord(check[0])), jnp.arange(4.0)
    if check == "leaked":           # begun, never ended (an exception between), dropped
        telemetry.span_begin("serving/dispatch/forward", round=7, dispatch=9)
    n = telemetry.build_count()
    t0 = time.perf_counter()
    if check in ("no_span", "leaked"):
        fn(x)
    else:
        with telemetry.span("fwd", step=3, fused=1):
            with telemetry.span("fwd/shard_batch", step=3):
                pass                # ended: not the innermost any more
            fn(x)
    wall = time.perf_counter() - t0
    recs = _since(n)
    if check == "nested":           # ``inner`` was traced inside ``outer``: one record
        assert [r["program"] for r in recs] == [f"jit({fn.__name__})"]
        return
    rec, = recs
    if check in ("no_span", "leaked"):
        assert rec["under"] is None and rec["tags"] == {}
        return
    assert (rec["under"], rec["tags"]) == ("fwd", {"step": 3, "fused": 1})
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0 and rec["compile_s"] > 0
    assert rec["load_s"] == 0 and t0 <= rec["t"] <= t0 + wall
    assert buildlog.seconds_of(rec) <= wall
    if check == "second_call":
        n = telemetry.build_count()
        with telemetry.span("fwd", step=4):
            fn(x)
        assert telemetry.build_count() == n and _since(n) == []
        assert telemetry.build_ms(0) == 0.0


def test_a_whole_build_inside_a_trace_is_its_own_record_and_counted_once():
    """A constant made eagerly while another program is traced compiles there:
    two records, and the outer program's trace does not hold the inner one's
    lowering and compile a second time."""
    def outer(x):
        with jax.ensure_compile_time_eval():
            c = jnp.linspace(0.0, 1.0, 7).sum()     # built while ``outer`` is traced
        return x * c
    outer.__name__ = "outer_eager_inside"
    x = jnp.arange(4.0)
    n = telemetry.build_count()
    t0 = time.perf_counter()
    jax.jit(outer)(x)
    wall = time.perf_counter() - t0
    recs = _since(n)
    assert recs[-1]["program"] == "jit(outer_eager_inside)" and len(recs) >= 2
    assert sum(buildlog.seconds_of(r) for r in recs) <= wall


@pytest.mark.parametrize("cache", ["off", "miss_then_hit"])
def test_the_record_says_what_the_persistent_cache_did(cache, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    fn, x = _fresh(7 if cache == "off" else 11), jnp.arange(4.0)
    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    try:
        if cache == "off":
            jax.config.update("jax_compilation_cache_dir", None)
            n = telemetry.build_count()
            fn(x)
            rec, = _since(n)
            assert rec["cache"] == "off" and rec["compile_s"] > 0 and not rec["stored"]
            return
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        n = telemetry.build_count()
        fn(x)
        miss, = _since(n)
        assert miss["cache"] == "miss" and miss["stored"] and miss["compile_s"] > 0
        jax.clear_caches()
        n = telemetry.build_count()
        fn(x)
        hit, = [r for r in _since(n) if r["program"] == miss["program"]]
        assert hit["cache"] == "hit" and hit["compile_s"] == 0
        assert hit["load_s"] >= hit["read_s"] > 0 and hit["trace_s"] > 0
        totals = buildlog.totals()
        assert totals["hit"] >= 1 and totals["miss"] >= 1
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _fake_build(name, seconds=0.001):
    """One program's events as jax emits them."""
    from jax import monitoring
    for event in (_TRACE, _LOWER, _COMPILE):
        fun = name if event == _TRACE else f"jit({name})"
        monitoring.record_scalar(event, time.time(), fun_name=fun)
        monitoring.record_event_duration_secs(event, seconds, fun_name=fun)


@pytest.mark.parametrize("check", ["ring", "twice", "uninstalled", "cleared"])
def test_the_ring_is_bounded_and_the_listeners_are_registered_once(check):
    n = telemetry.build_count()
    if check == "ring":
        before = buildlog.totals()
        for i in range(buildlog.CAPACITY + 44):
            _fake_build(f"p{i}")
        log = telemetry.build_log()
        assert len(log) == buildlog.CAPACITY == 256
        assert log[-1]["program"] == f"jit(p{buildlog.CAPACITY + 43})"
        assert log[0]["program"] == "jit(p44)"
        assert telemetry.build_count() == n + 300
        after = buildlog.totals()
        assert after["trace_s"] - before.get("trace_s", 0) == pytest.approx(0.3)
        assert after["listener_calls"] - before["listener_calls"] == 6 * 300
        assert len(telemetry.build_log(last=2)) == 2 and telemetry.build_log(last=0) == []
        return
    if check == "twice":
        buildlog.install()
        buildlog.install()
    elif check == "uninstalled":
        buildlog.uninstall()
        _fake_build("unheard")
        assert telemetry.build_count() == n
        buildlog.install()
    else:       # someone emptied jax's lists: install() registers again, once
        jax.monitoring.clear_event_listeners()
        buildlog.install()
    _fake_build("heard")
    assert telemetry.build_count() == n + 1
    assert telemetry.build_log(last=1)[0]["program"] == "jit(heard)"


def _dispatch_spans(tm):
    return [e["args"] for e in tm.trace_events if e["name"] == "serving/dispatch"]


@pytest.mark.parametrize("speculative", [False, True])
def test_serving_dispatches_say_what_was_built_under_them(speculative):
    """On the engine at a tiny size: the first dispatch of a shape has
    ``built >= 1`` and its records name the buckets, every later dispatch of
    the shape built nothing; the compile sinks hold the programs."""
    from test_program_spans import _scheduler
    cfg, sched = _scheduler(speculative=speculative)
    rng = np.random.default_rng(5)
    prompt = lambda n: np.tile(rng.integers(0, cfg.vocab_size, 3), n)[:n].astype(np.int32)
    telemetry.configure(enabled=True)
    tm = telemetry.get_telemetry()
    n = telemetry.build_count()
    sched.submit(1, prompt(21), max_new_tokens=4)
    sched.submit(2, prompt(9), max_new_tokens=6)
    sched.run_to_completion()
    sched.submit(3, prompt(12), max_new_tokens=3)     # no shape it brings is new
    sched.run_to_completion()
    dispatches = _dispatch_spans(tm)
    assert all(set(a) == {"round", "dispatch", "programs", "first_seen", "sampled_rows",
                          "built", "build_ms"} for a in dispatches)
    recs = [r for r in _since(n) if (r["under"] or "").startswith("serving/dispatch/")]
    by_dispatch = {}
    for r in recs:
        assert set(r["tags"]) == {"round", "dispatch", "seq_bucket", "chunk_bucket",
                                  "verify_k"}
        by_dispatch.setdefault(r["tags"]["dispatch"], []).append(r)
    shapes = set()
    for a in dispatches:
        mine = by_dispatch.get(a["dispatch"], [])
        assert a["built"] >= len(mine)
        if a["first_seen"]:
            forward, = [r for r in mine if r["under"] == "serving/dispatch/forward"]
            shapes.add(tuple(forward["tags"][k] for k in
                             ("seq_bucket", "chunk_bucket", "verify_k")))
            assert a["built"] >= 1 and a["build_ms"] > 0
        else:
            assert a["built"] == 0 and a["build_ms"] == 0.0 and not mine
    assert shapes == {(s, c, k or 0) for s, c, k in sched._engine._shapes_seen}
    assert len(dispatches) > len(shapes) >= 2
    programs = telemetry.summary()["compile"]["programs"]
    for s, c, _ in shapes:
        assert any(name.endswith(f"[{s}x{c}]") for name in programs), (s, c, list(programs))
    assert telemetry.summary()["ledger"]["seconds"]["compile"] > 0


@pytest.mark.parametrize("enabled", [False, True])
def test_the_training_engines_first_step_is_one_record_under_fwd(enabled):
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.parallel.topology import MeshTopology
    groups.reset()
    engine = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(GPT2Config.tiny(dtype=jnp.float32)),
        mesh=MeshTopology(dp=1, devices=jax.devices()[:1]),
        config={"train_micro_batch_size_per_gpu": 2, "fused_step": True,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})[0]
    telemetry.configure(enabled=enabled)
    tm = telemetry.get_telemetry()
    ids = np.random.default_rng(0).integers(0, 512, (2, 16)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    n = telemetry.build_count()
    for _ in range(2):
        engine.backward(engine(batch))
        engine.step()
    groups.reset()
    stepped = [r for r in _since(n)
               if (r["under"] or "").split("/")[0] in ("fwd", "bwd", "step")
               and r["trace_s"] > 0.01]
    rec, = stepped                    # the step program: once, at step 0
    assert rec["under"].split("/")[0] in ("fwd", "step") and rec["tags"]["step"] == 0
    if enabled:
        spans = {name: [e["args"] for e in tm.trace_events if e["name"] == name]
                 for name in ("fwd", "bwd", "step")}
        assert [a["step"] for a in spans["fwd"]] == [0, 1]
        assert spans["fwd"][0]["built"] >= 1 and spans["fwd"][0]["build_ms"] > 0
        assert all(a["built"] == 0 and a["build_ms"] == 0.0
                   for name in spans for a in spans[name][1:] if name != "step") \
            and spans["bwd"][0]["built"] == 0
        summary = telemetry.summary()
        assert rec["program"] in summary["compile"]["programs"]
        # the seconds went from the span's bucket to ``compile``: one bucket a second
        ledger = summary["ledger"]["seconds"]
        assert ledger["compile"] >= buildlog.seconds_of(rec) - 1e-3
        fwd_total = summary["spans"]["fwd"]["total_s"]
        assert ledger["compute"] <= fwd_total - buildlog.seconds_of(rec) + \
            summary["spans"]["bwd"]["total_s"] + summary["spans"]["step"]["total_s"] + 1e-3
    else:
        assert telemetry.summary() == {"enabled": False}


@pytest.mark.parametrize("tags,recorded", [({"step": 0}, False), ({"step": 2}, True),
                                           ({"round": 5, "dispatch": 9}, True), ({}, False)])
def test_a_compile_past_the_first_round_or_step_is_flight_recorded(tags, recorded):
    flightrec.reset()
    with telemetry.span("fwd", **tags):
        _fake_build("late_shape", seconds=0.002)
    events = [e for e in flightrec.get_recorder().events() if e["kind"] == "compile"]
    assert len(events) == recorded
    if recorded:
        event, = events
        assert event["name"] == "jit(late_shape)"
        assert event["detail"]["under"] == "fwd" and event["detail"]["cache"] == "off"
        assert all(event["detail"][k] == v for k, v in tags.items())
