"""The reader of the program's build ledger on made-up records: set-up and
window split at the ``window`` span, the three ``what``s over the records
under the engine's spans, the note's lines, None where there is no ledger."""

import pytest

from benchmark.readers import build_log


def rec(program, t, under=None, trace=0.0, lower=0.0, compile_s=0.0, load=0.0,
        cache="hit", stored=False, **tags):
    return {"program": program, "t": t, "trace_s": trace, "lower_s": lower,
            "compile_s": compile_s, "load_s": load, "read_s": 0.0, "saved_s": 0.0,
            "cache": cache, "stored": stored, "under": under, "tags": tags}


FORWARD, SAMPLE = "serving/dispatch/forward", "serving/dispatch/sample"
SHAPE = {"round": 0, "dispatch": 0, "seq_bucket": 64, "chunk_bucket": 1, "verify_k": 0}
RECORDS = [
    rec("jit(_make)", 1.0, trace=0.1, lower=0.2, compile_s=3.0, cache="miss", stored=True),
    rec("jit(broadcast_in_dim)", 2.0, trace=0.01, lower=0.02, compile_s=0.03, cache="miss"),
    rec("jit(relay)", 3.0, "serving/prepare_params", trace=0.5, lower=0.25, load=0.125,
        family="llama"),
    rec("jit(packed_forward)", 4.0, FORWARD, trace=1.0, lower=2.0, compile_s=16.0,
        cache="miss", stored=True, **SHAPE),
    rec("jit(sample_rows_packed)", 5.0, SAMPLE, trace=0.25, lower=0.25, compile_s=0.5,
        cache="miss", **SHAPE),
    rec("jit(packed_forward)", 6.0, FORWARD, trace=1.0, lower=1.0, load=0.5,
        **dict(SHAPE, round=1, dispatch=1, seq_bucket=1, chunk_bucket=512)),
    rec("jit(packed_forward)", 12.0, FORWARD, trace=1.0, lower=1.0, compile_s=9.0,
        cache="miss", stored=True, **dict(SHAPE, round=40, dispatch=77, seq_bucket=8)),
    rec("jit(reference_forward)", 30.0, trace=2.0, lower=1.0, compile_s=50.0, cache="miss",
        stored=True),
]
SPANS = [("round", 10.5, 10.6, {}), ("window", 10.0, 20.0, {})]


@pytest.fixture
def ledger(monkeypatch):
    """The program's ledger holding ``RECORDS``."""
    from deepspeed_tpu import telemetry
    held = list(RECORDS)
    monkeypatch.setattr(telemetry, "build_log", lambda last=None: [dict(r) for r in held])
    monkeypatch.setattr(telemetry, "build_count", lambda: len(held) + 3)
    monkeypatch.setattr(telemetry.buildlog, "totals",
                        lambda: {"listener_s": 0.004, "listener_calls": 1234})
    return held


def test_set_up_is_what_began_before_the_window_span():
    setup, window, later = build_log.split(RECORDS, SPANS)
    assert [r["t"] for r in setup] == [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    assert [r["t"] for r in window] == [12.0] and [r["t"] for r in later] == [30.0]
    assert build_log.split(RECORDS, [("round", 1.0, 2.0, {})]) is None


@pytest.mark.parametrize("what,value", [
    ("build_host_s", 0.75 + 3.0 + 0.5 + 2.0),          # trace + lower under the engine's spans
    ("build_backend_s", 0.125 + 16.0 + 0.5 + 0.5),     # compile or load of the same programs
    ("programs_cache_missed", 1)])                     # the sampler's was never kept
def test_a_metric_sums_the_set_up_records_under_the_engines_spans(what, value, ledger):
    ctx = {"spans": SPANS, "notes": []}
    got = build_log.read(ctx, {"what": what})
    assert got == pytest.approx(value) and type(got) is type(value)
    # the note once, however many metrics read the ledger
    n = len(ctx["notes"])
    build_log.read(ctx, {"what": "build_host_s"})
    assert len(ctx["notes"]) == n > 0


def test_the_note_is_the_table_a_builder_needs(ledger):
    ctx = {"spans": SPANS, "notes": []}
    build_log.read(ctx, {"what": "build_host_s"})
    note = "\n".join(ctx["notes"])
    assert "set-up built 6 programs; under the engine's spans 4 programs" in note
    assert ("  serving/dispatch/forward [64, 1] jit(packed_forward): trace 1.000 lower 2.000 "
            "compile 16.000 miss") in note
    assert ("  serving/dispatch/sample [64, 1] jit(sample_rows_packed): trace 0.250 lower 0.250 "
            "compile 0.500 miss (not kept)") in note
    assert "  serving/dispatch/forward [1, 512] jit(packed_forward): trace 1.000 lower 1.000 " \
           "load 0.500 hit" in note
    assert "  serving/prepare_params - jit(relay)" in note
    assert "under serving/dispatch/forward: 2 programs, host 5.000 s" in note
    # the builds under no span, summed beside it with the same three figures
    assert "under no span (the harness's weights, the pools) 2 programs, host 0.330 s " \
           "(trace 0.110 + lower 0.220), backend 3.030 s (compile 3.030 + load 0.000), " \
           "missed 1, too quick to keep 1" in note
    assert "  jit(_make) x1: host 0.300 s, backend 3.000 s, missed 1" in note
    assert "3 records had left the ring" in note
    assert "the ledger's own handlers 4.00 ms over 1234 calls" in note
    # what ``programs_compiled_or_loaded_in_window`` counts without a name
    assert "1 programs built INSIDE the window: jit(packed_forward) under " \
           "serving/dispatch/forward [8, 1] 11000.0 ms" in note
    assert "jit(reference_forward)" not in note


def test_nothing_inside_the_window_says_so(ledger):
    del ledger[6]
    ctx = {"spans": SPANS, "notes": []}
    assert build_log.read(ctx, {"what": "programs_cache_missed"}) == 1
    assert "nothing was built inside the window" in "\n".join(ctx["notes"])


@pytest.mark.parametrize("why", ["no ledger", "empty ledger", "no window span"])
def test_none_where_there_is_nothing_to_read(why, monkeypatch, ledger):
    from deepspeed_tpu import telemetry
    ctx = {"spans": SPANS, "notes": []}
    if why == "no ledger":              # the parent: telemetry has no ``build_log``
        monkeypatch.delattr(telemetry, "build_log")
    elif why == "empty ledger":
        ledger.clear()
    else:
        ctx["spans"] = SPANS[:1]
    assert build_log.read(ctx, {"what": "build_host_s"}) is None and ctx["notes"] == []


def test_the_six_metric_files_name_this_reader(tiny_bench):
    import json
    import os
    from benchmark import harness
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["moves"] == "setup_s"]
    assert [m["name"] for m in mine] == [
        f"{what}.{kind}" for what in build_log.WHAT for kind in ("serve", "train")]
    for m in mine:
        with open(os.path.join(harness.HERE, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec == {"reader": "build_log", "params": {"what": m["name"].rsplit(".", 1)[0]}}
        train = m["name"].endswith(".train")
        assert len(m["workloads"]) == (1 if train else 8) and m["better"] == "lower"
        assert m["layer"] == ("training engine" if train else "serving engine and scheduler")
