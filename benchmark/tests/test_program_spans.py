"""The readers of the program's ``ds/`` spans: on ``data/spans.xplane.pb``,
recorded on the chip by ``record_spans_trace.py`` (a few rounds of a small
serving engine, then a few train steps, one profiler session), and on
hand-made timelines."""

import os

import pytest

from benchmark import program_spans as ps, trace
from benchmark.readers import ds_spans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPANS = os.path.join(DATA, "spans.xplane.pb")
SERVE = ("batch_occupancy", "queue_wait_ms", "round_host_ms", "host_gap_ms")


def _ctx(path):
    return {"trace_path": path, "notes": []}


@pytest.fixture(scope="module")
def loaded():
    return ps.for_run(_ctx(SPANS))


def test_recorded_trace_is_small_and_holds_rounds_and_steps(loaded):
    assert os.path.getsize(SPANS) < 200_000
    assert len(ps.named(loaded, ps.ROUND)) >= 3
    assert len(ps.named(loaded, ps.PREFIX + "fwd")) == 3
    assert loaded["ops"], "recorded on the chip: the device's operations are there"
    # what the accepted reduction reads is in the same file
    old = trace.load(SPANS)
    assert trace.window_of(old) == loaded["window"]
    assert len(trace.spans_named(old, "bench/round")) == len(ps.named(loaded, ps.ROUND))


def test_offset_interval_is_not_empty_and_under_a_millisecond(loaded):
    table = loaded["table"]
    assert len(table) == len(ps.named(loaded, ps.BUILD))
    centre, width = loaded["offset"]
    assert 0 <= width < 1e6
    for row in table:              # every round keeps each of its bounds
        for before, after in (("dispatch_start", "fetch_end"), ("enqueue_start", "done_start")):
            assert row["dev_start"] - row[before] >= centre + width / 2 - 1e-6
            assert row["dev_end"] - row[after] <= centre - width / 2 + 1e-6
        assert row["dispatch_start"] < row["enqueue_start"] < row["done_start"] < row["fetch_end"]
        assert row["dev_start"] < row["dev_end"]
    # the program's spans alone give a wider interval around the same offset
    own = ps.offset([dict(r, enqueue_start=None, done_start=None) for r in table])
    assert own[1] > width and abs(own[0] - centre) < own[1] / 2


@pytest.mark.parametrize("what", SERVE + ("step_host_ms",))
def test_each_reader_returns_a_value_and_says_what_it_rests_on(what):
    ctx = _ctx(SPANS)
    value = ds_spans.read(ctx, {"what": what})
    assert value is not None and value >= 0
    assert any(n.startswith(f"ds_spans {what}: ") for n in ctx["notes"])
    assert sum(n.startswith("program_spans: ") for n in ctx["notes"]) == 1
    if what == "batch_occupancy":
        assert 0 < value <= 100
        assert "with prefill tokens" in ctx["notes"][-1]


def test_readers_agree_with_the_spans_they_read(loaded):
    builds = ps.named(loaded, ps.BUILD)
    real = sum(s[3]["real_tokens"] for s in builds)
    assert real == (40 + 100) + (3 + 2)       # two prompts, then the decode rows
    ctx = _ctx(SPANS)
    assert ds_spans.read(ctx, {"what": "batch_occupancy"}) == pytest.approx(
        100.0 * real / sum(s[3]["padded_slots"] for s in builds))
    # the host's part of a round is shorter than the round; the device's
    # idle time inside the program's rounds is part of its whole idle time
    # between two rounds (it also waits for the fetch's return, which the
    # host's part leaves out, so it may exceed that)
    host = ds_spans.read(ctx, {"what": "round_host_ms"})
    rounds = [(b - a) / 1e6 for _, a, b, _ in ps.named(loaded, ps.ROUND)]
    assert 0 < host < max(rounds)
    gap = ds_spans.read(ctx, {"what": "host_gap_ms"})
    table = loaded["table"]
    whole = [(nxt["dev_start"] - prev["dev_end"]) / 1e6 for prev, nxt in zip(table, table[1:])]
    assert 0 < gap <= ps.statistics.median(whole) < max(rounds)
    idle = ps.idle_by_span(loaded, loaded["offset"][0], loaded["offset"][1])
    assert set(idle) & {ps.COMPOSE, ps.BUILD, ps.DISPATCH, ps.RETIRE, ps.ROUND}


def test_a_trace_without_program_spans_gives_none_and_a_note():
    # the probe trace of PR 26 was recorded before the program had spans
    for what in SERVE + ("step_host_ms",):
        ctx = _ctx(os.path.join(DATA, "probe.xplane.pb"))
        assert ds_spans.read(ctx, {"what": what}) is None
        assert "no ds/ span" in ctx["notes"][0]


def test_no_trace_file_of_this_run_gives_none_and_a_note(tmp_path):
    class Cell:
        name = "no-such-cell"
    ctx = {"cell": Cell, "notes": []}
    assert ds_spans.read(ctx, {"what": "batch_occupancy"}) is None
    assert "no trace file" in ctx["notes"][0]
    old = tmp_path / ".bench_out" / "c" / "seed1_trace1" / "trace" / "plugins" / "profile" / "x"
    old.mkdir(parents=True)
    (old / "h.xplane.pb").write_bytes(b"")
    assert ps.find_trace("c", root=str(tmp_path)) == str(old / "h.xplane.pb")
    os.utime(old / "h.xplane.pb", (1, 1))      # from before this process: not this run's
    assert ps.find_trace("c", root=str(tmp_path)) is None


# -- hand-made timelines ------------------------------------------------------

def _round(n, t, dispatch_at, fetch_end):
    """A round's spans: compose, build, dispatch, fetch, retire inside it."""
    span = lambda name, a, b, **kw: (name, a, b, dict(round=n, **kw))
    return [span(ps.ROUND, t, fetch_end + 300),
            span(ps.COMPOSE, t + 10, t + 200),
            span(ps.BUILD, t + 200, dispatch_at, real_tokens=3, padded_slots=32, seqs=3),
            span(ps.DISPATCH, dispatch_at, dispatch_at + 400),
            span(ps.FETCH, dispatch_at + 500, fetch_end, what="ids"),
            span(ps.RETIRE, fetch_end, fetch_end + 250, new_tokens=3, finished=0)]


def test_offset_and_gaps_on_a_known_timeline():
    shift = 700                        # device clock - host clock, ns
    spans, ops = [], []
    for n, t in enumerate((0, 30_000, 70_000)):
        dispatch_at, dev = t + 1000, (t + 1300, t + 20_000)
        spans += _round(n, t, dispatch_at, dev[1] + 150)
        ops += [(dev[0] + shift, dev[0] + 9000 + shift), (dev[0] + 9010 + shift, dev[1] + shift)]
    loaded = {"spans": sorted(spans, key=lambda s: (s[1], -s[2])), "ops": ops,
              "window": (0, 100_000)}
    table = ps.round_table(loaded)
    assert [r["round"] for r in table] == [0, 1, 2]
    centre, width = ps.offset(table)
    assert centre - width / 2 <= shift <= centre + width / 2
    assert width == 300 + 150          # launch latency + the fetch's return
    # the device idles from one round's end to the next one's first operation
    # (10_000 + 1300 and 20_000 + 1300 ns); the program holds the host for
    # retire + compose + build + 300 ns of dispatch of that, but for the error
    # the centre makes
    gaps = ps.host_gaps(table, loaded, centre)
    inside = 150 + 300 + 1000 + 300
    assert all(abs(g - inside) <= width for g in gaps)
    idle = ps.idle_by_span(loaded, shift, min_gap_ns=100)
    assert idle[ps.BUILD] == pytest.approx(3 * 800 / 1e9)
    assert idle["outside ds/ spans"] == pytest.approx(
        ((30_000 - 20_450) + (70_000 - 50_450) + (100_000 - 90_450)) / 1e9)
    assert ps.RETIRE in idle and ps.COMPOSE in idle and ps.DISPATCH in idle
    # rounds paired with the wrong bursts: the bounds cross, no offset
    assert ps.offset(ps.round_table(dict(loaded, ops=ops[2:]))) is None


def test_bursts_cut_at_the_longest_gaps():
    ops = [(0, 10), (11, 20), (100, 110), (112, 130), (400, 410)]
    assert ps.bursts(ops, 3) == [(0, 20), (100, 130), (400, 410)]
    assert ps.bursts(ops, 1) == [(0, 410)]
    assert ps.bursts(ops, 6) == []


def test_innermost_segments_follow_the_nesting():
    spans = [("ds/a", 0, 100, {}), ("ds/b", 10, 40, {}), ("ds/c", 20, 30, {}),
             (ps.ADMIT, 25, 25, {}), ("ds/b", 50, 60, {})]
    assert ps.innermost_segments(spans) == [
        (0, 10, "ds/a"), (10, 20, "ds/b"), (20, 30, "ds/c"), (30, 40, "ds/b"),
        (40, 50, "ds/a"), (50, 60, "ds/b"), (60, 100, "ds/a")]
