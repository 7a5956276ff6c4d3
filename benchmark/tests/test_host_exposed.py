"""``benchmark/readers/host_exposed.py``: the pairing of each
``ds/serving/dispatch`` span with the device runs it says it enqueued, the
clock offset's interval from every dispatch, and the four metrics, on
hand-made timelines and on ``data/dispatch_spans.xplane.pb`` (recorded on the
chip by ``record_spans_trace.py`` from the program of PR 39; ``spans.xplane.pb``
is the same recording of the program before it)."""

import os

import pytest

from benchmark import program_spans as ps, trace
from benchmark.readers import host_exposed as hx

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW, OLD = (os.path.join(DATA, name) for name in ("dispatch_spans.xplane.pb", "spans.xplane.pb"))
WHAT = ("host_exposed_ms", "host_prelaunch_ms", "fetch_tail_ms", "dispatch_host_ms")

# -- a hand-made timeline -------------------------------------------------------
# host times in ns. The device starts a run LAUNCH after the span that called
# it opened, or 5 us behind the run before it where it is still busy; the
# fetch returns WAKE after the round's last run ends

US = 1000
FORWARD_NS, SAMPLE_NS, LAUNCH, WAKE = 9000 * US, 400 * US, 300 * US, 150 * US
QUEUED = 5 * US


def _dispatch(n, r, t, programs):
    """A dispatch's spans from ``t`` on, and its calls: [(host time of the
    span that makes the call, what the run takes)]."""
    span = lambda name, a, b, **kw: (name, t + a * US, t + b * US,
                                     dict(round=r, dispatch=n, **kw))
    spans = [span(ps.BUILD, 0, 500, seq_bucket=4 if n % 2 else 1,
                  chunk_bucket=1 if n % 2 else 16, seqs=1, real_tokens=1, padded_slots=4),
             span(ps.DISPATCH, 500, 1500, programs=programs, first_seen=int(n < 2),
                  sampled_rows=0),
             span(hx.H2D, 550, 700, arrays=4, bytes=96),
             span(hx.FORWARD, 700, 1100),
             span(hx.POST_FORWARD, 1500, 1600)]
    calls = [(t + 700 * US, FORWARD_NS)]
    if programs == 2:
        spans.append(span(hx.SAMPLE, 1100, 1400))
        calls.append((t + 1100 * US, SAMPLE_NS))
    return spans, calls


def _timeline(shift, starts=(0, 40_000, 80_000), two=(0,), programs=2):
    """Rounds at ``starts`` (us); those whose index is in ``two`` take two
    dispatches, the second built while the first runs. Returns the loaded
    trace (device events at host time + ``shift``) and, per round, (start,
    first run's start, last run's end, fetch's end, round's end) in host
    time."""
    spans, runs, facts, n, free = [], [], [], 0, 0
    for r, t in enumerate(t * US for t in starts):
        mine, at = [], t + 1000 * US
        for _ in range(2 if r in two else 1):
            s, calls = _dispatch(n, r, at, programs)
            spans += s
            for called, took in calls:
                start = max(called + LAUNCH, free + QUEUED)
                free = start + took
                mine.append((start, free))
            n, at = n + 1, at + 1600 * US
        fetch_end = free + WAKE
        spans += [(ps.ROUND, t, fetch_end + 300 * US, {"round": r}),
                  (ps.COMPOSE, t + 10 * US, t + 1000 * US, {"round": r}),
                  (ps.FETCH, at, fetch_end, {"round": r, "what": "ids"}),
                  (ps.RETIRE, fetch_end, fetch_end + 250 * US, {"round": r})]
        runs += mine
        facts.append((t, mine[0][0], free, fetch_end, fetch_end + 300 * US))
    device = [(a + shift, b + shift) for a, b in runs]
    loaded = {"spans": sorted(spans, key=lambda s: (s[1], -s[2])), "modules": device,
              "ops": device, "window": (0, (starts[-1] + 40_000) * US), "enqueue": [],
              "done": []}
    return loaded, facts


def _read(loaded, what):
    ctx = {"program_spans": loaded, "notes": []}
    return hx.read(ctx, {"what": what}), ctx["notes"]


def test_pairing_by_programs_is_exact_on_a_round_of_two_dispatches():
    loaded, _ = _timeline(700)
    pairs, _, why = hx.pair(loaded)
    assert why is None and [p["dispatch"] for p in pairs] == [0, 1, 2, 3]
    assert [p["round"] for p in pairs] == [0, 0, 1, 2]
    assert [r for p in pairs for r in p["runs"]] == loaded["modules"]
    assert all(len(p["runs"]) == 2 for p in pairs)
    # the second dispatch of round 0 was built while the first ran: its runs
    # follow the first's on the device, long after its own span ended
    assert pairs[1]["runs"][0][0] == pairs[0]["runs"][1][1] + QUEUED
    assert pairs[1]["runs"][0][0] - 700 > pairs[1]["span"][1]
    # a dispatch through ``put`` says one program, and takes one run
    one, _ = _timeline(700, programs=1)
    pairs, _, _ = hx.pair(one)
    assert [len(p["runs"]) for p in pairs] == [1, 1, 1, 1]
    assert [r for p in pairs for r in p["runs"]] == one["modules"]


@pytest.mark.parametrize("shift", [-2_000_000, 700, 1_500_000])
def test_a_shifted_host_clock_is_recovered_within_the_interval(shift):
    loaded, facts = _timeline(shift)
    found = hx.analyse(loaded)
    lower, upper = found["offset"]
    assert lower <= shift <= upper
    # the launch latency above, the fetch's wake-up below
    assert upper - lower == LAUNCH + WAKE
    centre = (lower + upper) / 2
    for row, (start, first, last, fetch_end, _) in zip(found["rounds"], facts):
        assert row["start"] == start + centre and row["fetch_end"] == fetch_end + centre
        assert row["first_run"] == first + shift and row["last_run"] == last + shift
    # the runtime's events, one a run, narrow it: enqueued 40 us before a run
    # starts, its completion handled 30 us after it ends
    runs = loaded["modules"]
    narrowed = dict(loaded, enqueue=[a - shift - 40 * US for a, _ in runs],
                    done=[b - shift + 30 * US for _, b in runs])
    lower, upper = hx.analyse(narrowed)["offset"]
    assert lower <= shift <= upper and upper - lower == (40 + 30) * US
    # fewer events than runs: left out, the spans' own interval stands
    fewer = dict(narrowed, enqueue=narrowed["enqueue"][1:], done=narrowed["done"][:-2])
    assert hx.analyse(fewer)["offset"] == found["offset"]


def test_the_four_metrics_on_a_known_timeline():
    loaded, facts = _timeline(700)
    err = (LAUNCH + WAKE) / 2 / 1e6           # the offset's half width, ms
    value, _ = _read(loaded, "host_prelaunch_ms")
    assert value == pytest.approx((1000 * US + 700 * US + LAUNCH) / 1e6, abs=err)
    value, _ = _read(loaded, "fetch_tail_ms")
    assert value == pytest.approx(WAKE / 1e6, abs=err)
    value, notes = _read(loaded, "dispatch_host_ms")
    assert value == pytest.approx(1.0)
    assert "4 dispatches, 2 first of their shape" in notes[-1]
    assert "/h2d 0.150, /forward 0.400, /sample 0.300, self 0.150" in notes[-1]
    assert "/h2d copies 4 host arrays of 96 bytes together a dispatch, 38 us an array" \
        in notes[-1]
    assert "serving/post_forward behind it 0.100" in notes[-1]
    assert "[1, 16] 1.000 (2), [4, 1] 1.000 (2)" in notes[-1]
    # idle inside the rounds: all of a round but its runs, give or take what
    # the centre's error moves across a round's edges
    value, notes = _read(loaded, "host_exposed_ms")
    busy = sum(b - a for a, b in loaded["modules"])
    inside = sum(end - start for start, _, _, _, end in facts) - busy
    assert value == pytest.approx(inside / 3 / 1e6, abs=2 * err)
    found = hx.analyse(loaded)
    # between the rounds and at the window's edges: the rest of the window
    window = loaded["window"][1] - loaded["window"][0]
    stretch = facts[-1][4] - facts[0][0]
    assert (found["idle_inside_s"] + found["idle_outside_s"]) * 1e9 == \
        pytest.approx(stretch - busy)
    assert found["idle_edges_s"] * 1e9 == pytest.approx(window - stretch)
    # the same total from the rounds' lengths and their paired runs alone
    assert found["idle_by_runs_s"] * 1e9 == pytest.approx(inside)
    # rounds 1 and 2 alike, round 0 (two dispatches) idles less
    assert found["round_idle_median_ms"] * 1e6 == pytest.approx(
        facts[1][4] - facts[1][0] - FORWARD_NS - SAMPLE_NS)
    assert found["idle_inside_s"] * 1e9 == pytest.approx(inside, abs=3 * 2 * err * 1e6)
    # the interval's ends move a span's share by no more than its half
    # width a round's edge
    assert 0 < found["moved_s"] * 1e9 <= 3 * 2 * err * 1e6
    split = found["idle_by_span"]
    assert {ps.COMPOSE, ps.BUILD, hx.H2D, hx.FORWARD, ps.RETIRE, ps.FETCH} <= set(split)
    # a round's compose and its first dispatch's copies find the device idle;
    # the second dispatch of round 0 was built under the first's run
    assert split[ps.COMPOSE] == pytest.approx(3 * 990 * US / 1e9)
    assert split[hx.H2D] == pytest.approx(3 * 150 * US / 1e9)
    assert split[ps.BUILD] == pytest.approx(3 * 500 * US / 1e9)
    assert "ms a round" in notes[-1] and "outside every round" in notes[-1]
    assert "moves no span's share by more than" in notes[-1]
    assert "less their own runs of XLA Modules" in notes[-1]
    assert sum("host_exposed: " in n and "dispatches paired" in n for n in notes) == 1


def test_a_trace_without_programs_reads_none_with_a_note():
    loaded, _ = _timeline(700)
    old = dict(loaded, spans=[(n, a, b, {k: v for k, v in attrs.items()
                                         if k not in ("programs", "dispatch", "first_seen")})
                              for n, a, b, attrs in loaded["spans"]
                              if n not in (hx.H2D, hx.FORWARD, hx.SAMPLE, hx.POST_FORWARD)])
    for what in WHAT:
        value, notes = _read(old, what)
        assert value is None
        assert "before PR 39" in notes[0] and "nothing read" in notes[0]


@pytest.mark.parametrize("case", ["a run missing", "a run of nobody's behind the last",
                                  "a run of nobody's between two dispatches"])
def test_a_window_whose_counts_do_not_match_reads_none_with_a_note(case):
    loaded, _ = _timeline(700)
    modules = {"a run missing": loaded["modules"][:-1],
               # over before the last round's fetch returns
               "a run of nobody's behind the last":
                   loaded["modules"] + [(loaded["modules"][-1][1] + 10 * US,
                                         loaded["modules"][-1][1] + 50 * US)],
               # a swap between rounds 0 and 1, which no span says
               "a run of nobody's between two dispatches":
                   sorted(loaded["modules"] + [(39_000 * US, 39_100 * US)])}[case]
    short = dict(loaded, modules=modules)
    for what in WHAT[:3]:
        value, notes = _read(short, what)
        assert value is None
        assert "spans say 8 programs, the device's XLA Modules line ran" in notes[0]
        assert "the counts do not match" in notes[0] and "nothing read" in notes[0]
    # the spans' own durations need no device run
    assert _read(short, "dispatch_host_ms")[0] == pytest.approx(1.0)


def test_runs_around_the_window_are_no_part_of_the_pairing():
    """A capture that holds a round before the window and one behind it: the
    window's dispatches take their own runs, whatever lies around them."""
    loaded, _ = _timeline(700, starts=(0, 40_000, 80_000, 120_000, 160_000))
    inner = dict(loaded, window=(39_000 * US, 125_000 * US))
    pairs, bounds, why = hx.pair(inner)
    whole = hx.pair(loaded)[0]
    assert why is None and [p["dispatch"] for p in pairs] == [2, 3, 4]
    assert [p["runs"] for p in pairs] == [p["runs"] for p in whole[2:5]]
    assert bounds[0] <= 700 <= bounds[1]
    found = hx.analyse(inner)
    assert [r["round"] for r in found["rounds"]] == [1, 2, 3]
    assert found["idle_edges_s"] > 0
    # a round cut by the window's edge adds neither a round nor idle time
    cut = hx.analyse(dict(loaded, window=(39_000 * US, 162_000 * US)))
    assert [r["round"] for r in cut["rounds"]] == [1, 2, 3]
    assert cut["idle_inside_s"] == found["idle_inside_s"]


# -- the recorded traces ----------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    ctx = {"trace_path": NEW, "notes": []}
    return ps.for_run(ctx), ctx


def test_recorded_pairing_is_exact_and_the_offset_no_wider_than_before(recorded):
    loaded, _ = recorded
    dispatches = ps.named(loaded, ps.DISPATCH)
    by_round = ps.by_round(loaded, ps.DISPATCH)
    assert max(len(v) for v in by_round.values()) == 2, "no round of two dispatches recorded"
    found = hx.analyse(loaded)
    pairs = found["pairs"]
    assert len(pairs) == len(dispatches) and all(len(p["runs"]) == 2 for p in pairs)
    serve_runs = [r for p in pairs for r in p["runs"]]
    assert serve_runs == loaded["modules"][:len(serve_runs)], "the train steps' runs come after"
    assert len(loaded["modules"]) > len(serve_runs)
    # every run starts after its dispatch's span (on the device's clock, at
    # the interval's lower end) and in the order of the spans
    lower, upper = found["offset"]
    assert 0 <= upper - lower < 1e6
    for p in pairs:
        assert p["runs"][0][0] >= p["span"][0] + lower
    assert loaded["offset"] is None or upper - lower <= loaded["offset"][1] + 1e-6
    assert "the counts agree" in found["note"][0]


@pytest.mark.parametrize("what", WHAT)
def test_each_metric_reads_a_number_on_the_recorded_trace(what):
    ctx = {"trace_path": NEW, "notes": []}
    value = hx.read(ctx, {"what": what})
    assert value is not None and value >= 0
    assert any(n.startswith(f"host_exposed {what}: ") for n in ctx["notes"])
    assert sum(n.startswith("host_exposed: ") for n in ctx["notes"]) == 1


def test_recorded_idle_adds_up_and_little_is_left_unnamed(recorded):
    loaded, _ = recorded
    found = hx.analyse(loaded)
    serving = {k: v for k, v in found["idle_by_span"].items() if k.startswith("ds/serving/")}
    # "inside the rounds" is the idle time under the ds/serving/ spans: each
    # of them lies inside a round; with the idle time between the rounds and
    # at the window's edges (here the train steps behind the last round) it
    # is the window less the union of the device's operations
    rounds = ps.named(loaded, ps.ROUND)
    assert all(any(a <= s[1] and s[2] <= b for _, a, b, _ in rounds)
               for s in loaded["spans"] if s[0].startswith("ds/serving/"))
    shift = sum(found["offset"]) / 2
    lo, hi = (t + shift for t in loaded["window"])
    busy = sum(b - a for a, b in trace.union([(max(a, lo), min(b, hi)) for a, b in loaded["ops"]
                                              if b > lo and a < hi]))
    assert (found["idle_inside_s"] + found["idle_outside_s"] + found["idle_edges_s"]) * 1e9 == \
        pytest.approx(hi - lo - busy)
    assert found["idle_edges_s"] > found["idle_outside_s"] > 0
    # the rounds' lengths less their paired runs: the same idle time, read
    # from neither the operations nor the spans inside a round
    assert found["idle_by_runs_s"] == pytest.approx(found["idle_inside_s"], rel=0.02)
    assert found["moved_s"] < 0.05 * found["idle_inside_s"]
    own = serving.get(ps.ROUND, 0) + serving.get(ps.DISPATCH, 0)
    assert own < 0.1 * sum(serving.values())
    assert {hx.H2D, hx.FORWARD, hx.SAMPLE, hx.POST_FORWARD} & set(serving)


def test_the_recording_from_before_pr_39_reads_none_with_a_note():
    for what in WHAT:
        ctx = {"trace_path": OLD, "notes": []}
        assert hx.read(ctx, {"what": what}) is None
        assert any("before PR 39" in n for n in ctx["notes"])
