"""Which host phase the device waits for: per-layer metrics read from the
program's ``ds/serving/*`` spans paired, per DISPATCH, with the device runs
they caused (``benchmark/program_spans.py`` loads the trace).

Each ``ds/serving/dispatch`` span says how many executables it enqueued
(``programs``), so the k-th span owns the next ``programs`` runs of the
device's ``XLA Modules`` line: no guess about rounds of equal length. Every
dispatch then bounds device clock minus host clock from above (the jitted
call, inside ``dispatch/forward`` and ``dispatch/sample``, precedes the run it
enqueues) and every round from below (``serving/fetch`` ends after the round's
last run); the runtime's own host events (``DoEnqueueProgram``, ``Execute=>Done``)
narrow the interval where the trace holds one per run. The host's spans go
onto the device's clock at the interval's centre.

params: ``what`` one of

- ``host_exposed_ms``: device idle time inside the window's ``serving/round``
  spans over its rounds; the note splits it by the innermost ``ds/`` span open
  at the time (ms a round), gives the idle time outside every round (the
  driver's, between ``step()`` calls), how far the offset's interval moves
  the split, and the same total from the paired runs alone;
- ``host_prelaunch_ms``: median over rounds of ``serving/round``'s start to
  the first run of the round's first dispatch: what composing round n + 1
  under round n could hide;
- ``fetch_tail_ms``: median over rounds of the end of the round's last run to
  the end of ``serving/fetch``: what only ids that stay on the device hide;
- ``dispatch_host_ms``: median over dispatches of ``serving/dispatch``; the
  note gives the medians of its children, its self time, the host arrays and
  bytes a dispatch copies (``dispatch/h2d``'s ``arrays``, ``bytes``), and the
  median by (sequence bucket, chunk bucket). Needs no pairing.

A trace whose dispatch spans lack ``programs`` (an older program) or whose
counts do not match gives None and a note that says why, never a guess.
"""

import bisect
import statistics

from benchmark import program_spans as ps

SERVING = ps.PREFIX + "serving/"
POST_FORWARD = SERVING + "post_forward"
H2D, FORWARD, SAMPLE = (ps.DISPATCH + "/" + n for n in ("h2d", "forward", "sample"))


def _between(times, lo, hi):
    return times[bisect.bisect_left(times, lo):bisect.bisect_right(times, hi)]


def pair(loaded):
    """(pairs, bounds, why): one {"dispatch", "round", "span": (start, end),
    "runs": [(start, end)] on the device's clock} per ``serving/dispatch``
    span of the window, in order, each with the ``programs`` runs of ``XLA
    Modules`` it enqueued, and ``offset_interval`` of that pairing. The runs
    are the one stretch of the line, as long as the spans say and starting
    within ``CLOCK_SLACK_NS`` of the first span, on which every dispatch's
    bounds hold together: a stretch one run early or late breaks them, so
    runs around the window (a round before it, the train steps after it) are
    no part of it. (None, None, why) where the spans lack ``programs``, no
    stretch or more than one fits, or a run of nobody's lies inside it."""
    spans = ps.named(loaded, ps.DISPATCH)
    if not spans:
        return None, None, "no ds/serving/dispatch span in the window"
    if any("programs" not in s[3] or "dispatch" not in s[3] for s in spans):
        return None, None, ("the ds/serving/dispatch spans carry no `programs` (a program "
                            "from before PR 39): no pairing, nothing read")
    said = sum(s[3]["programs"] for s in spans)
    runs = loaded.get("modules", [])
    starts = [a for a, _ in runs]
    fits = []
    for i in range(bisect.bisect_left(starts, spans[0][1] - ps.CLOCK_SLACK_NS),
                   min(bisect.bisect_right(starts, spans[0][1] + ps.CLOCK_SLACK_NS),
                       len(runs) - said + 1)):
        pairs, at = [], i
        for _, a, b, attrs in spans:
            pairs.append({"dispatch": attrs["dispatch"], "round": attrs.get("round"),
                          "span": (a, b), "runs": runs[at:at + attrs["programs"]]})
            at += attrs["programs"]
        bounds = offset_interval(loaded, pairs)
        if bounds is not None and bounds[0] <= bounds[1]:
            fits.append((pairs, bounds, at))
    if len(fits) != 1:
        near = len(_between(starts, spans[0][1] - ps.CLOCK_SLACK_NS,
                            max(s[2] for s in spans) + ps.CLOCK_SLACK_NS))
        return None, None, (f"{len(spans)} ds/serving/dispatch spans say {said} programs, the "
                            f"device's XLA Modules line ran {near} around them, and "
                            f"{'no' if not fits else 'more than one'} stretch of {said} runs "
                            "keeps every run after its call and before its round's fetch "
                            "ends: the counts do not match (a program without a span?), no "
                            "pairing, nothing read")
    pairs, bounds, at = fits[0]
    # a run of nobody's behind the last dispatch's that may have started
    # before the last fetch ended
    fetch_end = max([s[2] for s in ps.named(loaded, ps.FETCH)] + [spans[-1][2]])
    extra = sum(a < fetch_end + bounds[1] for a, _ in runs[at:])
    if extra:
        return None, None, (f"{len(spans)} ds/serving/dispatch spans say {said} programs, the "
                            f"device's XLA Modules line ran {said + extra} before the last "
                            "fetch ended, for all the clock offset tells: the counts do not "
                            "match, no pairing, nothing read")
    return pairs, bounds, None


def offset_interval(loaded, pairs):
    """(lower, upper, how) bounds in ns of device clock minus host clock.
    From the program's spans: a dispatch's first run starts after its
    ``dispatch/forward`` span does, its second after ``dispatch/sample``
    does (after the dispatch span itself where a child is missing); a round's
    ``serving/fetch`` ends after the round's last run. From the runtime's
    events, where the window holds exactly one per run: run k starts after
    the k-th ``DoEnqueueProgram`` and ends before the k-th ``Execute=>Done``."""
    children = {name: {s[3].get("dispatch"): s[1] for s in ps.named(loaded, name)}
                for name in (FORWARD, SAMPLE)}
    upper, last_run = [], {}
    for p in pairs:
        calls = [children[FORWARD].get(p["dispatch"], p["span"][0]),
                 children[SAMPLE].get(p["dispatch"], p["span"][0])]
        for (run_start, _), call in zip(p["runs"], calls):
            upper.append(run_start - call)
        if p["runs"]:
            last_run[p["round"]] = p["runs"][-1][1]
    lower = [last_run[r] - max(s[2] for s in spans)
             for r, spans in ps.by_round(loaded, ps.FETCH).items() if r in last_run]
    how = f"{len(upper)} runs after their calls, {len(lower)} fetches after their rounds' last"
    runs = [run for p in pairs for run in p["runs"]]
    host_lo, host_hi = pairs[0]["span"][0], max(
        [s[2] for s in ps.named(loaded, ps.FETCH)] + [pairs[-1]["span"][1]])
    enqueue = _between(loaded.get("enqueue", []), host_lo, host_hi)
    done = _between(loaded.get("done", []), host_lo, host_hi + ps.CLOCK_SLACK_NS)
    if len(enqueue) == len(runs):
        upper += [run[0] - t for run, t in zip(runs, enqueue)]
        how += f", {len(enqueue)} {ps.RUNTIME_ENQUEUE}"
    if len(done) == len(runs):
        lower += [run[1] - t for run, t in zip(runs, done)]
        how += f", {len(done)} {ps.RUNTIME_DONE}"
    if not upper or not lower:
        return None
    return max(lower), min(upper), how


def _serving(idle):
    return {k: v for k, v in idle.items() if k.startswith(SERVING)}


def analyse(loaded):
    """Everything the four metrics read, computed once a run: {"note": [...],
    "pairs", "offset": (lower, upper), "rounds": [{"round", "start", "end",
    "first_run", "last_run", "fetch_end"}]: the window's rounds that have
    their dispatches paired and their fetch, host times already on the
    device's clock at the interval's centre; "idle_by_span":
    ``program_spans.idle_by_span`` there, over the stretch from the first of
    these rounds' start to the last one's end and no further (a round cut by
    the window's edge adds no idle time as it adds no round);
    "idle_inside_s": its entries under ``ds/serving/`` (every such span lies
    inside a ``serving/round``, whose own self time is one of them);
    "idle_outside_s": the rest of the stretch's idle time, between the
    rounds; "idle_edges_s": the window's idle time before and behind the
    stretch; "idle_by_runs_s": the rounds' lengths less their own runs of
    ``XLA Modules``, which reads neither the operations nor the spans inside
    a round, and "round_idle_median_ms", the median round's; "moved_s": how
    far the interval's two ends move any one span's share}; or {"note":
    [why]} alone."""
    pairs, bounds, why = pair(loaded)
    if pairs is None:
        return {"note": [why]}
    lower, upper, how = bounds
    shift = (lower + upper) / 2.0
    old = loaded.get("offset")
    note = [f"{len(pairs)} dispatches paired with {sum(len(p['runs']) for p in pairs)} runs of "
            f"XLA Modules by `programs` (the counts agree); device clock - host clock in "
            f"[{lower / 1e3:.1f}, {upper / 1e3:.1f}] us, width {(upper - lower) / 1e3:.1f} us "
            f"({how})" + (f"; program_spans.offset on the same trace: width "
                          f"{old[1] / 1e3:.1f} us" if old else
                          "; program_spans.offset on the same trace: none")]
    by_round = {}
    for p in pairs:
        by_round.setdefault(p["round"], []).append(p)
    fetch = ps.by_round(loaded, ps.FETCH)
    rounds, spans, by_runs = [], [], []
    for span in ps.named(loaded, ps.ROUND):
        _, a, b, attrs = span
        mine = by_round.get(attrs.get("round"))
        ends = [s[2] for s in fetch.get(attrs.get("round"), [])]
        if mine and ends:
            spans.append(span)
            rounds.append({"round": attrs["round"], "start": a + shift, "end": b + shift,
                           "first_run": mine[0]["runs"][0][0],
                           "last_run": mine[-1]["runs"][-1][1],
                           "fetch_end": max(ends) + shift})
            by_runs.append((b - a) - sum(max(0, min(y, b + shift) - max(x, a + shift))
                                         for p in mine for x, y in p["runs"]))
    found = {"note": note, "pairs": pairs, "offset": (lower, upper), "rounds": rounds}
    if rounds:
        stretch = dict(loaded, window=(spans[0][1], spans[-1][2]))
        idle = ps.idle_by_span(stretch, shift, 0)
        inside = sum(_serving(idle).values())
        ends = [_serving(ps.idle_by_span(stretch, at, 0)) for at in (lower, upper)]
        found.update(
            idle_by_span=idle, idle_inside_s=inside,
            idle_outside_s=sum(idle.values()) - inside,
            idle_edges_s=sum(ps.idle_by_span(loaded, shift, 0).values()) - sum(idle.values()),
            idle_by_runs_s=sum(by_runs) / 1e9, round_idle_median_ms=ps.median_ms(by_runs),
            moved_s=max(abs(end.get(k, 0.0) - v) for end in ends
                        for k, v in _serving(idle).items()))
    return found


def host_exposed_ms(loaded, found, note):
    rounds = found["rounds"]
    if not rounds:
        return None
    n = len(rounds)
    split = _serving(found["idle_by_span"])
    inside, outside = found["idle_inside_s"], found["idle_outside_s"]
    own = split.get(ps.ROUND, 0.0) + split.get(ps.DISPATCH, 0.0)
    note(f"{n} rounds, {inside:.4f} s of idle device inside them and {outside:.4f} s between "
         f"them, outside every round (the driver's, between step() calls: "
         f"{1e3 * outside / n:.3f} ms a round); with it {1e3 * (inside + outside) / n:.3f} ms "
         f"a round; {found['idle_edges_s']:.4f} s more at the window's edges, before the "
         f"first and behind the last of these rounds; by the innermost span, ms a round: "
         + ", ".join(f"{k[len(ps.PREFIX):]} {1e3 * v / n:.3f}" for k, v in split.items())
         + f"; serving/round's and serving/dispatch's own self time together "
         f"{100.0 * own / inside if inside else 0.0:.1f} % of it; the clock offset's "
         f"interval moves no span's share by more than {1e3 * found['moved_s'] / n:.3f} ms a "
         f"round; the rounds' lengths less their own runs of XLA Modules (no operation, no "
         f"span inside a round read): {found['idle_by_runs_s']:.4f} s (the median round's "
         f"{found['round_idle_median_ms']:.3f} ms), "
         f"{inside - found['idle_by_runs_s']:+.4f} s from it (idle time between the "
         f"operations of a run, and what the offset moves across the rounds' edges)")
    return 1e3 * inside / n


def _spread(values_ns, found):
    """The quartiles of a per-round time in ms, and how far the clock
    offset's interval lets all of them move together."""
    q = statistics.quantiles(values_ns, n=4) if len(values_ns) > 1 else values_ns * 3
    lower, upper = found["offset"]
    return (f"quartiles {q[0] / 1e6:.3f}, {q[1] / 1e6:.3f}, {q[2] / 1e6:.3f} ms, smallest "
            f"{min(values_ns) / 1e6:.3f}, +- {(upper - lower) / 2e3:.1f} us by the clock "
            f"offset's interval")


def host_prelaunch_ms(loaded, found, note):
    waits = [r["first_run"] - r["start"] for r in found["rounds"]]
    if not waits:
        return None
    note(f"{len(waits)} rounds, serving/round's start to the first run of its first "
         f"dispatch: {_spread(waits, found)}")
    return ps.median_ms(waits)


def fetch_tail_ms(loaded, found, note):
    tails = [r["fetch_end"] - r["last_run"] for r in found["rounds"]]
    if not tails:
        return None
    note(f"{len(tails)} rounds, the end of the round's last run to the end of "
         f"serving/fetch: {_spread(tails, found)}")
    return ps.median_ms(tails)


def dispatch_host_ms(loaded, found, note):
    spans = [s for s in ps.named(loaded, ps.DISPATCH) if "dispatch" in s[3]]
    if not spans:
        note("the ds/serving/dispatch spans carry no `dispatch` (a program from before "
             "PR 39): nothing read")
        return None
    parts = {name: {s[3].get("dispatch"): s[2] - s[1] for s in ps.named(loaded, name)}
             for name in (H2D, FORWARD, SAMPLE, POST_FORWARD)}
    shapes = {s[3].get("dispatch"): (s[3].get("seq_bucket"), s[3].get("chunk_bucket"))
              for s in ps.named(loaded, ps.BUILD)}
    whole, own, by_shape = [], [], {}
    for _, a, b, attrs in spans:
        n = attrs["dispatch"]
        whole.append(b - a)
        own.append(b - a - sum(parts[name].get(n, 0) for name in (H2D, FORWARD, SAMPLE)))
        by_shape.setdefault(shapes.get(n), []).append(b - a)
    medians = ", ".join(f"{name[len(ps.DISPATCH):]} {ps.median_ms(list(parts[name].values())):.3f}"
                        for name in (H2D, FORWARD, SAMPLE) if parts[name])
    classes = ", ".join(f"[{k[0]}, {k[1]}] {ps.median_ms(v):.3f} ({len(v)})" if k else
                        f"no build span {ps.median_ms(v):.3f} ({len(v)})"
                        for k, v in sorted(by_shape.items(), key=lambda kv: -len(kv[1])))
    post = parts[POST_FORWARD]
    copies = [(s[2] - s[1], s[3]["arrays"], s[3]["bytes"]) for s in ps.named(loaded, H2D)
              if s[3].get("arrays") and "bytes" in s[3]]
    note(f"{len(whole)} dispatches, {sum(s[3].get('first_seen', 0) for s in spans)} first of "
         f"their shape; medians in ms: {medians}, self {ps.median_ms(own):.3f}"
         + (f"; /h2d copies {statistics.median(c[1] for c in copies):g} host arrays of "
            f"{statistics.median(c[2] for c in copies):g} bytes together a dispatch, "
            f"{1e3 * ps.median_ms([c[0] / c[1] for c in copies]):.0f} us an array (medians)"
            if copies else "")
         + (f"; serving/post_forward behind it {ps.median_ms(list(post.values())):.3f}"
            if post else "")
         + f"; by [sequence bucket, chunk bucket]: {classes}")
    return ps.median_ms(whole)


WHAT = {f.__name__: f for f in (host_exposed_ms, host_prelaunch_ms, fetch_tail_ms,
                                dispatch_host_ms)}
#: read from the spans alone: no pairing with the device's runs, no offset
SPANS_ALONE = ("dispatch_host_ms",)


def read(ctx, params):
    loaded = ps.for_run(ctx)
    if loaded is None:
        return None
    what = params["what"]
    note = lambda text: ctx["notes"].append(f"host_exposed {what}: {text}")
    if "host_exposed" not in ctx:
        ctx["host_exposed"] = analyse(loaded)
        ctx["notes"] += [f"host_exposed: {text}" for text in ctx["host_exposed"]["note"]]
    found = ctx["host_exposed"]
    if "pairs" not in found and what not in SPANS_ALONE:
        return None
    return WHAT[what](loaded, found, note)
