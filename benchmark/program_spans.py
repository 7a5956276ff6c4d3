"""The program's own spans (``ds/...``) read from a run's profiler trace.

``deepspeed_tpu.telemetry.span`` opens a ``jax.profiler.TraceAnnotation``
named ``ds/<name>`` with its attributes, so a traced run's ``.xplane.pb``
holds them on the host plane beside the harness's ``bench/`` spans, with the
attributes as the event's ``stats``, and the device's ``XLA Ops`` line in the
same file. Host and device lines run on clocks a millisecond or a few apart;
the serving rounds themselves bound the difference from both sides
(``offset``): a round's first device operation cannot start before
``serving/dispatch`` starts, and ``serving/fetch`` cannot end before its last
operation ends. Where the trace also holds the TPU runtime's own host events
(``RUNTIME_ENQUEUE``, ``RUNTIME_DONE``), the same argument on them narrows the
interval: a program cannot start before the runtime enqueues it, nor its
completion be handled before it ends.

A program without these spans (an older commit) gives a trace without
``ds/`` events: every function here then returns nothing, and the readers a
note.
"""

import bisect
import glob
import os
import statistics
import time

from benchmark import harness, trace

PREFIX = "ds/"
ROUND, COMPOSE, BUILD, DISPATCH, FETCH, RETIRE = (
    PREFIX + "serving/" + n for n in
    ("round", "compose", "build", "dispatch", "fetch", "retire"))
ADMIT = PREFIX + "serving/admit"
#: how far apart the host's and the device's clocks are taken to be at most
CLOCK_SLACK_NS = 20_000_000
#: host events of the TPU runtime (libtpu's names): the enqueueing of a
#: program, which its first operation cannot precede, and the handling of a
#: program's completion, which cannot precede its last
RUNTIME_ENQUEUE = "DoEnqueueProgram"
RUNTIME_DONE = "tpu::System::Execute=>Done"
#: zero-length request events: never what the host "was doing" in a gap
MARKS = tuple(PREFIX + "serving/" + n for n in ("admit", "first_token", "finish"))


def process_start_time():
    """Wall-clock time this process started, from /proc (Linux)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/stat") as f:
        boot = next(int(line.split()[1]) for line in f if line.startswith("btime"))
    return boot + ticks / os.sysconf("SC_CLK_TCK")


def find_trace(cell_name, since=None, root=None):
    """The newest ``*.xplane.pb`` under the cell's traced runs' output,
    written after ``since`` (default: the start of this process); None if
    there is none. The run's ``ctx`` holds no path to its trace."""
    since = process_start_time() - 1.0 if since is None else since
    pattern = os.path.join(root or harness.ROOT, ".bench_out", cell_name, "seed*_trace1",
                           "trace", "plugins", "profile", "*", "*.xplane.pb")
    paths = [p for p in glob.glob(pattern) if os.path.getmtime(p) >= since]
    return max(paths, key=os.path.getmtime) if paths else None


def load(path):
    """{"spans": [(name, start_ns, end_ns, attrs)] sorted by start, "ops" and
    "modules": [(start_ns, end_ns)] of the first device's operations and
    program runs, "enqueue" and "done": start times of the runtime's host
    events of those names, "window": (lo, hi) of ``bench/window`` or of
    everything}. Spans are ``ds/`` events of every host thread."""
    from jax.profiler import ProfileData
    spans, device, window = [], None, None
    runtime = {RUNTIME_ENQUEUE: [], RUNTIME_DONE: []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:") and device is None:
            device = {line.name: sorted((e.start_ns, e.start_ns + e.duration_ns)
                                        for e in line.events)
                      for line in plane.lines if line.name in ("XLA Ops", "XLA Modules")}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                      dict(e.stats)))
                    elif e.name in runtime:
                        runtime[e.name].append(e.start_ns)
                    elif e.name == trace.WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    spans.sort(key=lambda s: (s[1], -s[2]))
    if window is None and spans:
        window = (spans[0][1], max(s[2] for s in spans))
    device = device or {}
    return {"spans": spans, "ops": device.get("XLA Ops", []),
            "modules": device.get("XLA Modules", []), "window": window,
            "enqueue": sorted(runtime[RUNTIME_ENQUEUE]), "done": sorted(runtime[RUNTIME_DONE])}


def named(loaded, name):
    """The spans of one name that start inside the window."""
    lo, hi = loaded["window"]
    return [s for s in loaded["spans"] if s[0] == name and lo <= s[1] <= hi]


def by_round(loaded, name):
    """{round: [spans of that name]} for spans that carry ``round``."""
    out = {}
    for s in named(loaded, name):
        if "round" in s[3]:
            out.setdefault(s[3]["round"], []).append(s)
    return out


def bursts(ops, n):
    """The device's operations cut into ``n`` bursts at their ``n - 1``
    longest idle gaps: [(start_ns, end_ns)]. The scheduler is synchronous,
    so the device idles between rounds; inside a round it idles as long only
    where the round's programs are so short that the host cannot keep up."""
    merged = trace.union(ops)
    if n < 1 or len(merged) < n:
        return []
    gaps = sorted(range(1, len(merged)), key=lambda i: merged[i][0] - merged[i - 1][1])
    cuts = sorted(gaps[len(gaps) - (n - 1):]) if n > 1 else []
    out, first = [], 0
    for i in cuts + [len(merged)]:
        out.append((merged[first][0], merged[i - 1][1]))
        first = i
    return out


def round_table(loaded):
    """One row per serving round that has a dispatch and a fetch inside the
    window, in order, with its device burst: {"round", "dispatch_start",
    "fetch_end", "dev_start", "dev_end", "enqueue_start", "done_start"}; []
    when rounds and bursts do not pair up. Every round runs the same number
    of programs, so where the device's ``XLA Modules`` line is there the
    k-th round's burst is its k-th group of program runs; else the
    operations are cut at their longest gaps. The runtime's two events are
    None where the trace lacks them."""
    dispatch, fetch = by_round(loaded, DISPATCH), by_round(loaded, FETCH)
    rounds = sorted(r for r in dispatch if r in fetch)
    if not rounds:
        return []
    # the device's work of these rounds: the clocks are a few ms apart, and
    # any other work of the window (there is none in a serving cell) further
    lo = dispatch[rounds[0]][0][1] - CLOCK_SLACK_NS
    hi = max(s[2] for s in fetch[rounds[-1]]) + CLOCK_SLACK_NS
    inside = lambda events: [(a, b) for a, b in events if b > lo and a < hi]
    modules, n = inside(loaded.get("modules", [])), len(rounds)
    per_round = len(modules) // n
    if modules and len(modules) == per_round * n:
        cut = [(modules[i][0], modules[i + per_round - 1][1])
               for i in range(0, len(modules), per_round)]
    else:
        per_round = None
        cut = bursts(inside(loaded["ops"]), n)
    if len(cut) != n:
        return []
    table = []
    for r, (a, b) in zip(rounds, cut):
        start, end = dispatch[r][0][1], max(s[2] for s in fetch[r])
        between = lambda times: times[bisect.bisect_left(times, start):
                                      bisect.bisect_right(times, end)]
        enqueue, done = between(loaded.get("enqueue", [])), between(loaded.get("done", []))
        table.append({"round": r, "dispatch_start": start, "fetch_end": end,
                      "dev_start": a, "dev_end": b,
                      "enqueue_start": enqueue[0] if enqueue else None,
                      # the LAST program's completion: only where every one is there
                      "done_start": done[-1] if done and len(done) == per_round else None})
    return table


def _interval(table, before, after):
    """(lower, upper) bounds in ns of device clock minus host clock: the
    host's ``before`` precedes the burst's start, its ``after`` follows the
    burst's end; None where a round lacks either."""
    if not table or any(r[before] is None or r[after] is None for r in table):
        return None
    return (max(r["dev_end"] - r[after] for r in table),
            min(r["dev_start"] - r[before] for r in table))


def offset(table):
    """(centre_ns, width_ns) of the interval that device clock minus host
    clock must lie in, from every round of ``table``: the program's spans
    bound it, and the runtime's events inside them narrow it where the
    trace holds them. None when there is no round or the bounds cross (the
    rounds were paired with the wrong bursts)."""
    bounds = [iv for iv in (_interval(table, "dispatch_start", "fetch_end"),
                            _interval(table, "enqueue_start", "done_start")) if iv]
    if not bounds:
        return None
    lower, upper = max(iv[0] for iv in bounds), min(iv[1] for iv in bounds)
    if lower > upper:
        return None
    return (lower + upper) / 2.0, upper - lower


def innermost_segments(spans):
    """The host's timeline cut by the innermost open span: sorted
    [(start_ns, end_ns, name)]. Spans of one thread nest; marks are left
    out."""
    edges = []
    for name, a, b, _ in spans:
        if name not in MARKS and b > a:
            edges += [(a, 1, -b, name), (b, 0, -a, name)]
    edges.sort()
    out, stack, at = [], [], None
    for t, opening, _, name in edges:
        if stack and t > at:
            out.append((at, t, stack[-1]))
        if opening:
            stack.append(name)
        else:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
        at = t
    return out


def idle_by_span(loaded, shift_ns, min_gap_ns):
    """Seconds of device idle time inside the window, by the innermost
    ``ds/`` span open on the host (shifted onto the device's clock) at the
    time; gaps shorter than ``min_gap_ns`` are not attributed."""
    lo, hi = loaded["window"]
    lo, hi = lo + shift_ns, hi + shift_ns
    busy = trace.union([(max(a, lo), min(b, hi)) for a, b in loaded["ops"]
                        if b > lo and a < hi])
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b - a >= min_gap_ns]
    segments = [(a + shift_ns, b + shift_ns, n)
                for a, b, n in innermost_segments(loaded["spans"])]
    ends = [seg[1] for seg in segments]
    out = {}
    for a, b in gaps:
        covered = 0
        for i in range(bisect.bisect_right(ends, a), len(segments)):
            x, y, name = segments[i]
            if x >= b:
                break
            part = min(b, y) - max(a, x)
            out[name] = out.get(name, 0) + part
            covered += part
        out["outside ds/ spans"] = out.get("outside ds/ spans", 0) + (b - a) - covered
    return {k: v / 1e9 for k, v in sorted(out.items(), key=lambda kv: -kv[1]) if v > 0}


def host_gaps(table, loaded, shift_ns):
    """For each pair of consecutive rounds, the device's idle time between
    the last operation of one and the first of the next that falls inside
    the program's ``serving/round`` spans (ns); the time between rounds,
    when the harness or nobody holds the host, is not the program's."""
    rounds = [(a + shift_ns, b + shift_ns) for _, a, b, _ in named(loaded, ROUND)]
    out = []
    for prev, nxt in zip(table, table[1:]):
        a, b = prev["dev_end"], nxt["dev_start"]
        out.append(sum(max(0, min(b, y) - max(a, x)) for x, y in rounds))
    return out


def median_ms(values_ns):
    return statistics.median(values_ns) / 1e6 if values_ns else None


def for_run(ctx):
    """The loaded trace of the run ``ctx`` belongs to, found once and kept
    in ``ctx``; None (with a note) when the file or its ``ds/`` spans are
    missing. ``ctx["trace_path"]``, where a caller gives it, wins."""
    if "program_spans" not in ctx:
        path = ctx.get("trace_path") or find_trace(ctx["cell"].name)
        loaded = None
        if path is None:
            ctx["notes"].append("program_spans: no trace file of this run found, nothing read")
        else:
            t0 = time.perf_counter()
            loaded = load(path)
            if not loaded["spans"]:
                ctx["notes"].append("program_spans: the trace holds no ds/ span "
                                    "(a program from before they existed), nothing read")
                loaded = None
            else:
                table = round_table(loaded)
                loaded["table"], loaded["offset"] = table, offset(table)
                ctx["notes"].append(_offset_note(loaded, time.perf_counter() - t0))
        ctx["program_spans"] = loaded
    return ctx["program_spans"]


def _offset_note(loaded, took_s):
    n, table = len(loaded["spans"]), loaded["table"]
    if loaded["offset"] is None:
        why = "no serving round" if not table else "bounds cross"
        return (f"program_spans: {n} ds/ spans read in {took_s:.2f} s; "
                f"no clock offset ({why})")
    centre, width = loaded["offset"]
    own = _interval(table, "dispatch_start", "fetch_end")
    return (f"program_spans: {n} ds/ spans read in {took_s:.2f} s; device clock - host "
            f"clock in [{(centre - width / 2) / 1e3:.1f}, {(centre + width / 2) / 1e3:.1f}] us "
            f"from {len(table)} rounds: centre {centre / 1e3:.1f} us, width {width / 1e3:.1f} us "
            f"(the ds/ spans alone: [{own[0] / 1e3:.1f}, {own[1] / 1e3:.1f}] us, width "
            f"{(own[1] - own[0]) / 1e3:.1f} us; the rest from the runtime's "
            f"{RUNTIME_ENQUEUE} and {RUNTIME_DONE} events)")
