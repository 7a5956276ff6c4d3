"""Reduction of a profiler trace (``*.xplane.pb``) to numbers.

One reading of the file, with nothing but jax: device planes are named
``/device:TPU:<n>``; their line ``XLA Ops`` has one event per executed HLO
operation (the name is the operation's HLO text), start and duration in
nanoseconds. The host plane ``/host:CPU`` has one line per thread; the
``jax.profiler.TraceAnnotation`` spans the harness opened (names start with
``bench/``) are on the main thread's. The device's clock runs about a millisecond apart from the
host's (probe trace, PR 26), so gaps shorter than that are not attributed.
"""

import glob
import os
import re

SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
#: operations whose event spans the events of the operations inside them
CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path):
    """{"devices": {plane name: [(name, start_ns, end_ns)]}, "spans": [...]}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = sorted(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name == "/host:CPU":
            # a line is a thread, named after the command ("python", "python3")
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def window_of(trace):
    """(start_ns, end_ns): the ``bench/window`` span, else the extent of all
    the harness's spans."""
    for name, a, b in trace["spans"]:
        if name == WINDOW_SPAN:
            return a, b
    if not trace["spans"]:
        raise ValueError("trace holds no harness span")
    return (min(s[1] for s in trace["spans"]), max(s[2] for s in trace["spans"]))


def union(intervals):
    """Sorted, merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(events, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events if b > lo and a < hi]


def short_name(hlo_text):
    """``%fusion.5 = bf16[8,128]{...} fusion(...)`` -> ``fusion.5 bf16[8,128] fusion``."""
    m = re.match(r"%?(\S+) = \(?(\w+\[[\d,]*\])?[^ ]* ?.*?\b([a-z][\w-]*)\(", hlo_text)
    if not m:
        return hlo_text[:60]
    return " ".join(x for x in m.groups() if x)[:60]


def _innermost(spans, lo, hi):
    """Name of the harness span that covers most of [lo, hi]; the shortest
    such span wins a tie (the innermost)."""
    best, best_key = "unattributed", (0, 0)
    for name, a, b in spans:
        if name == WINDOW_SPAN:
            continue
        cover = min(b, hi) - max(a, lo)
        if cover > 0 and (cover, -(b - a)) > best_key:
            best, best_key = name, (cover, -(b - a))
    return best


def summarize(trace, min_gap_ns=1_000_000):
    """Busy and idle time of the window, the ten device operations that took
    most time, the idle gaps by what the host was doing in them."""
    lo, hi = window_of(trace)
    if not trace["devices"]:
        raise ValueError("trace holds no device plane")
    busy_ns, ops, gaps = [], {}, {}
    for events in trace["devices"].values():
        events = _clip(events, lo, hi)
        merged = union([(a, b) for _, a, b in events])
        busy_ns.append(sum(b - a for a, b in merged))
        for name, a, b in events:
            key = short_name(name)
            if key.rsplit(" ", 1)[-1] not in CONTAINERS:
                ops[key] = ops.get(key, 0) + (b - a)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b - a >= min_gap_ns:
                who = _innermost(trace["spans"], a, b)
                gaps[who] = gaps.get(who, 0) + (b - a)
    n = len(busy_ns)
    top = lambda d: [[k, v / n / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": (hi - lo) / 1e9, "busy_s": sum(busy_ns) / n / 1e9,
            "device_ops": top(ops), "idle_gaps": top(gaps)}


def kernel_events(trace, pattern, within=None):
    """[(start_ns, end_ns)] of device events whose HLO text matches
    ``pattern`` inside the window (first device; kernels run alike on all);
    ``within`` keeps only events that start inside one of those intervals."""
    lo, hi = window_of(trace)
    rx = re.compile(pattern)
    events = _clip(next(iter(trace["devices"].values())), lo, hi)
    out = [(a, b) for name, a, b in events if rx.search(name)]
    if within is not None:
        within = union(within)
        out = [(a, b) for a, b in out if any(x <= a < y for x, y in within)]
    return out


def spans_named(trace, name):
    return [(a, b) for n, a, b in trace["spans"] if n == name]
