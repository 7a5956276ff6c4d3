"""Per-layer metrics read from the program's own ``ds/`` spans in the run's
profiler trace (``benchmark/program_spans.py``).

params: ``what`` one of

- ``batch_occupancy`` (%): real tokens over padded slots of the window's
  ``serving/build`` spans;
- ``queue_wait_ms``: median ``waited_us`` of its ``serving/admit`` events;
- ``round_host_ms``: median over rounds of ``serving/round`` less the
  ``serving/fetch`` inside it, the host's own part of a round;
- ``host_gap_ms``: median over rounds of the device's idle time between one
  round's last operation and the next one's first, inside the program's
  rounds, with the clock offset applied;
- ``step_host_ms``: median per training step of ``fwd`` + ``bwd`` + ``step``.

Each says in a note what the number rests on. A trace without ``ds/`` spans
gives None and a note."""

import statistics

from benchmark import program_spans as ps


def _sum(spans, key):
    return sum(s[3].get(key, 0) for s in spans)


def batch_occupancy(loaded, note):
    builds = ps.named(loaded, ps.BUILD)
    if not builds or not _sum(builds, "padded_slots"):
        return None
    share = lambda spans: (100.0 * _sum(spans, "real_tokens") / _sum(spans, "padded_slots")
                           if spans else float("nan"))
    decode = [s for s in builds if s[3].get("real_tokens") == s[3].get("seqs")]
    prefill = [s for s in builds if s[3].get("real_tokens") != s[3].get("seqs")]
    note(f"{len(builds)} rounds, {_sum(builds, 'real_tokens')} real tokens in "
         f"{_sum(builds, 'padded_slots')} padded slots; rounds with prefill tokens "
         f"{share(prefill):.2f} % ({len(prefill)}), without {share(decode):.2f} % ({len(decode)})")
    return share(builds)


def queue_wait_ms(loaded, note):
    waits = [s[3]["waited_us"] / 1e3 for s in ps.named(loaded, ps.ADMIT) if "waited_us" in s[3]]
    if not waits:
        return None
    note(f"{len(waits)} admissions, largest {max(waits):.3f} ms")
    return statistics.median(waits)


def round_host_ms(loaded, note):
    fetch = ps.by_round(loaded, ps.FETCH)
    parts = {name: ps.by_round(loaded, name)
             for name in (ps.COMPOSE, ps.BUILD, ps.DISPATCH, ps.RETIRE)}
    own, split = [], {name: [] for name in parts}
    for _, a, b, attrs in ps.named(loaded, ps.ROUND):
        r = attrs.get("round")
        inside = [s for s in fetch.get(r, []) if s[1] >= a and s[2] <= b]
        if not inside:
            continue                  # a round that dispatched nothing
        own.append((b - a) - sum(s[2] - s[1] for s in inside))
        for name, table in parts.items():
            split[name].append(sum(s[2] - s[1] for s in table.get(r, [])
                                   if s[1] >= a and s[2] <= b))
    if not own:
        return None
    medians = {name.rsplit("/", 1)[1]: ps.median_ms(v) for name, v in split.items()}
    rest = ps.median_ms(own) - sum(medians.values())
    note(f"{len(own)} rounds; medians in ms: " +
         ", ".join(f"{k} {v:.3f}" for k, v in medians.items()) +
         f", rest {rest:.3f} (post-forward bookkeeping and the spans' own edges)")
    return ps.median_ms(own)


def host_gap_ms(loaded, note):
    if loaded.get("offset") is None or len(loaded["table"]) < 2:
        return None
    centre, width = loaded["offset"]
    gaps = ps.host_gaps(loaded["table"], loaded, centre)
    whole = [nxt["dev_start"] - prev["dev_end"]
             for prev, nxt in zip(loaded["table"], loaded["table"][1:])]
    idle = ps.idle_by_span(loaded, centre, max(width, 1.0))
    note(f"{len(gaps)} gaps between rounds, median with the time outside the program's "
         f"rounds {ps.median_ms(whole):.3f} ms; idle seconds of the window by span (gaps over "
         f"{width / 1e3:.1f} us): " + ", ".join(f"{k} {v:.4f}" for k, v in idle.items()))
    return ps.median_ms(gaps)


def step_host_ms(loaded, note):
    steps, inner = {}, {"fwd/shard_batch": [], "fwd/dispatch": []}
    for name, a, b, attrs in loaded["spans"]:
        short = name[len(ps.PREFIX):]
        if "step" not in attrs or not (loaded["window"][0] <= a <= loaded["window"][1]):
            continue
        if short in ("fwd", "bwd", "step"):
            steps.setdefault(attrs["step"], {})[short] = b - a
        elif short in inner:
            inner[short].append(b - a)
    whole = [sum(parts.values()) for parts in steps.values() if len(parts) == 3]
    if not whole:
        return None
    note(f"{len(whole)} steps; medians in ms: " +
         ", ".join(f"{k} {ps.median_ms(v):.3f}" for k, v in inner.items() if v))
    return ps.median_ms(whole)


WHAT = {f.__name__: f for f in (batch_occupancy, queue_wait_ms, round_host_ms, host_gap_ms,
                                step_host_ms)}


def read(ctx, params):
    loaded = ps.for_run(ctx)
    if loaded is None:
        return None
    what = params["what"]
    return WHAT[what](loaded, lambda text: ctx["notes"].append(f"ds_spans {what}: {text}"))
