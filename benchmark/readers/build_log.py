"""What set-up built, from the program's own build ledger
(``deepspeed_tpu.telemetry.build_log()``: one record a program jax traced,
lowered, compiled or loaded, with the span it was built under). It runs in
the program's process after the window; set-up is every record that began
before the ``window`` span of ``ctx["spans"]``.

A metric's value is over the records under the engine's own spans
(``serving/dispatch/forward`` and ``/sample``, ``serving/prepare_params``;
``fwd``, ``bwd``, ``step``); the builds under no span (the harness's weights,
the pools' zeros) are summed beside it in the note, so that the two together
are all that set-up built. ``params["what"]``:

``build_host_s``           trace + lower seconds: Python tracing and the
                           lowering to MLIR (every Pallas kernel's Mosaic
                           lowering), which no cache saves
``build_backend_s``        the backend's compile, or the load from the
                           persistent cache (key, read, deserialize)
``programs_cache_missed``  programs the cache was asked for, did not hold and
                           was given (jax's own ``cache_misses``): 0 in a warm
                           run. A program that compiles in under jax's floor
                           of a second is never kept and misses every run:
                           the note counts those apart

None where the program has no ledger (a parent before it) or no window span.
"""

from collections import defaultdict

def host_s(rec):
    return rec["trace_s"] + rec["lower_s"]


def backend_s(rec):
    return rec["compile_s"] + rec["load_s"]


def missed(rec):
    return int(rec["cache"] == "miss" and rec["stored"])


WHAT = {"build_host_s": host_s, "build_backend_s": backend_s,
        "programs_cache_missed": missed}


def split(records, spans):
    """(set-up, window, later) by when each record began against the
    ``window`` span; None without one."""
    window = [s for s in spans if s[0] == "window"]
    if not window:
        return None
    _, start, end, _ = window[0]
    return ([r for r in records if r["t"] < start],
            [r for r in records if start <= r["t"] <= end],
            [r for r in records if r["t"] > end])


def shape(rec):
    tags = rec["tags"]
    if "seq_bucket" not in tags:
        return "-"
    k = f" k={tags['verify_k']}" if tags.get("verify_k") else ""
    return f"[{tags['seq_bucket']}, {tags['chunk_bucket']}]{k}"


def _sums(records):
    return (f"{len(records)} programs, host {sum(map(host_s, records)):.3f} s "
            f"(trace {sum(r['trace_s'] for r in records):.3f} + lower "
            f"{sum(r['lower_s'] for r in records):.3f}), backend "
            f"{sum(map(backend_s, records)):.3f} s (compile "
            f"{sum(r['compile_s'] for r in records):.3f} + load "
            f"{sum(r['load_s'] for r in records):.3f}), missed "
            f"{sum(map(missed, records))}, too quick to keep "
            f"{sum(r['cache'] == 'miss' and not r['stored'] for r in records)}")


def _line(rec):
    back = f"load {rec['load_s']:.3f}" if rec["cache"] == "hit" else f"compile {rec['compile_s']:.3f}"
    kept = "" if rec["cache"] != "miss" or rec["stored"] else " (not kept)"
    return (f"  {rec['under']} {shape(rec)} {rec['program']}: trace {rec['trace_s']:.3f} "
            f"lower {rec['lower_s']:.3f} {back} {rec['cache']}{kept}")


def describe(setup, window, evicted, totals, cache):
    """The note's lines: one a program built under the engine's spans, the
    sums by span, the builds under no span by program, the cache directory,
    and anything built inside the window by name."""
    mine = [r for r in setup if r["under"]]
    loose = [r for r in setup if not r["under"]]
    lines = [f"build_log: set-up built {len(setup)} programs; under the engine's spans "
             + _sums(mine)]
    by_span = defaultdict(list)
    for r in mine:
        by_span[r["under"]].append(r)
    lines += [f"  under {name}: {_sums(recs)}" for name, recs in sorted(by_span.items())]
    lines += [_line(r) for r in mine]
    lines.append("build_log: under no span (the harness's weights, the pools) " + _sums(loose))
    by_program = defaultdict(list)
    for r in loose:
        by_program[r["program"]].append(r)
    top = sorted(by_program.items(), key=lambda kv: -sum(map(host_s, kv[1]))
                 - sum(map(backend_s, kv[1])))[:8]
    lines += [f"  {name} x{len(recs)}: host {sum(map(host_s, recs)):.3f} s, backend "
              f"{sum(map(backend_s, recs)):.3f} s, missed {sum(map(missed, recs))}"
              for name, recs in top]
    if evicted:
        lines.append(f"build_log: {evicted} records had left the ring before this reading: "
                     f"the sums above are of what stayed")
    lines.append(f"build_log: cache directory {cache[0]} entries, {cache[1]} bytes; the "
                 f"ledger's own handlers {1e3 * totals.get('listener_s', 0.0):.2f} ms over "
                 f"{totals.get('listener_calls', 0)} calls in the whole process")
    if window:
        lines.append(f"build_log: {len(window)} programs built INSIDE the window: " + "; ".join(
            f"{r['program']} under {r['under']} {shape(r)} {1e3 * (host_s(r) + backend_s(r)):.1f} ms"
            for r in window))
    else:
        lines.append("build_log: nothing was built inside the window")
    return lines


def read(ctx, params):
    try:
        from deepspeed_tpu import telemetry
        records, count = telemetry.build_log(), telemetry.build_count()
        totals = telemetry.buildlog.totals()
    except (ImportError, AttributeError):
        return None             # a program without the ledger
    parts = split(records, ctx["spans"])
    if parts is None or not records:
        return None
    setup, window, _ = parts
    if "build_log" not in ctx:          # the note once, whatever the metrics asked
        from deepspeed_tpu.utils import compile_cache
        ctx["build_log"] = True
        ctx["notes"] += describe(setup, window, count - len(records), totals,
                                 compile_cache.entries())
    mine = [r for r in setup if r["under"]]
    value = sum(map(WHAT[params["what"]], mine))
    return float(value) if params["what"] != "programs_cache_missed" else int(value)
