"""One run of one cell: ``python -m benchmark.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

Set-up (weights from the seed, the program built, every shape warmed), the
measured window, the peak memory, then the comparison with the plain
reference once the program's state is freed. The last line of standard output
is the result; the lines before it say where set-up went and what was
compared. No TPU, or a device kind without peaks, ends the run non-zero with
no result line.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, peaks, trace as trace_mod  # noqa: E402


def run_cell(cell, seed, seconds, trace, devices, device_info, t_start, out_dir):
    """Everything after the look for a chip. Returns the result object."""
    import jax

    rec = harness.Recorder(annotate=bool(trace))
    if trace:
        seconds = min(seconds, cell.traffic.get("trace_seconds", seconds))
    driver = harness.load("drivers", cell.config["driver"]).Driver(
        cell, seed, rec, devices=devices, seconds=seconds)
    setup_s = time.perf_counter() - t_start
    setup_parts = {}
    for name, a, b, _ in rec.spans:
        if name.startswith("setup/"):
            setup_parts[name[6:]] = setup_parts.get(name[6:], 0.0) + (b - a)
    setup_parts["other"] = setup_s - sum(setup_parts.values())
    print(json.dumps({"setup_breakdown_s": setup_parts}), flush=True)

    n_setup_spans = len(rec.spans)
    compiles = []                     # nothing may compile inside the window

    def count_compiles(name, secs, **kw):
        if name.endswith(("backend_compile_duration", "cache_retrieval_time_sec")):
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(count_compiles)
    if trace:
        trace_dir = os.path.join(out_dir, "trace")
        jax.profiler.start_trace(trace_dir)
    try:
        with rec.span("window"):
            facts = driver.window(seconds, out_dir)
    finally:
        if trace:
            jax.profiler.stop_trace()
    rec.spans = rec.spans[n_setup_spans:]
    longest = sorted(((b - a, name, attrs) for name, a, b, attrs in rec.spans
                      if name != "window"), key=lambda x: -x[0])[:3]
    print(json.dumps({"window_notes": {
        "programs_compiled_or_loaded_in_window": len(compiles),
        "longest_spans_s": [[round(d, 4), n, a] for d, n, a in longest],
        **{k: facts[k] for k in ("generator_late_ms", "window_s", "steps", "finished")
           if k in facts}}}), flush=True)
    device = dict(device_info, memory_peak_bytes=harness.memory_peak_bytes(devices))
    driver.release()

    t0 = time.perf_counter()
    checks = driver.compare()
    correct = bool(checks) and all(v == v and v <= lim for _, v, lim in checks)
    report = [f"compared {name}: {value:.6g} (limit {lim:.6g})"
              f"{'' if value <= lim else '  <-- NOT CORRECT'}" for name, value, lim in checks]
    report.append(f"reference took {time.perf_counter() - t0:.1f} s; correct={correct}")
    for line in report:
        print(line, flush=True)

    result = {"correct": correct, "attempted": int(facts["attempted"]),
              "failed": int(facts["failed"]), "metrics": {}, "device": device}
    if "tail" in facts:               # a serving window: what its tail stood on (benchmark/tails.py)
        result["window"] = facts["tail"]
    if not trace:
        facts = dict(facts, setup_s=setup_s)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": facts[m["name"]], "unit": m["unit"]}
    else:
        loaded = trace_mod.load(trace_mod.find_xplane(trace_dir))
        summary = trace_mod.summarize(loaded)
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
        ctx = {"cell": cell, "facts": facts, "spans": rec.spans, "trace": loaded,
               "summary": summary, "peaks": peaks.peaks_for(device_info["kind"]),
               "chips": len(devices), "notes": []}
        for m in cell.per_layer:
            with open(os.path.join(cell.metrics_dir, m["name"] + ".json")) as f:
                spec = json.load(f)
            value = harness.load("readers", spec["reader"]).read(ctx, spec.get("params", {}))
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        for note in ctx["notes"]:
            print(note, flush=True)
    # every number compared beside its limit: last in the line, last on standard error
    result["compared"] = {name: {"value": value, "limit": lim} for name, value, lim in checks}
    for line in report:
        print(line, file=sys.stderr, flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.Cell(args.workload)
    from deepspeed_tpu.utils import compile_cache
    compile_cache.enable()
    devices = harness.require_chips(cell.chips)
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    peaks.peaks_for(info["kind"])
    result = run_cell(cell, args.seed, args.seconds, args.trace, devices, info,
                      T_START, harness.out_dir(cell, args.seed, args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
