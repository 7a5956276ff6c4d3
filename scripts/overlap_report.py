"""Overlap & critical-path report: exposed-comm attribution from a device
trace, or chip-free from the analytic cost model.

Two modes, one payload shape:

**Trace mode** (stdlib-only — works on any machine with the trace files)::

    python scripts/overlap_report.py --trace /tmp/ds_tpu_trace
    python scripts/overlap_report.py --trace trace.json.gz --summary BENCH_x.json

ingests the trace-event JSON a ``jax.profiler`` capture (or our own
``telemetry.export_chrome_trace``) produced, reconstructs per-device op
timelines and attributes every collective's exposed seconds. ``--summary``
joins a bench payload's embedded telemetry ``comm`` table so collectives
the trace couldn't size carry bytes/wire bytes.

**Analytic mode** (chip-free, ``JAX_PLATFORMS=cpu`` + 8 forced host
devices — the repo's AOT-without-a-TPU pattern)::

    python scripts/overlap_report.py --analytic [--device-kind tpu_v5e]

traces (never executes) a small ZeRO-shaped step — all_gather the sharded
weights, matmul, reduce_scatter the grads, all_reduce the grad norm — so
the traced collectives land in comm telemetry with exact bytes and axes,
reads the compiled program's XLA cost analysis, and builds the schedule
XLA's synchronous collectives imply from ``autotuning/kernel_tuner.py``'s
roofline + link cost models: compute first, every collective serialized
after it, fully exposed. That worst-case exposure is the baseline the
future overlap-scheduling pass (ROADMAP item 2) ratchets against.

Prints the human table to stderr and ONE JSON payload line to stdout
(bench payload convention)::

    {"metric": "overlap_exposed_comm_s", "value": <s>, "unit": "s",
     "extra": {"overlap": <report>, "telemetry": <summary when enabled>}}

``scripts/perf_gate.py --dry-run`` shape-validates this payload and gates
``exposed_comm_s`` growth. See docs/OBSERVABILITY.md "Overlap".
"""

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def _load_comm_stats(summary_path):
    """The ``comm.ops`` table from a bench payload / summary JSON doc (the
    wire-byte join for trace mode). Accepts a raw summary, a bench payload
    with ``extra.telemetry``, or anything ``perf_gate.find_summary`` digs
    the summary out of."""
    with open(summary_path) as f:
        doc = json.load(f)
    for probe in (doc, doc.get("extra", {}).get("telemetry"),
                  doc.get("telemetry")):
        if isinstance(probe, dict) and isinstance(probe.get("comm"), dict):
            return probe["comm"].get("ops", {})
    return {}


def run_trace(args):
    from deepspeed_tpu.telemetry import overlap
    events = overlap.load_trace_events(args.trace)
    per_device = overlap.intervals_from_trace(events)
    if not per_device:
        print(f"no device duration events in {args.trace}", file=sys.stderr)
        return None
    comm_stats = _load_comm_stats(args.summary) if args.summary else None
    return overlap.overlap_report(per_device, mode="trace",
                                  comm_stats=comm_stats, top_k=args.top_k)


def run_analytic(args):
    # force a CPU host mesh BEFORE jax import — trace + AOT only, never run
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.comm import comm
    from deepspeed_tpu.telemetry import overlap

    ndev = min(len(jax.devices()), 8)
    telemetry.configure(enabled=True)
    mesh = Mesh(np.array(jax.devices()[:ndev]), ("dp",))

    B, D, F = args.batch, args.hidden, args.ffn

    def zero_step(x, w_shard, g_full):
        # ZeRO shape: gather sharded weights, compute, scatter grads,
        # all-reduce the scalar grad norm — the collective mix a real
        # stage-3 micro step issues
        w = comm.all_gather(w_shard, axis_name="dp", axis=0)
        y = jnp.tanh(x @ w)
        g = comm.reduce_scatter(g_full, axis_name="dp", scatter_dim=0)
        gn = comm.all_reduce(jnp.sum(g * g) + jnp.sum(y) * 0.0,
                             axis_name="dp")
        return y, g, gn

    fn = jax.shard_map(zero_step, mesh=mesh,
                       in_specs=(P(), P("dp"), P()),
                       out_specs=(P(), P("dp"), P()), check_vma=False)
    x = jax.ShapeDtypeStruct((B, D), jnp.float32)
    w_shard = jax.ShapeDtypeStruct((D, F), jnp.float32)  # P("dp") shards dim 0
    g_full = jax.ShapeDtypeStruct((D, F), jnp.float32)

    lowered = jax.jit(fn).lower(x, w_shard, g_full)  # traced record_comm
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})

    comm_ops = []
    ops = telemetry.summary().get("comm", {}).get("ops", {})
    for op, per_axis in sorted(ops.items()):
        for axis, st in sorted(per_axis.items()):
            comm_ops.append({"op": op, "axis": axis, "bytes": st["bytes"],
                             "wire_bytes": st["wire_bytes"],
                             "count": st["count"]})
    report = overlap.analytic_report(
        dict(ca), comm_ops, device_kind=args.device_kind,
        axis_sizes={"dp": ndev}, top_k=args.top_k)

    if args.schedule:
        # scheduled analytic mode: run the overlap pass's two-resource
        # timeline over the SAME inventory; the serialized report's advice
        # seeds the planner when depth/buckets aren't pinned on the CLI
        from deepspeed_tpu.runtime.zero import overlap_schedule as osched
        specs = osched.fill_comm_seconds(comm_ops,
                                         device_kind=args.device_kind,
                                         axis_sizes={"dp": ndev})
        if args.prefetch_depth is None or args.grad_buckets is None:
            plan, _, _ = osched.best_plan(report["compute_s"], specs,
                                          hints=report.get("advice"),
                                          n_layers=args.layers)
            if args.prefetch_depth is not None:
                plan.prefetch_depth = args.prefetch_depth
            if args.grad_buckets is not None:
                plan.grad_buckets = args.grad_buckets
        else:
            plan = osched.OverlapPlan(prefetch_depth=args.prefetch_depth,
                                      grad_buckets=args.grad_buckets,
                                      n_layers=args.layers)
        report = osched.scheduled_report(dict(ca), comm_ops, plan,
                                         device_kind=args.device_kind,
                                         axis_sizes={"dp": ndev},
                                         top_k=args.top_k)
    telemetry.attach_overlap(report)
    return report


def emit_profile(report, args):
    """Fold the report's sized collectives into the persisted per-op profile
    store (telemetry/profile_store.py): per-call seconds = total_s / count,
    bucketed by per-call payload bytes. Returns a small provenance dict for
    the payload, or None when nothing was emitted."""
    from deepspeed_tpu.telemetry import profile_store

    entries = {}
    for c in report.get("collectives", []):
        count = max(int(c.get("count", 1) or 1), 1)
        total_s = float(c.get("total_s", 0.0) or 0.0)
        if total_s <= 0:
            continue
        per_call_s = total_s / count
        per_call_b = int(c.get("bytes", 0) or 0) // count
        key = profile_store.bucket_key(c["op"], per_call_b)
        prev = entries.get(key)
        if prev is not None and prev["count"] >= count:
            continue  # keep the better-sampled measurement per bucket
        entries[key] = profile_store.make_entry(
            per_call_s, per_call_b, args.profile_source, count=count,
            extra={"axis": c.get("axis")})
    if not entries:
        print("emit-profile: no sized collectives to record", file=sys.stderr)
        return None

    device = profile_store.default_device_kind()
    path = (args.emit_profile
            or os.environ.get("DS_TPU_PROFILE_STORE", "")
            or profile_store.store_path(device))
    mode = "--trace" if args.trace else "--analytic"
    doc = profile_store.merge_store(
        path, device, entries,
        generated_by=f"scripts/overlap_report.py {mode} --emit-profile")
    print(f"emit-profile: {len(entries)} entr"
          f"{'y' if len(entries) == 1 else 'ies'} -> {path} "
          f"(device {doc['device_kind']}, source {args.profile_source})",
          file=sys.stderr)
    return {"path": path, "device_kind": doc["device_kind"],
            "source": args.profile_source,
            "entries": len(doc["entries"]),
            "keys": sorted(entries)}


def main():
    ap = argparse.ArgumentParser(
        description="compute/comm overlap exposure report")
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--trace",
                     help="trace-event .json/.json.gz file or jax.profiler "
                          "output directory")
    src.add_argument("--analytic", action="store_true",
                     help="chip-free analytic schedule (CPU, AOT only)")
    ap.add_argument("--summary",
                    help="bench payload / summary JSON to join comm wire "
                         "bytes (trace mode)")
    ap.add_argument("--device-kind", default="tpu_v5e",
                    help="cost-model chip for --analytic")
    ap.add_argument("--top-k", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=1024)
    ap.add_argument("--ffn", type=int, default=1024)
    ap.add_argument("--schedule", action="store_true",
                    help="analytic mode: score the overlap pass's scheduled "
                         "timeline (runtime/zero/overlap_schedule.py) "
                         "instead of the serialized worst case; the payload "
                         "carries the serialized baseline in "
                         "extra.overlap.schedule")
    ap.add_argument("--prefetch-depth", type=int, default=None,
                    help="pin the schedule's prefetch depth (default: "
                         "planner sweep seeded by the advisor hints)")
    ap.add_argument("--grad-buckets", type=int, default=None,
                    help="pin the schedule's grad bucket count (default: "
                         "planner sweep)")
    ap.add_argument("--layers", type=int, default=8,
                    help="layer count the scheduled timeline pipelines over")
    ap.add_argument("--advise", action="store_true",
                    help="print the top-K actionable prefetch hints with "
                         "their potential_saving_s")
    ap.add_argument("--emit-profile", nargs="?", const="", default=None,
                    metavar="PATH",
                    help="merge the report's measured per-op seconds into "
                         "the profile store (telemetry/profile_store.py); "
                         "PATH overrides the default "
                         "onchip_results/profile_<device>.json "
                         "(DS_TPU_PROFILE_STORE / "
                         "DS_TPU_PROFILE_STORE_DEVICE honoured)")
    ap.add_argument("--profile-source", default="trace_cpu",
                    choices=["trace_cpu", "trace_tpu", "onchip", "manual"],
                    help="provenance tag for --emit-profile entries")
    args = ap.parse_args()

    if args.analytic:
        report = run_analytic(args)
    else:
        report = run_trace(args)
    if report is None:
        return 1

    from deepspeed_tpu.telemetry import overlap
    errs = overlap.validate_report(report)
    if errs:
        print("malformed report: " + "; ".join(errs), file=sys.stderr)
        return 1

    print(overlap.format_report(report, top_k=args.top_k), file=sys.stderr)
    if args.advise:
        hints = (report.get("advice") or [])[:args.top_k]
        print(f"advisor hints (top {len(hints)}):", file=sys.stderr)
        for h in hints:
            print(f"  {h['hint']}  "
                  f"potential_saving_s={h['potential_saving_s']}",
                  file=sys.stderr)
        if not hints:
            print("  (none — nothing exposed next to independent compute)",
                  file=sys.stderr)
    extra = {"overlap": report}
    if args.emit_profile is not None:
        emitted = emit_profile(report, args)
        if emitted is not None:
            extra["profile_store"] = emitted
    if args.analytic:
        from deepspeed_tpu import telemetry
        if telemetry.enabled():
            extra["telemetry"] = telemetry.summary()
    payload = {"metric": "overlap_exposed_comm_s",
               "value": report["exposed_comm_s"], "unit": "s",
               "extra": extra}
    print(json.dumps(payload))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
