"""Regenerate the persistent kernel tuning tables (docs/AUTOTUNING.md).

Chip-free (default — no TPU needed; compiles every candidate for the target
topology and ranks by the XLA cost-analysis roofline proxy):

    python scripts/tune_kernels.py --mode chip-free --topology v5e:2x2

On-chip (requires a live TPU; timed sweep, ground truth):

    python scripts/tune_kernels.py --mode on-chip

Both write the table to ``deepspeed_tpu/autotuning/tables/<device>.json``
(the file every dispatch reads — commit it) and the full per-candidate
ranking to ``onchip_results/kernel_tuning_<device>.json`` (the evidence —
commit that too, so a table change is always attributable to a sweep).
"""

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")  # chip-free host: libtpu
# must not probe the GCP metadata server (30 HTTP retries per var)


def keep_other_kernels(kernels, entries, report, generated_by, table_path,
                       ranking_path):
    """A sweep of some kernels replaces THEIR entries and rankings and keeps
    every other kernel's as it is, in its place (each entry and each sweep
    names its mode; the table's ``generated_by`` stays the full sweep's)."""
    from deepspeed_tpu.autotuning import kernel_table
    swept = lambda key: key.split("|", 1)[0] in kernels
    old = kernel_table.load_table(path=table_path)
    if old is not None:
        entries = {**{k: entries.get(k, v) for k, v in old["entries"].items()
                      if k in entries or not swept(k)}, **entries}
        generated_by = old["generated_by"]
    if os.path.exists(ranking_path):
        with open(ranking_path) as f:
            was = json.load(f)
        kept = [dict(s, mode=s.get("mode", was.get("mode")))
                for s in was.get("sweeps", []) if s["kernel"] not in kernels]
        report = dict(report, sweeps=kept + [dict(s, mode=report["mode"])
                                             for s in report["sweeps"]])
    return entries, report, generated_by


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", choices=("chip-free", "on-chip"),
                    default="chip-free")
    ap.add_argument("--topology", default="v5e:2x2",
                    help="AOT compile target for chip-free mode")
    ap.add_argument("--kernels", default="",
                    help="comma list (default: all five)")
    ap.add_argument("--iters", type=int, default=10,
                    help="timed iterations per candidate (on-chip)")
    ap.add_argument("--out", default="",
                    help="table path (default: the device's checked-in "
                         "tables/<device>.json)")
    ap.add_argument("--results-dir", default="onchip_results")
    args = ap.parse_args(argv)

    if args.mode == "chip-free":
        # host platform is CPU; compiles target the real TPU topology. Must
        # happen before the backend initializes (same as aot_tpu_check).
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from deepspeed_tpu.utils import compile_cache
    compile_cache.enable()

    from deepspeed_tpu.autotuning import kernel_table, kernel_tuner

    kernels = [k for k in args.kernels.split(",") if k] or None
    entries, report = kernel_tuner.tune(mode=args.mode, kernels=kernels,
                                        topology_name=args.topology,
                                        iters=args.iters)
    device = report["device_kind"]

    out = args.out or kernel_table.table_path(device)
    generated_by = (f"scripts/tune_kernels.py --mode {args.mode}"
                    + (f" --topology {args.topology}"
                       if args.mode == "chip-free" else ""))
    os.makedirs(args.results_dir, exist_ok=True)
    ranking_path = os.path.join(args.results_dir,
                                f"kernel_tuning_{device}.json")
    if kernels:
        entries, report, generated_by = keep_other_kernels(
            kernels, entries, report, generated_by, out, ranking_path)
    with open(ranking_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"ranking -> {ranking_path} "
          f"({sum(len(s['candidates']) for s in report['sweeps'])} "
          f"candidates across {len(report['sweeps'])} sweeps)")

    if not entries:
        print("no feasible candidates — table NOT written", file=sys.stderr)
        return 1

    kernel_table.save_table(out, device, entries, generated_by,
                            sort=not kernels)
    print(f"table -> {out} ({len(entries)} entries)")
    missing = [k for k in (kernels or kernel_table.KERNEL_KNOBS)
               if not any(key.startswith(f"{k}|") for key in entries)]
    if missing:
        print(f"WARNING: no feasible entry for {missing}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
