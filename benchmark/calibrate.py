"""Readings that the limits of ``correct`` are set from, in one process:

    python -m benchmark.calibrate --workload <name> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 20

For every seed the cell is built and driven as a run drives it (a short
window at the cell's own load where the comparison needs served output), the
program's numbers against the plain reference are printed, and for the
control seeds the control's numbers (the reference in the next precision
down, put in the program's place). One JSON line per seed, on standard output
and in ``chiprun_out/calibrate/<workload>.jsonl``. Not part of a benchmark
run; the limits it led to are in the configuration files and in PERF.md.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    cell = harness.Cell(args.workload)
    from deepspeed_tpu.utils import compile_cache
    compile_cache.enable()
    devices = harness.require_chips(cell.chips)
    out = os.path.join(harness.ROOT, "chiprun_out", "calibrate")
    os.makedirs(out, exist_ok=True)
    driver_mod = harness.load("drivers", cell.config["driver"])
    with open(os.path.join(out, cell.name + ".jsonl"), "a") as log:
        for seed in seeds:
            t0 = time.perf_counter()
            driver = driver_mod.Driver(cell, seed, harness.Recorder(), devices=devices,
                                       seconds=args.seconds)
            line = {"seed": seed, "setup_s": time.perf_counter() - t0}
            if args.seconds > 0:
                facts = driver.window(args.seconds, harness.out_dir(cell, seed, 0))
                line["facts"] = {k: v for k, v in facts.items()
                                 if isinstance(v, (int, float, str, type(None)))}
            line["memory_peak_bytes"] = harness.memory_peak_bytes(devices)
            driver.release()
            t1 = time.perf_counter()
            line["program"] = {n: v for n, v, _ in driver.compare()}
            line["reference_s"] = time.perf_counter() - t1
            if seed in control:
                line["control"] = {n: v for n, v, _ in driver.control()}
            text = json.dumps(line)
            print(text, flush=True)
            log.write(text + "\n")
            log.flush()


if __name__ == "__main__":
    main()
