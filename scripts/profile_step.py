"""Capture a jax.profiler trace of the headline training step on the chip.

Writes a perfetto/tensorboard trace to ``/tmp/ds_tpu_trace`` and prints the
top compiled-program cost split (from XLA's own cost analysis) so the next
optimization lever is visible without a trace viewer.

``DS_TPU_TELEMETRY=1`` enables the unified telemetry pipeline and emits one
JSON payload line to stdout (bench payload convention) with the summary —
including the overlap report attributed from the captured trace
(``telemetry/overlap.py``) — embedded in ``extra.telemetry``.

    python scripts/profile_step.py [--batch 32] [--remat dots] [--steps 5]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _attach_trace_overlap(trace_dir):
    """Best-effort: attribute exposure from the trace just captured and
    attach it to telemetry. Profiler output layout varies by jax version —
    never let report plumbing kill the profile run."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry import overlap
    try:
        events = overlap.load_trace_events(trace_dir)
        per_device = overlap.intervals_from_trace(events)
        if not per_device:
            return None
        report = overlap.overlap_report(
            per_device, mode="trace",
            comm_stats=telemetry.get_telemetry().comm_stats)
        return telemetry.attach_overlap(report)
    except Exception as e:
        print(f"overlap attribution unavailable: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--out", default="/tmp/ds_tpu_trace")
    args = ap.parse_args()

    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.parallel import groups

    telemetry_on = os.environ.get("DS_TPU_TELEMETRY") == "1"
    if telemetry_on:
        telemetry.configure(enabled=True,
                            chrome_trace_path=os.environ.get(
                                "DS_TPU_TELEMETRY_TRACE", ""))

    print("devices:", jax.devices(), flush=True)
    seq = 1024
    cfg = GPT2Config.small()
    cfg = type(cfg)(**{**cfg.__dict__, "n_positions": max(cfg.n_positions, seq),
                       "scan_layers": True, "remat": True})
    model = GPT2LMHeadModel(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(args.batch, seq)).astype(np.int32)
    batch = {"input_ids": ids, "labels": ids}
    groups.reset()
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_micro_batch_size_per_gpu": args.batch,
                "gradient_accumulation_steps": 1,
                "bf16": {"enabled": True},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                "zero_optimization": {"stage": 1},
                "gradient_clipping": 1.0,
                "activation_checkpointing": {"policy": args.remat}})

    def step():
        loss = engine(batch)
        engine.backward(loss)
        engine.step()
        return loss

    print("compiling...", flush=True)
    jax.block_until_ready(step())

    # cost analysis of the compiled micro-step: flops vs bytes accessed tells
    # whether the step is MXU- or HBM-bound before opening any trace
    try:
        lowered = engine._micro_step_fn.lower(engine.state, batch)
        ca = lowered.compile().cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        flops = ca.get("flops", 0.0)
        bytes_ = ca.get("bytes accessed", 0.0)
        print(f"micro-step cost analysis: {flops/1e12:.2f} TFLOP, "
              f"{bytes_/1e9:.2f} GB accessed, "
              f"arithmetic intensity {flops/max(bytes_,1):.0f} flop/byte",
              flush=True)
    except Exception as e:
        print(f"cost analysis unavailable: {type(e).__name__}: {e}", flush=True)

    t0 = time.perf_counter()
    with jax.profiler.trace(args.out):
        for _ in range(args.steps):
            loss = step()
        jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / args.steps
    toks = args.batch * seq / dt
    print(f"{dt*1000:.1f} ms/step, {toks:.0f} tokens/s "
          f"(batch {args.batch}, remat {args.remat})", flush=True)
    print(f"trace written to {args.out}", flush=True)

    if telemetry_on:
        _attach_trace_overlap(args.out)
        payload = {"metric": "profile_step_ms", "value": round(dt * 1e3, 3),
                   "unit": "ms",
                   "extra": {"tokens_per_s": round(toks, 1),
                             "batch": args.batch, "remat": args.remat,
                             "trace_dir": args.out,
                             "telemetry": telemetry.summary()}}
        print(json.dumps(payload), flush=True)


if __name__ == "__main__":
    main()
