"""Flagship-path on-chip bench: llama-architecture training MFU.

Exercises exactly the stack BASELINE.md's north-star rows name: flash
attention (Pallas), GQA, scan-over-layers, ZeRO-3 param partitioning, bf16 —
on a ~0.5B llama config sized for one v5e-class chip. Prints ONE JSON line
like bench.py (metric/value/unit/vs_baseline where vs_baseline = MFU / 0.45).

Usage: python scripts/bench_llama.py [--steps N] [--seq T] [--batch B]

One process, one configuration; needs a TPU and exits non-zero without one,
or when the configuration does not fit.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # repo-root bench.py: require_tpu + the peak table


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seq", type=int, default=2048)
    # COMPILER-CALIBRATED for the single-chip bench (scripts/
    # aot_ladder_calibration.py --model llama,
    # onchip_results/ladder_calibration_llama.json): b16 OOMs at 16.8-46GB
    # program bytes; b8-dots fits the bare program (14.0GB) but not next to
    # ~6GB UNSHARDED optimizer state (world 1); b4-dots (9.3GB) is the
    # largest batch with headroom. A configuration that does not fit is an
    # error, not a reason to try another.
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--remat", default="dots", help="remat policy")
    args = ap.parse_args()

    devs = bench.require_tpu()

    import jax
    import numpy as np
    import deepspeed_tpu
    from deepspeed_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                            llama_flops_per_token)
    from deepspeed_tpu.utils import compile_cache

    compile_cache.enable()
    n_chips = len(devs)
    kind = devs[0].device_kind
    seq, batch, remat_policy, fused = args.seq, args.batch, args.remat, True

    # ~0.5B: 16 layers x 1536 hidden, 12 heads (GQA 6:1 -> 2 kv heads).
    # Sizing is HBM-bound, not ambition-bound: params cost 14 bytes each
    # (bf16 + fp32 master + Adam m,v) plus fp32 transients during the
    # update, so ~0.5B is the largest llama that trains on one 16GB v5e
    # with a batch big enough to saturate the MXU — the previous 0.8B
    # config OOM'd at every batch size it was ever tried at.
    cfg = LlamaConfig(vocab_size=32000, hidden_size=1536,
                      intermediate_size=4096, num_hidden_layers=16,
                      num_attention_heads=12, num_key_value_heads=2,
                      max_position_embeddings=seq)
    model = LlamaForCausalLM(cfg)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size,
                       size=(batch * n_chips, seq)).astype(np.int32)
    data = {"input_ids": ids, "labels": ids}
    params = model.init(jax.random.PRNGKey(0), data)["params"]
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": batch,
            "gradient_accumulation_steps": 1,
            "bf16": {"enabled": True},
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 3,
                                  "stage3_param_persistence_threshold": 0},
            "gradient_clipping": 1.0,
            "fused_step": fused,
            "activation_checkpointing": {"policy": remat_policy},
        })

    def step():
        loss = engine(data)
        engine.backward(loss)
        engine.step()
        return loss

    t0 = time.perf_counter()
    loss = step()
    jax.block_until_ready(loss)
    print(f"llama bench: compile+first {time.perf_counter()-t0:.1f}s "
          f"batch={batch} remat={remat_policy} fused={fused} "
          f"loss={float(jax.device_get(loss)):.3f}", file=sys.stderr)

    n_steps = args.steps
    t0 = time.perf_counter()
    for _ in range(n_steps):
        loss = step()
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    tokens = batch * n_chips * seq * n_steps
    tok_chip = tokens / dt / n_chips
    mfu = tok_chip * llama_flops_per_token(cfg, seq) / bench.peak_flops(kind)
    bench.emit({
        "metric": "llama500m_bf16_zero3_tokens_per_sec_per_chip",
        "value": round(tok_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {"mfu": round(mfu, 4), "chips": n_chips, "device": kind,
                  "params_m": round(cfg.num_parameters() / 1e6, 1),
                  "batch_per_chip": batch, "seq": seq, "steps": n_steps,
                  "remat_policy": remat_policy, "fused_step": fused,
                  "loss": float(jax.device_get(loss))},
    })


if __name__ == "__main__":
    main()
