"""Compile the bench ladder's configs for the REAL v5e target, chip-free
(VERDICT r4 #1 groundwork): per (batch, remat-policy), the XLA:TPU
compiler's own memory assignment decides feasibility — no more hand
activation-arithmetic (which had (32, save-all) fitting; the compiler says
26.2GB > 15.75GB HBM) — and its flops/bytes counts give the roofline that
bounds achievable MFU.

The programs are the bench's model fwd+bwd with the flash kernel active
(DS_TPU_ASSUME_TPU) under the ladder's activation policies. The engine's
fused step adds optimizer state (~14 bytes/param ≈ 1.8GB for GPT-2-small)
on top of the program's own allocation — column `fits+opt` accounts for it.

Feasibility is computed for the SINGLE-chip bench environment: one v5e,
ZeRO world 1, optimizer states unsharded (``--zero-world N`` divides the
state bytes for multi-chip what-ifs; program temp bytes stay per-chip
pessimistic since activations shard too).

Usage: python scripts/aot_ladder_calibration.py [--model gpt2|llama]
Writes onchip_results/ladder_calibration_{model}.json.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("DS_TPU_ASSUME_TPU", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from deepspeed_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

HBM = 15.75e9          # v5e usable HBM (from the compiler's own OOM message)
PEAK = 197e12          # bf16 FLOP/s
BW = 819e9             # HBM bytes/s
OPT_BYTES_PER_PARAM = 14  # bf16 working + fp32 master + fp32 m,v


def _mesh():
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return Mesh(np.array(topo.devices[:1]), ("d",))


def build(model_name, batch, policy):
    from deepspeed_tpu.runtime.activation_checkpointing import checkpointing
    checkpointing._CONFIG["policy"] = policy if policy != "nothing" else "dots"
    if model_name == "gpt2":
        from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2LMHeadModel,
                                               gpt2_flops_per_token)
        cfg = dataclasses.replace(GPT2Config.small(),
                                  remat=policy != "nothing")
        model = GPT2LMHeadModel(cfg)
        T = 1024
        fpt = gpt2_flops_per_token(cfg, T)
    else:
        from deepspeed_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                                llama_flops_per_token)
        cfg = LlamaConfig(vocab_size=32000, hidden_size=1536,
                          intermediate_size=4096, num_hidden_layers=16,
                          num_attention_heads=12, num_key_value_heads=2,
                          max_position_embeddings=2048,
                          remat=policy != "nothing")
        model = LlamaForCausalLM(cfg)
        T = 2048
        fpt = llama_flops_per_token(cfg, T)
    b = {"input_ids": jax.ShapeDtypeStruct((batch, T), jnp.int32),
         "labels": jax.ShapeDtypeStruct((batch, T), jnp.int32)}
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), {"input_ids": jnp.zeros((1, 8), jnp.int32)}))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree.leaves(shapes["params"]))
    fn = jax.value_and_grad(lambda p, bb: model.apply({"params": p}, bb))
    return fn, (shapes["params"], b), batch * T, fpt, n_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt2", choices=("gpt2", "llama"))
    ap.add_argument("--configs", default="")
    ap.add_argument("--zero-world", type=int, default=1,
                    help="divide optimizer-state bytes by this (ZeRO shard "
                         "count) for multi-chip feasibility what-ifs")
    args = ap.parse_args()
    mesh = _mesh()
    s = NamedSharding(mesh, P())

    if args.configs:
        ladder = [(int(b), p) for b, p in
                  (c.split(":") for c in args.configs.split(","))]
    elif args.model == "gpt2":
        ladder = [(32, "nothing"), (64, "dots"), (32, "dots"), (16, "dots"),
                  (32, "everything")]
    else:
        ladder = [(16, "nothing"), (16, "dots"), (8, "dots"), (4, "dots"),
                  (8, "everything")]

    rows = []
    for batch, policy in ladder:
        t0 = time.perf_counter()
        try:
            fn, abstract, tokens, fpt, n_params = build(args.model, batch,
                                                        policy)
            c = jax.jit(fn, in_shardings=jax.tree.map(lambda _: s, abstract)) \
                .lower(*abstract).compile()
            ca, ma = c.cost_analysis(), c.memory_analysis()
            prog = (ma.temp_size_in_bytes + ma.argument_size_in_bytes +
                    ma.output_size_in_bytes - ma.alias_size_in_bytes)
            opt_extra = (n_params * OPT_BYTES_PER_PARAM // args.zero_world
                         - ma.argument_size_in_bytes)  # args hold the fp32
            # params this bare program takes; the engine replaces them with
            # bf16 working + (sharded) fp32 master/moments
            t_mem = ca["bytes accessed"] / BW
            t_flops = fpt * tokens / PEAK
            bound = max(t_mem, t_flops)
            rows.append({
                "batch": batch, "policy": policy, "ok": True,
                "compile_s": round(time.perf_counter() - t0, 1),
                "program_bytes": prog,
                "fits": prog < HBM,
                "fits_with_opt_states": prog + max(opt_extra, 0) < HBM,
                "xla_flops": ca["flops"],
                "bytes_accessed": ca["bytes accessed"],
                "t_mem_ms": round(t_mem * 1e3, 1),
                "t_flops_6nd_ms": round(t_flops * 1e3, 1),
                "mfu_ceiling": round(t_flops / bound, 3),
                "tokens": tokens})
            r = rows[-1]
            print(f"{args.model} b{batch} {policy:10s}: prog="
                  f"{prog/1e9:5.1f}GB fits={r['fits']} "
                  f"(+opt {r['fits_with_opt_states']})  "
                  f"t_mem={r['t_mem_ms']:6.1f}ms t_flops={r['t_flops_6nd_ms']:6.1f}ms "
                  f"mfu_ceiling={r['mfu_ceiling']:.2f}", flush=True)
        except Exception as e:
            msg = str(e)
            rows.append({"batch": batch, "policy": policy, "ok": False,
                         "compile_s": round(time.perf_counter() - t0, 1),
                         "error": f"{type(e).__name__}: {msg[:300]}"})
            oom = "RESOURCE_EXHAUSTED" in msg
            print(f"{args.model} b{batch} {policy:10s}: "
                  f"{'DOES NOT FIT (compiler OOM)' if oom else 'FAILED'} "
                  f"{msg[:120]}", flush=True)

    os.makedirs("onchip_results", exist_ok=True)
    path = f"onchip_results/ladder_calibration_{args.model}.json"
    with open(path, "w") as f:
        json.dump({"model": args.model, "hbm": HBM, "peak": PEAK, "bw": BW,
                   "rows": rows}, f, indent=1)
    print(json.dumps({"metric": f"ladder_feasible_{args.model}",
                      "value": sum(1 for r in rows if r.get("ok")),
                      "unit": f"configs (of {len(rows)})",
                      "vs_baseline": 1.0}))


if __name__ == "__main__":
    main()
