"""Headline benchmark: GPT-2-small (124M) bf16 causal-LM training throughput on
the attached TPU chip(s), reported as tokens/sec/chip and MFU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
``vs_baseline`` is MFU / 0.45 — the north-star MFU target from BASELINE.json
(≥45% MFU for ZeRO-3 pretraining); >1.0 beats the target.

One process, one fixed configuration (batch 32 x 1024, remat ``dots``, fused
step): without a TPU, when the configuration does not fit, or when the first
loss is off, the script raises and exits non-zero — it never prints a metric
it did not measure on the chip.
"""

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BATCH, SEQ, REMAT_POLICY = 32, 1024, "dots"


def peak_flops(device_kind):
    """Per-chip peak bf16 FLOP/s: THE table lives in telemetry/core.py, has
    no CPU row, and raises for a device it does not know."""
    from deepspeed_tpu.telemetry.core import peak_bf16_flops
    return peak_bf16_flops(device_kind)


def emit(payload):
    print(json.dumps(payload))
    sys.stdout.flush()


def require_tpu():
    """The attached devices, or an error: benchmarks measure the chip."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"this benchmark needs a TPU; jax found "
                           f"{devs[0].platform!r} ({devs[0].device_kind})")
    return devs


def run_bench():
    import jax
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel, gpt2_flops_per_token

    from deepspeed_tpu.utils import compile_cache

    devs = require_tpu()
    compile_cache.enable()
    n_chips = len(devs)
    kind = devs[0].device_kind
    print(f"bench: {n_chips}x {kind}", file=sys.stderr)

    # DS_TPU_TELEMETRY=1 folds the unified-telemetry summary (span stats,
    # comm bytes/bandwidth, kernel-dispatch outcomes) into payload["extra"].
    # Off by default; no span waits for the device either way.
    # docs/OBSERVABILITY.md has the schema.
    from deepspeed_tpu import telemetry
    if os.environ.get("DS_TPU_TELEMETRY") == "1":
        telemetry.configure(enabled=True,
                            chrome_trace_path=os.environ.get(
                                "DS_TPU_TELEMETRY_TRACE", ""))

    batch, seq, remat_policy, fused = BATCH, SEQ, REMAT_POLICY, True
    cfg = GPT2Config.small(scan_layers=True, remat=True)
    model = GPT2LMHeadModel(cfg)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size,
                       size=(batch * n_chips, seq)).astype(np.int32)
    batch_data = {"input_ids": ids, "labels": ids}
    params = model.init(jax.random.PRNGKey(0), batch_data)["params"]
    # DS_BENCH_GAS>1 measures the fused whole-window step (one jit per
    # accumulation window via train_batch) instead of GAS=1
    gas = max(1, int(os.environ.get("DS_BENCH_GAS", "1")))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": batch,
            "gradient_accumulation_steps": gas,
            "bf16": {"enabled": True},
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "zero_optimization": {"stage": 1},
            "gradient_clipping": 1.0,
            "fused_step": fused,
            "activation_checkpointing": {"policy": remat_policy},
        })

    if gas > 1:
        import itertools
        window_iter = itertools.repeat(batch_data)

        def step():
            # train_batch returns the device-resident window mean; the
            # timing loop's block_until_ready pays the sync
            return jax.numpy.asarray(engine.train_batch(window_iter))
    else:
        def step():
            loss = engine(batch_data)
            engine.backward(loss)
            engine.step()
            return loss

    t0 = time.perf_counter()
    loss = step()
    jax.block_until_ready(loss)
    first_loss = float(jax.device_get(loss))

    print(f"compile+first step: {time.perf_counter()-t0:.1f}s "
          f"batch={batch} remat={remat_policy} fused={fused} "
          f"loss={first_loss:.3f}", file=sys.stderr)
    # sanity: random-init CE should be ~ln(vocab); an insane/NaN loss means
    # a kernel miscompile, and a throughput of a wrong program is no result
    expected = math.log(cfg.vocab_size)
    if not abs(first_loss - expected) < 3.0:
        raise RuntimeError(f"first loss {first_loss:.2f}, expected "
                           f"~{expected:.1f}")

    n_steps = 10
    fpt = gpt2_flops_per_token(cfg, seq)
    tokens_per_step = batch * n_chips * seq * gas
    # feed the telemetry goodput ledger the same FLOP model the ad-hoc MFU
    # below uses, so extra.mfu and extra.telemetry.ledger.mfu_rolling agree
    telemetry.set_model_flops(flops_per_step=fpt * tokens_per_step,
                              peak_flops=peak_flops(kind) * n_chips)
    t0 = time.perf_counter()
    for i in range(n_steps):
        loss = step()
        telemetry.ledger_step(step=i)  # no-op when telemetry is off
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    tokens = tokens_per_step * n_steps
    tok_per_sec_chip = tokens / dt / n_chips
    mfu = tok_per_sec_chip * fpt / peak_flops(kind)

    payload = {
        "metric": "gpt2_small_bf16_zero1_tokens_per_sec_per_chip",
        "value": round(tok_per_sec_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {"mfu": round(mfu, 4), "chips": n_chips, "device": kind,
                  "batch_per_chip": batch, "seq": seq, "steps": n_steps,
                  "remat_policy": remat_policy, "fused_step": fused,
                  "gas": gas, "loss": float(jax.device_get(loss))},
    }
    # which block configs actually ran (tuning table vs ladder vs env) and
    # how many blocking d2h fetches the engine issued — a tuned table with
    # ladder_fallback sources or a nonzero steady-state sync count is the
    # 32%→45% MFU gap showing up in the payload (docs/AUTOTUNING.md)
    from deepspeed_tpu.ops import registry as _kernel_registry
    payload["extra"]["kernel_configs"] = _kernel_registry.active_kernel_configs()
    payload["extra"]["host_sync_count"] = engine.host_sync_count
    if telemetry.enabled():
        hbm = telemetry.sample_memory("bench_end") or {}
        summ = telemetry.summary()
        payload["extra"]["telemetry"] = summ
        payload["extra"]["peak_hbm_bytes"] = max(
            int(hbm.get("peak_bytes_in_use", 0) or 0),
            int(summ.get("memory", {}).get("peak_bytes", 0)))
        payload["extra"]["goodput_ledger"] = summ.get("ledger", {})
        # compact wire view: per comm op/axis, quantized wire bytes vs the
        # logical fp32 bytes (the ZeRO++ fitness function: DCN ratio <= 0.3)
        comm = summ.get("comm", {})
        wire = {}
        for op, per_axis in comm.get("ops", {}).items():
            for axis, st in per_axis.items():
                if st.get("wire_bytes", st["bytes"]) != st["bytes"]:
                    wire[f"{op}@{axis}"] = {
                        "bytes": st["bytes"],
                        "wire_bytes": st["wire_bytes"],
                        "ratio": round(st["wire_bytes"] / st["bytes"], 4)
                        if st["bytes"] else 0.0}
        if wire:
            payload["extra"]["wire_bytes"] = wire
        # analytic overlap exposure for the measured step: the traced comm
        # inventory against the FLOP model's roofline compute, scored by the
        # scheduled timeline when the overlap pass is on (perf_gate gates
        # exposed_comm_s growth on exactly this block)
        try:
            from deepspeed_tpu.autotuning.kernel_table import (
                normalize_device_kind)
            from deepspeed_tpu.telemetry import overlap as _overlap
            comm_ops = []
            for op, per_axis in comm.get("ops", {}).items():
                for axis, st in per_axis.items():
                    comm_ops.append({"op": op, "axis": axis,
                                     "bytes": st["bytes"],
                                     "wire_bytes": st["wire_bytes"],
                                     "count": st["count"]})
            slug = normalize_device_kind(kind)
            cost = {"flops": fpt * tokens_per_step / n_chips}
            axis_sizes = {"dp": n_chips}
            ov_cfg = engine.config.overlap_config
            if ov_cfg.schedule and comm_ops:
                from deepspeed_tpu.runtime.zero import (
                    overlap_schedule as _osched)
                plan = _osched.OverlapPlan(
                    prefetch_depth=ov_cfg.prefetch_depth,
                    grad_buckets=ov_cfg.grad_buckets)
                ov_rep = _osched.scheduled_report(
                    cost, comm_ops, plan, device_kind=slug,
                    axis_sizes=axis_sizes)
            else:
                ov_rep = _overlap.analytic_report(
                    cost, comm_ops, device_kind=slug,
                    axis_sizes=axis_sizes)
            payload["extra"]["overlap"] = ov_rep
        except Exception as e:
            print(f"bench: overlap embed failed: {e}", file=sys.stderr)
    emit(payload)


def _moe_stack(d_model, n_layers, num_experts, k, wire_bits):
    """GPT-2-ish block stack with a dropless expert-parallel MoE FFN every
    other layer — the --moe bench model. Returns a flax module whose apply
    gives (logits-shaped output, summed aux loss, last MoE layer's
    exp_counts)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.moe.sharded_moe import MOELayer

    class ExpertFFN(nn.Module):
        hidden: int = d_model
        GMM_COMPAT = ("w1", "w3", "w2")

        def gmm_shapes(self, d):
            return {"w1": (d, self.hidden), "w3": (d, self.hidden),
                    "w2": (self.hidden, d)}

        @nn.compact
        def __call__(self, x):
            h = (nn.silu(nn.Dense(self.hidden, use_bias=False, name="w1")(x))
                 * nn.Dense(self.hidden, use_bias=False, name="w3")(x))
            return nn.Dense(d_model, use_bias=False, name="w2")(h)

    class Block(nn.Module):
        moe: bool = False

        @nn.compact
        def __call__(self, x):
            h = nn.LayerNorm()(x)
            B, T, D = h.shape
            q = nn.Dense(D, use_bias=False, name="q")(h)
            kk = nn.Dense(D, use_bias=False, name="k")(h)
            v = nn.Dense(D, use_bias=False, name="v")(h)
            att = jnp.einsum("btd,bsd->bts", q, kk) / jnp.sqrt(D)
            att = jax.nn.softmax(
                jnp.where(jnp.tril(jnp.ones((T, T), bool)), att, -1e9), -1)
            x = x + nn.Dense(D, use_bias=False, name="o")(
                jnp.einsum("bts,bsd->btd", att, v))
            h = nn.LayerNorm()(x)
            if self.moe:
                y, l_aux, counts = MOELayer(
                    ExpertFFN, num_experts, k, drop_tokens=False,
                    dispatch_mode="gmm", a2a_wire_bits=wire_bits,
                    name="moe")(h)
                return x + y, l_aux, counts
            return x + ExpertFFN(name="ffn")(h), 0.0, None

    class Stack(nn.Module):
        @nn.compact
        def __call__(self, x):
            aux, counts = 0.0, None
            for i in range(n_layers):
                x, la, c = Block(moe=(i % 2 == 1), name=f"block_{i}")(x)
                aux = aux + la
                if c is not None:
                    counts = c
            return x, aux, counts

    return Stack()


def run_moe_bench():
    """--moe leg: dropless expert-parallel MoE micro-step throughput on an
    8-device (dp x ep) mesh, with the quantized-a2a wire-byte ratios, the
    per-step MoE gauges, and the analytic chunked-a2a overlap report (the
    ``check_moe_baseline`` ratchet source) embedded in ``extra``. Emits ONE
    JSON line. The counts and the analytic report are the same on any
    backend, so the baseline regenerates on the virtual CPU mesh:
    ``JAX_PLATFORMS=cpu DS_TPU_PALLAS_INTERPRET=1 python bench.py --moe |
    tail -1 > onchip_results/moe_overlap_baseline.json`` (docs/MOE.md).
    The rate is a device metric: ``value`` is null unless the run was on
    TPUs."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    import numpy as np

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.parallel.topology import MeshTopology

    n_dev = len(jax.devices())
    kind = jax.devices()[0].device_kind
    if n_dev < 8:
        raise RuntimeError(f"--moe needs 8 devices, have {n_dev}")
    # telemetry is always on for this leg: the traced comm records ARE the
    # wire-byte payload (trace-time, no steady-state sync)
    telemetry.configure(enabled=True)

    d_model, n_layers, experts, k, seq, batch = 256, 4, 4, 2, 128, 8
    wire_bits = 8
    model = _moe_stack(d_model, n_layers, experts, k, wire_bits)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, seq, d_model)).astype(np.float32)

    groups.reset()
    groups.initialize(mesh_topology=MeshTopology(dp=-1, ep=2))
    try:
        params = model.init(jax.random.PRNGKey(0), x)["params"]
        step = jax.jit(lambda p, xx: model.apply({"params": p}, xx))
        out, aux, counts = step(params, x)   # compile + trace-time comm
        jax.block_until_ready(out)
        n_steps = 5
        t0 = time.perf_counter()
        for _ in range(n_steps):
            out, aux, counts = step(params, x)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
    finally:
        groups.reset()

    tokens = batch * seq * n_steps
    tok_per_sec = tokens / dt
    host_counts = [int(c) for c in np.asarray(jax.device_get(counts))]

    summ = telemetry.summary()
    comm = summ.get("comm", {}).get("ops", {})
    wire, comm_ops, a2a_wire_total = {}, [], 0
    for op, per_axis in comm.items():
        for axis, st in per_axis.items():
            comm_ops.append({"op": op, "axis": axis, "bytes": st["bytes"],
                             "wire_bytes": st["wire_bytes"],
                             "count": st["count"]})
            if op.startswith("a2a_"):
                a2a_wire_total += st["wire_bytes"]
            if st.get("wire_bytes", st["bytes"]) != st["bytes"]:
                wire[f"{op}@{axis}"] = {
                    "bytes": st["bytes"], "wire_bytes": st["wire_bytes"],
                    "ratio": round(st["wire_bytes"] / st["bytes"], 4)
                    if st["bytes"] else 0.0}
    # the three standard gauges, from the fetched post-step routing stats
    telemetry.record_moe_step(host_counts, sum(host_counts), dropped=0,
                              a2a_wire_bytes=a2a_wire_total)

    # analytic chunked-a2a overlap on the v5e target (the checked-in
    # baseline is chip-free: deterministic roofline, not wall clock)
    from deepspeed_tpu.autotuning import kernel_tuner
    from deepspeed_tpu.runtime.zero import overlap_schedule as _osched
    slug = "tpu_v5e"
    tokens_step = batch * seq
    # matmul flops per step: attn projections + dense/expert FFN rows
    flops = tokens_step * n_layers * 8 * d_model * d_model \
        + tokens_step * (n_layers // 2) * 6 * d_model * d_model * (1 + k)
    compute_s = kernel_tuner.roofline_compute_seconds(
        float(flops), 0.0, device_kind=slug)
    axis_sizes = {"dp": 4, "ep": 2}
    specs = _osched.fill_comm_seconds(comm_ops, device_kind=slug,
                                      axis_sizes=axis_sizes)
    plan, exposed, ranking = _osched.best_moe_a2a_chunks(compute_s, specs)
    ov_rep = _osched.moe_scheduled_report({}, specs, plan,
                                          device_kind=slug,
                                          axis_sizes=axis_sizes,
                                          compute_s=compute_s)
    ov_rep["a2a_chunks_ranking"] = ranking

    on_tpu = jax.devices()[0].platform == "tpu"
    payload = {
        "metric": "moe_dropless_ep2_tokens_per_sec",
        "value": round(tok_per_sec, 1) if on_tpu else None,
        "unit": "tokens/s",
        "vs_baseline": 0.0,
        "extra": {
            "device": kind, "devices": n_dev, "d_model": d_model,
            "n_layers": n_layers, "num_experts": experts, "k": k,
            "seq": seq, "batch": batch, "steps": n_steps,
            "dropless": True, "a2a_wire_bits": wire_bits,
            "loss_aux": float(jax.device_get(aux)),
            "exp_counts": host_counts,
            "expert_load_max_frac": round(
                max(host_counts) / max(sum(host_counts), 1), 4),
            "drop_rate": 0.0,
            "wire_bytes": wire,
            "overlap": ov_rep,
            "telemetry": {"moe": summ.get("moe", {"gauges": {}})},
        },
    }
    # refresh the gauges into the embedded summary (record_moe_step ran
    # after summary() above)
    payload["extra"]["telemetry"]["moe"] = telemetry.summary().get("moe")
    emit(payload)


def main():
    if "--moe" in sys.argv:
        run_moe_bench()
    else:
        run_bench()


if __name__ == "__main__":
    main()
