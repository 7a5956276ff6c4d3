"""Kernel block-size autotuner: the search driver behind the tuning tables.

Two modes (docs/AUTOTUNING.md):

- **chip-free** — no TPU needed. Every candidate block config is compiled
  for the target topology with the ``jax.experimental.topologies`` AOT
  compiler (the same machinery as ``scripts/aot_tpu_check.py``): a candidate
  is *feasible* iff Mosaic accepts it (VMEM limits, tiling rules), and
  feasible candidates are ranked by a roofline proxy built from XLA's
  ``cost_analysis`` (flops / peak + bytes / HBM bandwidth) plus an analytic
  grid-dispatch overhead term that rewards larger blocks when the roofline
  ties. The ranking is a *model*, not a measurement — the table it produces
  is the best chip-free guess, refined by on-chip mode when silicon answers.

- **on-chip** — a timed sweep on the live TPU backend: each feasible
  candidate runs ``iters`` times under ``block_until_ready`` and the median
  wall time ranks them. This is ground truth; it requires the chip.

``tune()`` sweeps the canonical bench shapes (``kernel_table.BENCH_SHAPES``)
for every kernel and returns table entries for ``kernel_table.save_table``
plus the full per-candidate ranking (recorded under ``onchip_results/`` by
``scripts/tune_kernels.py`` so a perf claim is always attributable).
"""

import contextlib
import os
import time

from deepspeed_tpu.autotuning import kernel_table

VMEM_BUDGET = 16 * 1024 * 1024  # per-core VMEM; pre-filter only, Mosaic is
# the authority (oversized candidates it rejects are recorded as infeasible)

GRID_STEP_SECONDS = 5e-7  # per-grid-step dispatch overhead for the proxy

GMM_TUNING_EXPERTS = 4  # experts of the moe_ffn_gmm tuning program

FLASH_TUNING_B, FLASH_TUNING_H = 2, 4  # the flash_mha tuning program's
FLASH_TUNING_BH = FLASH_TUNING_B * FLASH_TUNING_H

FLASH_MATMULS = 9  # [tq, tk, dh] products of a forward (2), dq (3), dkv (4)

GMM_CANDIDATES_A_GEMM = 4  # tilings swept for each of the FFN's GEMM shapes

#: per-chip HBM bandwidth (bytes/s) for the roofline proxy denominator
_HBM_BYTES_PER_S = {
    "tpu_v4": 1228e9,
    "tpu_v5e": 819e9,
    "tpu_v5p": 2765e9,
    "tpu_v6e": 1640e9,
}

#: per-chip peak bf16 FLOP/s (kept in sync with telemetry's MFU table)
_PEAK_FLOPS = {
    "tpu_v4": 275e12,
    "tpu_v5e": 197e12,
    "tpu_v5p": 459e12,
    "tpu_v6e": 918e12,
}

#: per-chip aggregate ICI bandwidth (bytes/s) for the collective cost
#: model (telemetry/overlap.py analytic mode). Same spirit as the HBM
#: table above: a MODEL for relative cost and CI ratchets, not a latency
#: prediction.
LINK_BYTES_PER_S = {
    "tpu_v4": 300e9,
    "tpu_v5e": 200e9,
    "tpu_v5p": 600e9,
    "tpu_v6e": 400e9,
}

#: fixed per-collective launch latency so tiny messages never model as
#: zero-duration intervals
_COMM_LATENCY_S = 1e-6


def _dtype_bytes(dtype):
    import jax.numpy as jnp
    return jnp.dtype(dtype).itemsize


# ---------------------------------------------------------------------------
# candidate spaces — only configs that tile the exact dims are proposed
# ---------------------------------------------------------------------------

def candidate_space(kernel, dims, dtype):
    """All block configs worth compiling for this kernel at these dims."""
    if kernel == "flash_mha":
        tq, tk = dims["tq"], dims["tk"]
        return [{"block_q": bq, "block_k": bk}
                for bq in (128, 256, 512, 1024) if tq % bq == 0
                for bk in (128, 256, 512, 1024) if tk % bk == 0]
    if kernel == "quantized_matmul":
        from deepspeed_tpu.ops.pallas.quantized_matmul import _blocks_fit
        m, k, n, g = dims["m"], dims["k"], dims["n"], dims["g"]
        return [{"block_m": bm, "block_n": bn, "block_k": bk}
                for bm in (128, 256, 512)
                for bn in (128, 256, 512)
                for bk in (256, 512, 1024)
                if _blocks_fit(bm, bn, bk, m, k, n, g)]
    if kernel == "moe_ffn_gmm":
        # a GEMM at a time: each shape's few best tilings by its own (k, n),
        # fewest grid steps a visited group first, and megablox's 128^3
        from deepspeed_tpu.ops.pallas import grouped_gemm as gg
        d, f, db = dims["d"], dims["f"], _dtype_bytes(dtype)

        def swept(k, n):
            every = gg.gmm_tilings(k, n, db)        # 128^3 is its last
            return every[:GMM_CANDIDATES_A_GEMM] + every[
                max(GMM_CANDIDATES_A_GEMM, len(every) - 1):]

        return [gg.ffn_blocks(up, down)
                for up in swept(d, f) for down in swept(f, d)]
    if kernel in ("block_quantize", "block_dequantize_reduce"):
        from deepspeed_tpu.ops.pallas.quant_collective import _blocks_fit
        rows, g = dims["rows"], dims["g"]
        return [{"block_g": bg} for bg in (8, 16, 32, 64, 128, 256)
                if _blocks_fit(bg, rows, g)]
    if kernel in ("paged_mha", "sparse_mha"):
        return [{}]  # no free knobs — the single candidate pins the defaults
    raise ValueError(f"unknown kernel {kernel!r}")


def grid_steps(kernel, dims, config):
    """Analytic grid-step count at tuning-harness batch/head sizes — the
    dispatch-overhead term of the proxy score."""
    if kernel == "flash_mha":
        # the tuning program is causal: a block above the diagonal is a step
        # that computes nothing and fetches nothing
        from deepspeed_tpu.ops.pallas.flash_attention import _block_visible
        bq, bk = config["block_q"], config["block_k"]
        return FLASH_TUNING_BH * sum(
            _block_visible(iq, ik, causal=True, window=None, bq=bq, bk=bk,
                           off=dims["tk"] - dims["tq"])
            for iq in range(dims["tq"] // bq)
            for ik in range(dims["tk"] // bk))
    if kernel == "quantized_matmul":
        bm = min(config["block_m"], dims["m"])
        return ((dims["m"] // bm) * (dims["n"] // config["block_n"])
                * (dims["k"] // config["block_k"]))
    if kernel == "moe_ffn_gmm":
        from deepspeed_tpu.ops.pallas import grouped_gemm as gg
        rows, d, f = dims["rows"], dims["d"], dims["f"]
        up, down = gg.ffn_tilings(config)
        return (2 * gg.gmm_grid_steps(rows, GMM_TUNING_EXPERTS, d, f, up)
                + gg.gmm_grid_steps(rows, GMM_TUNING_EXPERTS, f, d, down))
    if kernel == "block_quantize":
        bg = min(config["block_g"], dims["rows"])
        return dims["rows"] // bg
    if kernel == "block_dequantize_reduce":
        bg = min(config["block_g"], dims["rows"])
        return (dims["rows"] // bg) * dims["peers"]
    return 1


def vmem_bytes(kernel, dims, dtype, config):
    """Rough per-grid-step VMEM residency (double-buffered inputs + f32
    scratch). A pre-filter: candidates past the budget are skipped without
    a compile; Mosaic remains the real arbiter for everything else."""
    db = _dtype_bytes(dtype)
    if kernel == "flash_mha":
        bq, bk, dh = config["block_q"], config["block_k"], dims["dh"]
        io = (bq * dh + 2 * bk * dh) * db * 2          # q + k/v, double-buffed
        scratch = (2 * bq * 128 + bq * dh) * 4         # m/l lanes + acc, f32
        logits = bq * bk * 4
        return io + scratch + logits
    if kernel == "quantized_matmul":
        bm, bn, bk = (min(config["block_m"], dims["m"]), config["block_n"],
                      config["block_k"])
        io = (bm * bk * db + bk * bn * 1 + bk * (bn // dims["g"]) * 4) * 2
        return io + bm * bn * 4 + bk * bn * 4          # acc + dequant temp
    if kernel == "moe_ffn_gmm":
        from deepspeed_tpu.ops.pallas import grouped_gemm as gg
        return max(gg.gmm_vmem_bytes(tiling, db)
                   for tiling in gg.ffn_tilings(config))
    if kernel == "block_quantize":
        bg = min(config["block_g"], dims["rows"])
        g = dims["g"]
        gw = g if dims["bits"] == 8 else g // 2
        return bg * g * 4 * 2 + bg * gw + bg * 128 * 4   # f32 in (db) + wire + scales
    if kernel == "block_dequantize_reduce":
        bg = min(config["block_g"], dims["rows"])
        g = dims["g"]
        gw = g if dims["bits"] == 8 else g // 2
        return (bg * gw + bg * 128 * 4) * 2 + bg * g * 4 * 2  # wire+scales (db) + acc + out
    return 0


# ---------------------------------------------------------------------------
# tuning programs — the real kernel entry points with the candidate pinned
# ---------------------------------------------------------------------------

def build_program(kernel, dims, dtype, config):
    """(fn, abstract_args) invoking the kernel with ``config`` pinned.
    flash compiles fwd+bwd (its bench use is training); the rest fwd."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = dict(config) if config else None
    if kernel == "flash_mha":
        from deepspeed_tpu.ops.pallas.flash_attention import flash_mha
        qkv = tuple(jax.ShapeDtypeStruct(
            (FLASH_TUNING_B, dims["tq"], FLASH_TUNING_H, dims["dh"]), dtype)
            for _ in range(3))

        def loss(q, k, v):
            return jnp.sum(flash_mha(q, k, v, causal=True, block_config=cfg)
                           .astype(jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2)), qkv

    if kernel == "quantized_matmul":
        from deepspeed_tpu.ops.pallas.quantized_matmul import quantized_matmul
        m, k, n, g = dims["m"], dims["k"], dims["n"], dims["g"]
        args = (jax.ShapeDtypeStruct((m, k), dtype),
                jax.ShapeDtypeStruct((k, n), jnp.int8),
                jax.ShapeDtypeStruct((k, n // g), jnp.float32))
        return (lambda x, q, s: quantized_matmul(x, q, s, g,
                                                 block_config=cfg)), args

    if kernel == "moe_ffn_gmm":
        from deepspeed_tpu.ops.pallas.grouped_gemm import moe_ffn_gmm
        E, topk = GMM_TUNING_EXPERTS, 2
        T = max(dims["rows"] // topk, 1)
        d, f = dims["d"], dims["f"]
        args = (jax.ShapeDtypeStruct((T, d), dtype),
                jax.ShapeDtypeStruct((T, topk), jnp.float32),
                jax.ShapeDtypeStruct((T, topk), jnp.int32),
                jax.ShapeDtypeStruct((E, d, f), dtype),
                jax.ShapeDtypeStruct((E, f, d), dtype),
                jax.ShapeDtypeStruct((E, d, f), dtype))
        return (lambda x, tv, ti, w1, w2, w3: moe_ffn_gmm(
            x, tv, ti, w1, w2, w3, n_experts=E, dtype=dtype,
            block_config=cfg)), args

    if kernel == "paged_mha":
        from deepspeed_tpu.ops.pallas.paged_attention import paged_mha
        S, Q, H, KV, NB, MB = 3, 2, 4, 2, 10, 4
        bs, dh = dims["bs"], dims["dh"]
        args = (jax.ShapeDtypeStruct((S, Q, H, dh), dtype),
                jax.ShapeDtypeStruct((NB, KV, bs, dh), dtype),
                jax.ShapeDtypeStruct((NB, KV, bs, dh), dtype),
                jax.ShapeDtypeStruct((S, MB), jnp.int32),
                jax.ShapeDtypeStruct((S,), jnp.int32),
                jax.ShapeDtypeStruct((S,), jnp.int32))
        return paged_mha, args

    if kernel == "block_quantize":
        from deepspeed_tpu.ops.pallas.quant_collective import block_quantize
        rows, g, bits = dims["rows"], dims["g"], dims["bits"]
        args = (jax.ShapeDtypeStruct((rows, g), dtype),)
        return (lambda x: block_quantize(x, num_bits=bits, group_size=g,
                                         block_config=cfg)), args

    if kernel == "block_dequantize_reduce":
        from deepspeed_tpu.ops.pallas.quant_collective import (
            block_dequantize_reduce)
        peers, rows, g, bits = (dims["peers"], dims["rows"], dims["g"],
                                dims["bits"])
        gw = g if bits == 8 else g // 2
        args = (jax.ShapeDtypeStruct((peers, rows * gw), dtype),
                jax.ShapeDtypeStruct((peers, rows), jnp.float32))
        return (lambda q, s: block_dequantize_reduce(
            q, s, num_bits=bits, group_size=g, block_config=cfg)), args

    if kernel == "sparse_mha":
        from deepspeed_tpu.ops.pallas.block_sparse_attention import sparse_mha
        B, H = 2, 4
        s, block, dh = dims["s"], dims["block"], dims["dh"]
        nq = s // block
        rng = np.random.default_rng(2)
        layout = ((rng.random((H, nq, nq)) < 0.4)
                  | np.eye(nq, dtype=bool)[None]).astype(np.int32)
        args = tuple(jax.ShapeDtypeStruct((B, H, s, dh), dtype)
                     for _ in range(3))
        return (lambda q, k, v: sparse_mha(q, k, v, layout, block,
                                           causal=True)), args

    raise ValueError(f"unknown kernel {kernel!r}")


# ---------------------------------------------------------------------------
# chip-free mode
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _assume_tpu():
    """Traced programs must take the Pallas fast paths even on a CPU host —
    the compile target is the real TPU (see scripts/aot_tpu_check.py)."""
    old = os.environ.get("DS_TPU_ASSUME_TPU")
    os.environ["DS_TPU_ASSUME_TPU"] = "1"
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("DS_TPU_ASSUME_TPU", None)
        else:
            os.environ["DS_TPU_ASSUME_TPU"] = old


def _cost_dict(compiled):
    """Normalize ``compiled.cost_analysis()`` across jax versions
    (dict vs one-element list of dicts vs None)."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost) if isinstance(cost, dict) else {}


def make_aot_compiler(topology_name="v5e:2x2"):
    """compile_fn(fn, abstract_args) -> (cost dict, memory_analysis) against
    the target topology, raising on Mosaic/XLA rejection (= infeasible)."""
    import numpy as np
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology_name)
    mesh = Mesh(np.array(topo.devices[:1]), ("d",))
    shard = NamedSharding(mesh, P())

    def compile_fn(fn, abstract):
        with _assume_tpu():
            jitted = jax.jit(
                fn, in_shardings=jax.tree.map(lambda _: shard, abstract))
            compiled = jitted.lower(*abstract).compile()
        return _cost_dict(compiled), compiled.memory_analysis()

    return compile_fn, topo.devices[0].device_kind


def proxy_score(kernel, dims, dtype, config, cost, device_kind):
    """Roofline seconds + grid-dispatch overhead. A MODEL of relative cost
    (monotone ordering is what matters), not a latency prediction."""
    slug = kernel_table.normalize_device_kind(device_kind)
    peak = _PEAK_FLOPS.get(slug, _PEAK_FLOPS["tpu_v5e"])
    bw = _HBM_BYTES_PER_S.get(slug, _HBM_BYTES_PER_S["tpu_v5e"])
    flops = float(cost.get("flops", 0.0) or 0.0)
    nbytes = float(cost.get("bytes accessed", 0.0) or 0.0)
    if kernel == "flash_mha":
        flops += flash_logit_flops(dims, config)
    return (flops / peak + nbytes / bw
            + grid_steps(kernel, dims, config) * GRID_STEP_SECONDS)


def flash_logit_flops(dims, config):
    """The causal tuning program's products over the logits its blocks and
    the walk's tiles inside them compute (``visible_share``). XLA's cost
    analysis gives a Pallas custom-call the same cost whatever its blocks,
    so without this the score could only prefer the fewest grid steps, and a
    block that skips nothing scored like one that skips half."""
    from deepspeed_tpu.ops.pallas.flash_attention import visible_share
    tq, tk = dims["tq"], dims["tk"]
    bq, bk = config["block_q"], config["block_k"]
    share = visible_share(tq, tk, bq, bk, True, None)
    return FLASH_MATMULS * 2.0 * tq * tk * dims["dh"] * FLASH_TUNING_BH * share


def roofline_compute_seconds(flops, bytes_accessed, device_kind="tpu_v5e"):
    """Roofline seconds for a compiled program's cost_analysis() numbers:
    flops over peak plus HBM traffic over bandwidth (the additive form
    ``proxy_score`` uses, minus the grid-dispatch term). Feeds the
    telemetry overlap analyzer's chip-free analytic mode."""
    slug = kernel_table.normalize_device_kind(device_kind)
    peak = _PEAK_FLOPS.get(slug, _PEAK_FLOPS["tpu_v5e"])
    bw = _HBM_BYTES_PER_S.get(slug, _HBM_BYTES_PER_S["tpu_v5e"])
    return float(flops) / peak + float(bytes_accessed) / bw


def comm_roofline_seconds(op, nbytes, n=None, device_kind="tpu_v5e"):
    """Modeled seconds for one collective of ``nbytes`` payload across
    ``n`` participants, using the ring busbw factors from
    ``utils/comms_logging.calc_bw_log`` — all_reduce moves 2(n-1)/n of the
    payload over the wire, gather/scatter/all-to-all (n-1)/n, point-to-point
    the payload itself — over the chip's aggregate ICI bandwidth, plus a
    fixed launch latency. Unknown ``n`` uses the asymptotic factor."""
    slug = kernel_table.normalize_device_kind(device_kind)
    link = LINK_BYTES_PER_S.get(slug, LINK_BYTES_PER_S["tpu_v5e"])
    op = str(op)
    if op in ("all_reduce", "psum"):
        factor = (2.0 * (n - 1) / n) if n and n > 1 else 2.0
    elif op in ("all_gather", "reduce_scatter", "all_to_all",
                "psum_scatter"):
        factor = ((n - 1) / n) if n and n > 1 else 1.0
    else:  # broadcast / permute / send / recv: payload over the wire once
        factor = 1.0
    return float(nbytes) * factor / link + _COMM_LATENCY_S


def chip_free_rank(kernel, dims, dtype, candidates=None, compile_fn=None,
                   topology_name="v5e:2x2", device_kind=None):
    """Rank candidates without silicon. Returns (ranking, device_kind):
    ranking is a list of per-candidate records sorted best-first (feasible
    by ascending score, then infeasible), each
    ``{"blocks", "feasible", "score", "compile_s", "flops",
    "bytes_accessed", "temp_bytes", "error"}``.

    ``compile_fn`` is injectable for CPU-fast tests; the default compiles
    via the AOT topology client (``make_aot_compiler``).
    """
    if candidates is None:
        candidates = candidate_space(kernel, dims, dtype)
    if compile_fn is None:
        compile_fn, device_kind = make_aot_compiler(topology_name)
    elif device_kind is None:
        device_kind = topology_name.split(":")[0]

    ranking = []
    for config in candidates:
        rec = {"blocks": dict(config), "feasible": False, "score": None,
               "compile_s": None, "flops": None, "bytes_accessed": None,
               "temp_bytes": None, "error": None}
        est = vmem_bytes(kernel, dims, dtype, config)
        if est > VMEM_BUDGET:
            rec["error"] = (f"vmem estimate {est} > budget {VMEM_BUDGET} "
                            f"(skipped without compiling)")
            ranking.append(rec)
            continue
        t0 = time.perf_counter()
        try:
            fn, abstract = build_program(kernel, dims, dtype, config)
            cost, mem = compile_fn(fn, abstract)
        except Exception as e:
            rec["compile_s"] = round(time.perf_counter() - t0, 2)
            rec["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            ranking.append(rec)
            continue
        rec["compile_s"] = round(time.perf_counter() - t0, 2)
        rec["feasible"] = True
        rec["flops"] = float(cost.get("flops", 0.0) or 0.0)
        rec["bytes_accessed"] = float(cost.get("bytes accessed", 0.0) or 0.0)
        if mem is not None:
            rec["temp_bytes"] = getattr(mem, "temp_size_in_bytes", None)
        rec["score"] = proxy_score(kernel, dims, dtype, config, cost,
                                   device_kind)
        ranking.append(rec)
    ranking.sort(key=lambda r: (not r["feasible"],
                                r["score"] if r["score"] is not None else 0.0))
    return ranking, device_kind


# ---------------------------------------------------------------------------
# on-chip mode
# ---------------------------------------------------------------------------

def onchip_rank(kernel, dims, dtype, candidates=None, iters=10, warmup=2):
    """Timed sweep on the live TPU backend (ground truth). Each feasible
    candidate runs ``iters`` times; the median wall time is its score."""
    import jax
    import numpy as np

    plat = jax.devices()[0].platform
    if plat != "tpu":
        raise RuntimeError(f"on-chip tuning needs a live TPU backend, "
                           f"got {plat!r} — use chip-free mode")
    if candidates is None:
        candidates = candidate_space(kernel, dims, dtype)
    device_kind = jax.devices()[0].device_kind

    ranking = []
    for config in candidates:
        rec = {"blocks": dict(config), "feasible": False, "score": None,
               "compile_s": None, "error": None}
        if vmem_bytes(kernel, dims, dtype, config) > VMEM_BUDGET:
            rec["error"] = "vmem estimate over budget (skipped)"
            ranking.append(rec)
            continue
        try:
            fn, abstract = build_program(kernel, dims, dtype, config)
            rng = np.random.default_rng(0)

            def concrete(a):
                if np.issubdtype(np.dtype(a.dtype), np.integer):
                    return jax.numpy.zeros(a.shape, a.dtype)
                return jax.numpy.asarray(
                    rng.standard_normal(a.shape).astype("float32"), a.dtype)
            args = jax.tree.map(concrete, abstract)
            jitted = jax.jit(fn)  # graftlint: allow[GL101] the tuner compiles each candidate config on purpose — compile_s is part of the score
            t0 = time.perf_counter()
            jax.block_until_ready(jitted(*args))
            rec["compile_s"] = round(time.perf_counter() - t0, 2)
            for _ in range(warmup):
                jax.block_until_ready(jitted(*args))
            times = []
            for _ in range(iters):
                t0 = time.perf_counter()
                jax.block_until_ready(jitted(*args))
                times.append(time.perf_counter() - t0)
            rec["feasible"] = True
            rec["score"] = float(np.median(times))
        except Exception as e:
            rec["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        ranking.append(rec)
    ranking.sort(key=lambda r: (not r["feasible"],
                                r["score"] if r["score"] is not None else 0.0))
    return ranking, device_kind


# ---------------------------------------------------------------------------
# full sweep -> table entries + ranking artifact
# ---------------------------------------------------------------------------

def tune(mode="chip-free", kernels=None, shapes=None, compile_fn=None,
         topology_name="v5e:2x2", iters=10):
    """Sweep every (kernel, bench shape) and pick winners.

    Returns ``(entries, report)``: ``entries`` feeds
    ``kernel_table.save_table``; ``report`` is the full per-candidate
    ranking for the ``onchip_results/`` artifact. Deterministic for a fixed
    mode/backend — same inputs, same table.
    """
    shapes = shapes if shapes is not None else kernel_table.BENCH_SHAPES
    kernels = list(kernels) if kernels else list(kernel_table.KERNEL_KNOBS)
    entries, report = {}, {"mode": mode, "topology": topology_name,
                           "sweeps": []}
    device_kind = None
    for kernel in kernels:
        for dims, dtype in shapes.get(kernel, []):
            if mode == "chip-free":
                ranking, device_kind = chip_free_rank(
                    kernel, dims, dtype, compile_fn=compile_fn,
                    topology_name=topology_name, device_kind=device_kind)
            elif mode == "on-chip":
                ranking, device_kind = onchip_rank(kernel, dims, dtype,
                                                   iters=iters)
            else:
                raise ValueError(f"mode must be chip-free|on-chip, "
                                 f"got {mode!r}")
            key = kernel_table.bucket_key(kernel, dims, dtype)
            sweep = {"kernel": kernel, "dims": dict(dims),
                     "dtype": str(dtype), "bucket_key": key,
                     "candidates": ranking}
            report["sweeps"].append(sweep)
            best = next((r for r in ranking if r["feasible"]), None)
            if best is not None:
                entries[key] = {"blocks": best["blocks"], "mode": mode,
                                "score": best["score"],
                                "dims": dict(dims)}
    report["device_kind"] = kernel_table.normalize_device_kind(
        device_kind or topology_name.split(":")[0])
    return entries, report
