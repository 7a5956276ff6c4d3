"""On-chip serving benchmarks: SplitFuse throughput, traffic replay, W8A16.

VERDICT r2 #9 plus the serving-observability stream (PR 6):

- ``serving_bench`` — fixed prompt/decode mix, peak tokens/s (the original
  throughput number).
- ``--replay`` — a seeded traffic-replay harness: heavy-tailed
  (lognormal) prompt/output-length mixes and Poisson or burst arrival
  schedules, submitted on a wall clock against the live scheduler. Emits the
  latency numbers a serving stack is actually judged on — p50/p99 TTFT,
  p50/p99 TPOT, tokens/s/chip, peak KV-block occupancy — sourced from the
  telemetry serving histograms/gauges, and gated by scripts/perf_gate.py.
- ``w8a16_check`` — fused W8A16 quantized matmul vs the fp reference.

Prints ONE JSON line per section plus stderr progress. ``DS_TPU_TELEMETRY=1``
additionally embeds the full telemetry summary in each payload's ``extra``
(same contract as bench.py; docs/OBSERVABILITY.md has the schema).

- ``--replay --prefix-mix`` — shared system-prompt pools: the same seeded
  trace runs with ``prefix_caching`` off then on, and the payload reports the
  prefill-token reduction, prefix hit rate, and TTFT comparison the prefix
  cache is judged on (gated by perf_gate's prefix checks).

- ``--speculate`` — draft-then-verify decode: the same seeded
  template-heavy greedy trace runs with speculation off then on (n-gram
  prompt-lookup drafting, verification through the ragged prefill kernel).
  Reports the wall-clock tokens/s multiplier, accept rate, verify-batch
  occupancy, and the greedy bit-exactness flag — gated by perf_gate's
  ``check_speculate_baseline`` (multiplier >= 1.5x, parity must hold).

- ``--long-context`` — KV capacity-tiering workload: seeded long prompts
  (32k–128k on TPU; scaled down on CPU) over a shared prefix, driven at an
  EQUAL KV HBM byte budget with fp then int8 KV pages, host-DRAM spill tier
  on. Reports concurrent max-context sequences per chip (the >= 2x int8
  capacity ratchet), swap-in stall seconds, the swap accounting identity,
  and the prefill reduction across a spill/restore round trip — gated by
  perf_gate's ``check_longctx_baseline`` and ``--max-swap-stall-growth``.

- ``--replay --fleet`` — serving-fleet replay: the same seeded trace runs
  twice — once against a single scheduler at its saturation rate, then
  against an ``SLORouter`` over a ``PrefillDecodeFleet`` (prefill/decode
  disaggregation with KV-page handoffs) at DOUBLE the offered rate. The
  payload reports the sustained-rate multiplier, both legs' TTFT/TPOT
  percentiles, the shed rate, and the page-handoff accounting
  (pages shipped == pages bound; bytes; latency), gated by perf_gate's
  fleet checks.

- ``--fleet --two-process`` — KV fabric microbench: a prefix-mix trace
  runs four legs — monolithic reference, in-process fleet on the
  serialized ``wire`` codec with delta-shipping OFF then ON (with
  ``FlowControl``), and a ``TwoProcessFleet`` leg where decode lives in a
  SEPARATE OS process and every KV page crosses a pipe as a framed,
  CRC32-checked wire message. The payload reports the int8-wire-to-fp32
  byte ratio, the delta-shipping savings, CRC failure counts, and greedy
  parity of every leg against the reference — gated by perf_gate's
  ``check_kvfabric_baseline``.

- ``--diurnal --chaos [SPEC]`` — elastic-fleet chaos replay: the SLO
  router + prefill/decode fleet + ``FleetAutoscaler`` drive a seeded
  diurnal trace with fault injection armed (a decode replica dies
  mid-stream, a handoff transfer drops, a replica stalls). Reports goodput
  per replica-second, re-admission/leak accounting, and per-class shedding
  — gated by perf_gate's ``check_chaos_baseline``.

Usage: python scripts/bench_serving.py [--replay] [--prefix-mix] [--fleet]
           [--speculate] [--long-context] [--longctx-max T]
           [--requests N] [--seed S] [--arrival poisson|burst] [--rate R]
           [--burst-size B] [--prompt T] [--new T]
           [--prefix-pools P] [--prefix-len L]
           [--fleet-prefill N] [--fleet-decode N] [--two-process]
           [--chaos [SPEC]] [--diurnal] [--diurnal-period T]
           [--diurnal-depth D]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # repo-root bench.py: emit


def _embed_telemetry(extra):
    """DS_TPU_TELEMETRY=1 -> fold the unified-telemetry summary into the
    payload (bench.py behavior)."""
    if os.environ.get("DS_TPU_TELEMETRY") != "1":
        return
    from deepspeed_tpu import telemetry
    extra["telemetry"] = telemetry.summary()


#: default two-class SLO mix for --replay / --fleet: an interactive class
#: with tight targets and a throughput-oriented batch class (docs/SERVING.md
#: "SLO classes"). Replay requests alternate classes deterministically so
#: the same seed yields the same per-class populations. Targets are
#: CPU-replay scale — 2x above the worst observed mid-run compile stall
#: (~1.6 s on the CPU grid) so a one-off stall does not violate, tight
#: enough that a real scheduling regression drags attainment under the
#: perf gate's 0.9 ratchet (onchip_results/serving_slo_baseline.json).
REPLAY_SLO_CLASSES = {
    "interactive": {"ttft_target_s": 4.0, "tpot_target_s": 3.0,
                    "attainment_target": 0.9},
    "batch": {"ttft_target_s": 30.0, "tpot_target_s": 10.0,
              "attainment_target": 0.9},
}


def _assign_slo_classes(n_req):
    """Deterministic per-request class assignment (alternating)."""
    names = sorted(REPLAY_SLO_CLASSES)  # ["batch", "interactive"]
    return [names[(i + 1) % len(names)] for i in range(n_req)]


def _slo_classes_extra(tm):
    """Per-class attainment + TTFT/TPOT percentiles for a bench payload
    (None when no SLO observations landed). perf_gate validates the shape
    and gates the minimum attainment."""
    from deepspeed_tpu import telemetry
    snap = telemetry.slo_snapshot()
    if not snap:
        return None
    out = {}
    for cls, entry in snap.items():
        e = dict(entry)
        pcts = {}
        for metric in ("ttft", "tpot"):
            p = tm.hist_percentiles(f"serving/{metric}_s/{cls}")
            if p is not None:
                pcts[metric] = {"p50_s": round(p[0], 6),
                                "p95_s": round(p[1], 6),
                                "p99_s": round(p[2], 6)}
        if pcts:
            e["percentiles"] = pcts
        out[cls] = e
    return out


def _min_attainment(slo):
    """Worst per-class/per-metric attainment in a ``slo_classes`` section
    (the number ``perf_gate --min-slo-attainment`` gates)."""
    vals = [m["attainment"] for e in (slo or {}).values()
            for m in e.get("metrics", {}).values()]
    return min(vals) if vals else None


def _build_stack(cfg, n_req, prompt_len, new_tokens, budget, on_tpu,
                 num_kv_blocks=None, prefix_caching=False, kv_dtype="fp",
                 host_kv_blocks=0, model_and_params=None, speculative=None,
                 slo_classes=None):
    import jax
    import numpy as np
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    if model_and_params is None:
        model = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg.vocab_size, (1, 8)).astype(np.int32)
        params = model.init(jax.random.PRNGKey(0),
                            {"input_ids": ids})["params"]
    else:
        model, params = model_and_params

    block = 32 if on_tpu else 8
    max_ctx = prompt_len + new_tokens + block
    if num_kv_blocks is None:
        num_kv_blocks = max(64, (max_ctx // block + 2) * n_req)
    config = {
        "state_manager": {
            "max_ragged_sequence_count": max(4, n_req) + 1,  # +1 warmup
            "max_ragged_batch_size": budget,
            "max_context": max_ctx,
            "num_kv_blocks": num_kv_blocks,
            "kv_dtype": kv_dtype,
            "host_kv_blocks": host_kv_blocks},
        "kv_cache": {"block_size": block,
                     "cache_dtype": "bf16" if on_tpu else "fp32"},
        "prefix_caching": prefix_caching}
    if speculative is not None:
        config["speculative"] = speculative
    if slo_classes:
        config["slo_classes"] = dict(slo_classes)
    engine = InferenceEngineV2(model, params, config=config)
    return model, SplitFuseScheduler(engine, token_budget=budget)


def serving_bench(args, on_tpu):
    import numpy as np
    from deepspeed_tpu.models.llama import LlamaConfig

    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=args.prompt + args.new + 64,
                          remat=False)
        n_req, prompt_len, new_tokens = args.requests, args.prompt, args.new
        budget = 256
    else:
        cfg = LlamaConfig.tiny(remat=False)
        n_req, prompt_len, new_tokens, budget = 2, 24, 4, 16

    model, sched = _build_stack(cfg, n_req, prompt_len, new_tokens, budget,
                                on_tpu)
    rng = np.random.default_rng(0)
    prompts = {u: rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
               for u in range(n_req)}

    # warmup round (compile) with one request
    t0 = time.perf_counter()
    sched.submit(10_000, prompts[0], max_new_tokens=2)
    sched.run_to_completion()
    print(f"serving: warmup/compile {time.perf_counter()-t0:.1f}s",
          file=sys.stderr)

    for u, p in prompts.items():
        sched.submit(u, p, max_new_tokens=new_tokens)
    t0 = time.perf_counter()
    got = sched.run_to_completion()
    dt = time.perf_counter() - t0
    # count ONLY the timed requests — run_to_completion also returns the
    # warmup uid, whose tokens were generated before the timer started
    decoded = sum(len(got[u]) for u in prompts)
    total = decoded + n_req * prompt_len
    extra = {"decode_tokens_per_sec": round(decoded / dt, 1),
             "requests": n_req, "prompt_len": prompt_len,
             "new_tokens": new_tokens, "token_budget": budget,
             "wall_s": round(dt, 2),
             "model": f"llama-{cfg.hidden_size}x{cfg.num_hidden_layers}"}
    _embed_telemetry(extra)
    payload = {
        "metric": "splitfuse_serving_tokens_per_sec",
        "value": round(total / dt, 1),
        "unit": "tokens/s (prefill+decode)",
        "vs_baseline": None,
        "extra": extra,
    }
    bench.emit(payload)
    return payload


def make_workload(n_req, seed, arrival="poisson", rate=4.0, burst_size=4,
                  prompt_scale=256, new_scale=64, max_prompt=2048,
                  max_new=512):
    """Seeded request trace: heavy-tailed lengths + an arrival schedule.

    Lengths are lognormal (the shape real prompt/completion mixes follow —
    most requests short, a fat tail of long ones). Arrivals are either
    ``poisson`` (exponential gaps at ``rate`` req/s — open-loop steady
    traffic) or ``burst`` (groups of ``burst_size`` land simultaneously,
    groups spaced to the same average rate — the queue-depth stress case).
    Same seed -> identical trace, so perf_gate compares like against like.
    """
    import numpy as np
    gen = np.random.default_rng(seed)
    prompt_lens = np.clip(
        gen.lognormal(np.log(prompt_scale), 0.7, n_req), 4, max_prompt
    ).astype(np.int64)
    out_lens = np.clip(
        gen.lognormal(np.log(new_scale), 0.6, n_req), 1, max_new
    ).astype(np.int64)
    if arrival == "poisson":
        arrivals = np.cumsum(gen.exponential(1.0 / rate, n_req))
    elif arrival == "burst":
        n_groups = -(-n_req // burst_size)
        group_t = np.arange(n_groups) * (burst_size / rate)
        arrivals = np.repeat(group_t, burst_size)[:n_req]
    else:
        raise ValueError(f"unknown arrival schedule {arrival!r}")
    arrivals -= arrivals[0]  # first request lands at t=0
    return prompt_lens, out_lens, arrivals


def _drive_replay(sched, prompts, out_lens, arrivals, slo_classes=None):
    """Open-loop wall-clock submission of a request trace against the live
    scheduler (uids = trace indices). ``slo_classes`` optionally maps each
    trace index to its SLO class name. Returns the wall seconds."""
    n_req = len(prompts)
    t_start = time.perf_counter()
    nxt = 0
    while nxt < n_req or sched.has_work:
        now = time.perf_counter() - t_start
        while nxt < n_req and arrivals[nxt] <= now:
            kw = {}
            if slo_classes is not None:
                kw["slo_class"] = slo_classes[nxt]
            sched.submit(nxt, prompts[nxt],
                         max_new_tokens=int(out_lens[nxt]), **kw)
            nxt += 1
        if sched.has_work:
            sched.step()
        elif nxt < n_req:
            # open-loop: idle until the next arrival is due
            time.sleep(min(float(arrivals[nxt]) - now, 0.05))
    return time.perf_counter() - t_start


def _precompile_batch_grid(sched, n_req, budget):
    """Compile every (sequence-bucket, token-bucket) batch shape the replay
    can reach, directly through ``put_sampled`` (the scheduler's only device
    path). ``RaggedBatchWrapper.build`` buckets S and Q to powers of two
    (min 4 / 8, capped at the config maxima), so the reachable grid is small
    and enumerable — compiling it up front makes the measured legs
    compile-free regardless of how arrival timing composes the batches.
    Sequences use throwaway uids and are flushed afterwards."""
    import numpy as np
    eng = sched._engine
    sm = eng._config.state_manager
    max_s = min(sm.max_ragged_sequence_count, n_req)
    s_vals, s = [], 4
    while s < max_s:
        s_vals.append(s)
        s *= 2
    s_vals.append(max_s)
    q_vals, q = [], 8
    while q < budget:
        q_vals.append(q)
        q *= 2
    q_vals.append(budget)
    for n in s_vals:
        for qb in q_vals:
            if qb < n:
                continue  # can't give every sequence a token
            # compose a batch totalling EXACTLY qb tokens so the wrapper
            # buckets it to (bucket(n), qb) — one chunk takes the slack,
            # the rest decode one token. Covers pure-decode rounds
            # (qb == min bucket) as well as chunked-prefill mixes; a shape
            # missed here cold-compiles inside the measured leg
            longest = qb - (n - 1)
            uids = list(range(90_000, 90_000 + n))
            toks = [np.zeros(longest, np.int32)] + \
                [np.zeros(1, np.int32)] * (n - 1)
            eng.put_sampled(uids, toks, temperatures=[0.0] * n,
                            top_ks=[0] * n, top_ps=[1.0] * n,
                            seeds=[0] * n, positions=[0] * n)
            for u in uids:
                eng.flush(u)


def prefix_mix_bench(args, on_tpu):
    """Shared-system-prompt replay: every request's prompt = one of
    ``--prefix-pools`` seeded pool prefixes + a private lognormal suffix.
    Runs the SAME trace twice — ``prefix_caching`` off, then on — so the
    payload carries a like-for-like prefill-token and TTFT comparison at an
    identical seed. Emits one ``serving_replay_tokens_per_sec_per_chip``
    payload (value = cached leg) whose extra adds the prefix-cache fields
    perf_gate validates (hit rate, tokens saved/executed, reduction,
    nocache TTFT)."""
    import jax
    import numpy as np
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.models.llama import LlamaConfig

    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=args.prompt + args.new + 64,
                          remat=False)
        n_req, block = args.requests, 32
        prefix_len = args.prefix_len or 256
        suffix_scale, max_suffix = 32, 128
        new_scale, max_new = args.new, args.new * 2
        budget, rate = 256, args.rate
    else:
        cfg = LlamaConfig.tiny(remat=False)
        n_req, block = min(args.requests, 16), 8
        prefix_len = args.prefix_len or 40
        suffix_scale, max_suffix = 6, 16
        new_scale, max_new = 2, 4
        budget, rate = 48, max(args.rate, 200.0)
    prefix_len -= prefix_len % block  # block-aligned prefixes share fully
    n_pools = max(1, args.prefix_pools)

    suffix_lens, out_lens, arrivals = make_workload(
        n_req, args.seed, arrival=args.arrival, rate=rate,
        burst_size=args.burst_size, prompt_scale=suffix_scale,
        new_scale=new_scale, max_prompt=max_suffix, max_new=max_new)
    gen = np.random.default_rng(args.seed)
    pools = [gen.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
             for _ in range(n_pools)]
    assign = gen.integers(0, n_pools, n_req)
    prompts = [np.concatenate([
        pools[assign[i]],
        gen.integers(0, cfg.vocab_size, int(suffix_lens[i])).astype(np.int32)])
        for i in range(n_req)]
    prompt_total = int(sum(len(p) for p in prompts))

    legs = {}
    for label, caching in (("nocache", False), ("cached", True)):
        model, sched = _build_stack(cfg, n_req, prefix_len + max_suffix,
                                    int(max_new), budget, on_tpu,
                                    prefix_caching=caching)
        # warmup: compile the full reachable batch-shape grid before the
        # clock starts. The cached leg fuses more, shorter chunks per
        # forward and so composes different (seqs, tokens) buckets than the
        # nocache leg — a trace-shaped warmup chases a moving target, the
        # grid covers both legs by construction
        t0 = time.perf_counter()
        _precompile_batch_grid(sched, n_req, budget)
        print(f"prefix-mix[{label}]: warmup/compile "
              f"{time.perf_counter()-t0:.1f}s", file=sys.stderr)
        # the warmup batches must not pollute the comparison: zero the
        # prefill counters and drop their donated blocks + match stats so
        # the measured leg starts with a cold, empty cache
        sched.prefill_tokens_executed = 0
        sched.prefill_tokens_saved = 0
        cache = sched._engine._state.prefix_cache
        if cache is not None:
            cache.evict(cache.evictable_blocks)
            cache.hits = cache.misses = cache.tokens_saved = 0
            cache.insertions = cache.evictions = 0
        telemetry.reset()
        telemetry.configure(enabled=True,
                            chrome_trace_path=os.environ.get(
                                "DS_TPU_TELEMETRY_TRACE", ""))
        tm = telemetry.get_telemetry()
        wall = _drive_replay(sched, prompts, out_lens, arrivals)
        decoded = sum(len(r.generated) for u, r in sched._requests.items()
                      if u < 10_000)
        ttft = tm.hist_percentiles("serving/ttft_s", (0.5, 0.99)) or (0.0, 0.0)
        tpot = tm.hist_percentiles("serving/tpot_s", (0.5, 0.99)) or (0.0, 0.0)
        serving = telemetry.summary()["serving"]
        kv_gauge = serving["gauges"].get("serving/kv_occupancy", {})
        cached_gauge = serving["gauges"].get("serving/cached_blocks", {})
        legs[label] = {
            "wall": wall, "decoded": decoded,
            "executed": sched.prefill_tokens_executed,
            "saved": sched.prefill_tokens_saved,
            "ttft": ttft, "tpot": tpot,
            "kv_peak": float(kv_gauge.get("peak", 0.0)),
            "cached_blocks_peak": float(cached_gauge.get("peak", 0.0)),
            "hit_rate": cache.hit_rate if cache is not None else 0.0,
            "preemptions": int(serving["requests"].get("preempted", 0)),
        }
    c, nc = legs["cached"], legs["nocache"]
    reduction = (nc["executed"] - c["executed"]) / nc["executed"] \
        if nc["executed"] else 0.0
    total = c["decoded"] + prompt_total
    n_chips = jax.device_count()
    extra = {
        "ttft_p50_s": round(c["ttft"][0], 6),
        "ttft_p99_s": round(c["ttft"][1], 6),
        "tpot_p50_s": round(c["tpot"][0], 6),
        "tpot_p99_s": round(c["tpot"][1], 6),
        "tokens_per_sec": round(total / c["wall"], 1),
        "decode_tokens_per_sec": round(c["decoded"] / c["wall"], 1),
        "peak_kv_occupancy": round(c["kv_peak"], 6),
        "preemptions": c["preemptions"],
        "requests": n_req, "seed": args.seed, "arrival": args.arrival,
        "rate_req_per_s": rate,
        "prompt_tokens_total": prompt_total,
        "decode_tokens_total": int(c["decoded"]),
        "wall_s": round(c["wall"], 2), "chips": n_chips,
        "model": f"llama-{cfg.hidden_size}x{cfg.num_hidden_layers}",
        # prefix-cache comparison (same trace, caching off vs on)
        "prefix_pools": n_pools, "prefix_len": prefix_len,
        "prefix_hit_rate": round(c["hit_rate"], 6),
        "prefill_tokens_saved": int(c["saved"]),
        "executed_prefill_tokens": int(c["executed"]),
        "executed_prefill_tokens_nocache": int(nc["executed"]),
        "prefill_reduction": round(reduction, 6),
        "ttft_p50_nocache_s": round(nc["ttft"][0], 6),
        "ttft_p99_nocache_s": round(nc["ttft"][1], 6),
        "wall_nocache_s": round(nc["wall"], 2),
        "cached_blocks_peak": int(c["cached_blocks_peak"]),
    }
    _embed_telemetry(extra)
    payload = {
        "metric": "serving_replay_tokens_per_sec_per_chip",
        "value": round(total / c["wall"] / max(n_chips, 1), 1),
        "unit": "tokens/s/chip (prefill+decode)",
        "vs_baseline": None,
        "extra": extra,
    }
    bench.emit(payload)
    return payload


def speculate_bench(args, on_tpu):
    """Draft-then-verify replay: the SAME seeded template-heavy greedy trace
    runs twice — speculation off, then on (n-gram self-speculation drafting
    through the ragged verify kernel) — and the payload reports the
    wall-clock tokens/s multiplier the second leg buys, the accept rate,
    verify-batch occupancy, and the greedy bit-exactness flag (speculate leg
    stream == plain leg stream, the correctness oracle). The workload is a
    tiled 4-token pattern: template-heavy in the way the prompt-lookup
    drafter exploits, and single-row so both legs pad to the same ragged
    token bucket and the comparison isolates round-count savings. Emits one
    ``serving_speculate_tokens_per_sec_multiplier`` payload gated by
    perf_gate's ``check_speculate_baseline`` (multiplier >= 1.5x)."""
    import numpy as np
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.models.llama import LlamaConfig

    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=4096, remat=False)
        tile_reps, max_new, budget = 64, max(args.new, 96), 256
    else:
        # tiny() shape, but with room for the 40-token prompt + 96 new
        cfg = LlamaConfig(vocab_size=512, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=256, remat=False)
        tile_reps, max_new, budget = 10, 96, 32
    seed = args.seed or 31
    max_drafts = 7  # k_max buckets to 8 either way; wider drafts are free
    gen = np.random.default_rng(seed)
    prompt = np.tile(gen.integers(0, cfg.vocab_size, 4).astype(np.int32),
                     tile_reps)
    reps = 3  # sequential timed repetitions per leg; min wall wins

    legs = {}
    for label, spec in (
            ("plain", None),
            ("speculate", {"enabled": True,
                           "max_draft_tokens": max_drafts})):
        model, sched = _build_stack(cfg, reps, len(prompt), max_new, budget,
                                    on_tpu, speculative=spec)
        t0 = time.perf_counter()
        sched.submit(10_000, prompt, max_new_tokens=max_new)
        sched.run_to_completion()
        print(f"speculate[{label}]: warmup/compile "
              f"{time.perf_counter()-t0:.1f}s", file=sys.stderr)
        sched.speculated_tokens = 0
        sched.accepted_tokens = 0
        sched.rejected_tokens = 0
        telemetry.reset()
        telemetry.configure(enabled=True,
                            chrome_trace_path=os.environ.get(
                                "DS_TPU_TELEMETRY_TRACE", ""))
        walls = []
        for r in range(reps):
            t0 = time.perf_counter()
            sched.submit(r, prompt, max_new_tokens=max_new)
            sched.run_to_completion()
            walls.append(time.perf_counter() - t0)
        serving = telemetry.summary()["serving"]
        occ = serving["gauges"].get("serving/verify_batch_occupancy", {})
        ar = serving["gauges"].get("serving/accept_rate", {})
        legs[label] = {
            "wall": min(walls), "walls": walls,
            "stream": [int(t) for t in sched.results()[0]],
            "speculated": int(sched.speculated_tokens),
            "accepted": int(sched.accepted_tokens),
            "rejected": int(sched.rejected_tokens),
            "tokens_per_round": float(sched.tokens_per_round()),
            "verify_occ_peak": float(occ.get("peak", 0.0)),
            "accept_rate_gauge": float(ar.get("last", 0.0)),
        }
        print(f"speculate[{label}]: walls="
              f"{[round(w, 3) for w in walls]} "
              f"tokens_per_round={legs[label]['tokens_per_round']:.2f}",
              file=sys.stderr)
    pl, sp = legs["plain"], legs["speculate"]
    multiplier = pl["wall"] / sp["wall"] if sp["wall"] else 0.0
    accept_rate = sp["accepted"] / max(sp["speculated"], 1)
    parity = pl["stream"] == sp["stream"]
    decoded = len(sp["stream"]) * reps
    extra = {
        "tokens_per_sec_multiplier": round(multiplier, 4),
        "accept_rate": round(accept_rate, 6),
        "verify_batch_occupancy": round(sp["verify_occ_peak"], 6),
        "greedy_parity": bool(parity),
        "speculated_tokens": sp["speculated"],
        "accepted_tokens": sp["accepted"],
        "rejected_tokens": sp["rejected"],
        "tokens_per_round": round(sp["tokens_per_round"], 4),
        "decode_tokens_per_sec": round(decoded / sp["wall"], 1),
        "decode_tokens_per_sec_plain": round(decoded / pl["wall"], 1),
        "wall_s": round(sp["wall"], 4),
        "wall_plain_s": round(pl["wall"], 4),
        "walls_s": [round(w, 4) for w in sp["walls"]],
        "walls_plain_s": [round(w, 4) for w in pl["walls"]],
        "repetitions": reps, "seed": seed,
        "prompt_len": int(len(prompt)), "new_tokens": max_new,
        "max_draft_tokens": max_drafts, "token_budget": budget,
        "model": f"llama-{cfg.hidden_size}x{cfg.num_hidden_layers}",
    }
    _embed_telemetry(extra)
    payload = {
        "metric": "serving_speculate_tokens_per_sec_multiplier",
        "value": round(multiplier, 4),
        "unit": "x (plain wall / speculate wall, same greedy trace)",
        "vs_baseline": None,
        "extra": extra,
    }
    bench.emit(payload)
    return payload


def long_context_bench(args, on_tpu):
    """Long-context KV capacity tiering: seeded long prompts over shared
    prefix pools, driven twice at an EQUAL KV HBM byte budget — fp pages,
    then int8 pages + per-row fp32 scales — both with prefix caching and
    the host-DRAM spill tier on. Each leg runs three deterministic waves:
    warm (park the shared prefixes), pressure (private long prompts force
    the parked blocks through the spill path), reuse (shared-prefix
    requests revive spilled chains from host DRAM). The payload reports
    the capacity ratchet (concurrent sequences per chip at the shared
    budget, fp vs int8) plus the host-tier numbers from the pressured fp
    leg: swap-in stall seconds, the swap accounting identity
    (swapped_out == swapped_in + swap_dropped + resident_host_blocks),
    host occupancy, and ``swap_outs_live == 0`` — no live sequence ever
    paid for pressure while parked blocks could. Gated by perf_gate's
    ``check_longctx_baseline`` / ``--max-swap-stall-growth``."""
    import jax
    import numpy as np
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.models.llama import LlamaConfig

    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=args.longctx_max + 256,
                          remat=False)
        block = 32
        prefix_len = args.prefix_len or 32768       # 32k shared prefix
        suffix_scale, max_suffix = 16384, args.longctx_max - prefix_len
        new_tokens = args.new
        n_req, n_filler = args.requests, 2
        budget = 512
    else:
        # CPU leg: the same three-wave shape at toy scale (the prefix-mix
        # pattern) — tiny model, 64-token "long" prefixes, a pool tight
        # enough that wave 2 must spill wave 1's parked prefix blocks
        cfg = LlamaConfig.tiny(remat=False)
        block = 8
        prefix_len = args.prefix_len or 64
        suffix_scale, max_suffix = 12, 24
        new_tokens = 2
        n_req, n_filler = min(args.requests, 6), 2
        budget = 48
    prefix_len -= prefix_len % block  # block-aligned prefixes share fully
    max_ctx = prefix_len + max_suffix + new_tokens + block

    # equal HBM budget: size the fp pool to hold ~1.5 max-context sequences
    # (so wave-2 pressure exists), then give the int8 leg the SAME bytes
    num_layers = cfg.num_hidden_layers
    kv_heads = cfg.num_key_value_heads
    head_dim = cfg.hidden_size // cfg.num_attention_heads
    fp_elt = 2.0 if on_tpu else 4.0                 # bf16 / fp32 pages
    q_elt = 1.0 + 4.0 / head_dim                    # int8 page + fp32 scale
    blk_tokens = 2 * num_layers * block * kv_heads * head_dim
    ctx_blocks = -(-max_ctx // block)
    fp_blocks = int(ctx_blocks * 1.5)
    budget_bytes = int(fp_blocks * blk_tokens * fp_elt)
    q_blocks = int(budget_bytes // (blk_tokens * q_elt))
    host_blocks = 4 * ctx_blocks

    seed_gen = np.random.default_rng(args.seed)
    pool_prefix = seed_gen.integers(
        0, cfg.vocab_size, prefix_len).astype(np.int32)
    suffix_lens = np.clip(seed_gen.lognormal(
        np.log(suffix_scale), 0.6, n_req), 4, max_suffix).astype(np.int64)
    reuse_prompts = [np.concatenate([
        pool_prefix,
        seed_gen.integers(0, cfg.vocab_size,
                          int(suffix_lens[i])).astype(np.int32)])
        for i in range(n_req)]
    filler_prompts = [seed_gen.integers(
        0, cfg.vocab_size,
        prefix_len + max_suffix).astype(np.int32) for _ in range(n_filler)]
    prompt_total = int(sum(len(p) for p in reuse_prompts)
                       + sum(len(p) for p in filler_prompts) + prefix_len + 4)

    legs = {}
    for label, kv_dtype, blocks in (("fp", "fp", fp_blocks),
                                    ("int8", "int8", q_blocks)):
        model, sched = _build_stack(
            cfg, n_req + n_filler + 1, prefix_len + max_suffix, new_tokens,
            budget, on_tpu, num_kv_blocks=blocks, prefix_caching=True,
            kv_dtype=kv_dtype, host_kv_blocks=host_blocks)
        engine = sched._engine
        t0 = time.perf_counter()
        _precompile_batch_grid(sched, n_req + n_filler + 1, budget)
        print(f"long-context[{label}]: warmup/compile "
              f"{time.perf_counter()-t0:.1f}s", file=sys.stderr)
        sched.prefill_tokens_executed = 0
        sched.prefill_tokens_saved = 0
        cache = engine._state.prefix_cache
        cache.evict(cache.evictable_blocks)
        cache.hits = cache.misses = cache.tokens_saved = 0
        cache.insertions = cache.evictions = 0
        telemetry.reset()
        telemetry.configure(enabled=True,
                            chrome_trace_path=os.environ.get(
                                "DS_TPU_TELEMETRY_TRACE", ""))
        t0 = time.perf_counter()
        # wave 1 — warm: park the shared prefix blocks
        sched.submit(10_000, np.concatenate(
            [pool_prefix,
             seed_gen.integers(0, cfg.vocab_size, 4).astype(np.int32)]),
            max_new_tokens=new_tokens)
        sched.run_to_completion()
        # wave 2 — pressure: private max-length prompts spill the parked
        # prefix chain into the host tier
        for i, p in enumerate(filler_prompts):
            sched.submit(20_000 + i, p, max_new_tokens=new_tokens)
            sched.run_to_completion()
        spilled_after_pressure = engine.kv_stats()["kv_spilled"]
        # wave 3 — reuse: shared-prefix requests revive the spilled chain
        for i, p in enumerate(reuse_prompts):
            sched.submit(i, p, max_new_tokens=new_tokens)
            sched.run_to_completion()
        wall = time.perf_counter() - t0
        if engine._state.kv_cache.swapper is not None:
            engine._state.kv_cache.swapper.drain()  # flush deferred landings

        stats = engine.kv_stats()
        srv = telemetry.summary()["serving"]
        hists = srv["histograms"]

        def hist_total(name):
            h = hists.get(name)
            return (h["count"] * h["mean_s"], h["p50_s"]) if h else (0.0, 0.0)

        swap_in_stall, swap_in_p50 = hist_total("serving/kv_swap_in_s")
        swap_out_stall, _ = hist_total("serving/kv_swap_out_s")
        tm = telemetry.get_telemetry()
        ttft = tm.hist_percentiles("serving/ttft_s", (0.5, 0.99)) or (0.0, 0.0)
        tpot = tm.hist_percentiles("serving/tpot_s", (0.5, 0.99)) or (0.0, 0.0)
        executed = sched.prefill_tokens_executed
        saved = sched.prefill_tokens_saved
        kv = engine._state.kv_cache
        pool_bytes = kv.k_pool.nbytes + kv.v_pool.nbytes
        if kv.quantized:
            pool_bytes += kv.k_scale.nbytes + kv.v_scale.nbytes
        legs[label] = {
            "blocks": blocks, "pool_bytes": int(pool_bytes), "wall": wall,
            "concurrent_seqs": blocks // ctx_blocks,
            "spilled": stats["kv_spilled"],
            "spilled_after_pressure": spilled_after_pressure,
            "restored": stats["kv_restored"],
            "dropped": stats["kv_dropped"],
            "resident_host": stats["host_kv_blocks"],
            "host_occupancy": stats["host_kv_occupancy"],
            "swap_outs_live": stats["swap_outs_live"],
            "swap_in_stall": swap_in_stall, "swap_in_p50": swap_in_p50,
            "swap_out_stall": swap_out_stall,
            "ttft": ttft, "tpot": tpot,
            "executed": executed, "saved": saved,
            "hit_rate": cache.hit_rate,
        }
    fp, q = legs["fp"], legs["int8"]
    n_chips = jax.device_count()
    reduction = fp["saved"] / (fp["saved"] + fp["executed"]) \
        if fp["saved"] + fp["executed"] else 0.0
    extra = {
        # capacity ratchet: same bytes, how many max-context sequences fit
        "concurrent_sequences_per_chip": round(
            q["concurrent_seqs"] / max(n_chips, 1), 4),
        "concurrent_sequences_per_chip_fp": round(
            fp["concurrent_seqs"] / max(n_chips, 1), 4),
        "capacity_multiplier": round(
            q["concurrent_seqs"] / fp["concurrent_seqs"], 4)
        if fp["concurrent_seqs"] else 0.0,
        "kv_hbm_budget_bytes": budget_bytes,
        "fp_blocks": fp["blocks"], "int8_blocks": q["blocks"],
        "fp_pool_bytes": fp["pool_bytes"], "int8_pool_bytes": q["pool_bytes"],
        "max_context_tokens": max_ctx, "blocks_per_sequence": ctx_blocks,
        # host-tier numbers from the pressured fp leg (equal budget -> it
        # must spill; the int8 leg's headroom is the capacity win)
        "swapped_out": fp["spilled"], "swapped_in": fp["restored"],
        "swap_dropped": fp["dropped"],
        "resident_host_blocks": fp["resident_host"],
        "host_kv_occupancy": round(fp["host_occupancy"], 6),
        "host_kv_capacity_blocks": host_blocks,
        "swap_outs_live": fp["swap_outs_live"],
        "swap_in_stall_s": round(fp["swap_in_stall"], 6),
        "swap_in_p50_s": round(fp["swap_in_p50"], 6),
        "swap_out_stall_s": round(fp["swap_out_stall"], 6),
        "spilled_after_pressure": fp["spilled_after_pressure"],
        # serving latency (fp leg headline; int8 leg for comparison)
        "ttft_p50_s": round(fp["ttft"][0], 6),
        "ttft_p99_s": round(fp["ttft"][1], 6),
        "tpot_p50_s": round(fp["tpot"][0], 6),
        "tpot_p99_s": round(fp["tpot"][1], 6),
        "ttft_p50_int8_s": round(q["ttft"][0], 6),
        "ttft_p99_int8_s": round(q["ttft"][1], 6),
        # prefix reuse across the spill/restore round trip
        "prefill_reduction": round(reduction, 6),
        "prefill_tokens_saved": int(fp["saved"]),
        "executed_prefill_tokens": int(fp["executed"]),
        "prefix_hit_rate": round(fp["hit_rate"], 6),
        "int8_swapped_out": q["spilled"], "int8_swapped_in": q["restored"],
        "requests": n_req, "fillers": n_filler, "seed": args.seed,
        "prefix_len": prefix_len, "prompt_tokens_total": prompt_total,
        "wall_s": round(fp["wall"] + q["wall"], 2), "chips": n_chips,
        "model": f"llama-{cfg.hidden_size}x{cfg.num_hidden_layers}",
    }
    _embed_telemetry(extra)
    payload = {
        "metric": "serving_longctx_concurrent_seqs_per_chip",
        "value": round(q["concurrent_seqs"] / max(n_chips, 1), 4),
        "unit": "max-context sequences/chip at the fp leg's KV HBM budget",
        "vs_baseline": None,
        "extra": extra,
    }
    bench.emit(payload)
    return payload


def fleet_replay_bench(args, on_tpu):
    """Serving-fleet replay: single scheduler at saturation rate R, then
    ``SLORouter`` + ``PrefillDecodeFleet`` at rate 2R over the same seeded
    trace (arrival gaps halved). The fleet leg must SUSTAIN the doubled
    rate: perf_gate's fleet baseline ratchet holds the completed-request
    rate multiplier >= 2x and the fleet's TTFT p99 near the single leg's,
    with bounded shedding and exact page-handoff accounting."""
    import jax
    import numpy as np
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference.v2.fleet import SLORouter, PrefillDecodeFleet
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    n_prefill, n_decode = args.fleet_prefill, args.fleet_decode
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=args.prompt + args.new + 64,
                          remat=False)
        n_req = args.requests
        prompt_scale, new_scale = args.prompt // 2, args.new
        max_prompt, max_new = args.prompt, args.new * 4
        budget, rate = 256, args.rate
    else:
        cfg = LlamaConfig.tiny(remat=False)
        n_req = min(args.requests, 32)
        # prompt-heavy with real decode tails: the monolithic leg must pay
        # the decode-interference tax (every live decode row occupies a
        # sequence slot in the shared forward — S-bucket padding plus one
        # budget token per round — throttling prefill), which is the
        # contention disaggregation removes
        prompt_scale, new_scale = 96, 4
        max_prompt, max_new = 256, 8
        # rate well past the single replica's service capacity: the
        # reference leg must be SATURATED for the multiplier to mean
        # anything (an underloaded single replica tracks the offered rate
        # and no fleet can look faster)
        budget, rate = 16, max(args.rate, 400.0)
    # the disaggregation dividend: a monolithic replica must chunk prefill
    # to the small TPOT-bounding budget (decode rows ride every forward),
    # but a prefill-only replica hosts no decodes, so it runs WHOLE-PROMPT
    # chunks (Splitwise/DistServe phase splitting — chunking exists solely
    # to protect decode latency); decode replicas keep the latency budget
    prefill_budget = max(budget * 4, max_prompt)
    if (n_prefill + n_decode) > len(jax.devices()):
        raise RuntimeError(
            f"fleet replay needs {n_prefill + n_decode} devices, have "
            f"{len(jax.devices())} (CPU runs force 8 host devices)")

    prompt_lens, out_lens, arrivals = make_workload(
        n_req, args.seed, arrival=args.arrival, rate=rate,
        burst_size=args.burst_size, prompt_scale=prompt_scale,
        new_scale=new_scale, max_prompt=max_prompt, max_new=max_new)
    gen = np.random.default_rng(args.seed)
    prompts = [gen.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in prompt_lens]
    prompt_total = int(prompt_lens.sum())

    model = LlamaForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 8)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    block = 32 if on_tpu else 8
    max_ctx = int(max_prompt) + int(max_new) + block
    eng_cfg = {
        "state_manager": {"max_ragged_sequence_count": max(4, n_req) + 1,
                          "max_ragged_batch_size": prefill_budget,
                          "max_context": max_ctx,
                          "num_kv_blocks":
                              max(64, (max_ctx // block + 2) * n_req)},
        "kv_cache": {"block_size": block,
                     "cache_dtype": "bf16" if on_tpu else "fp32"},
        "slo_classes": REPLAY_SLO_CLASSES}
    # prefill replicas cap the per-forward sequence count at the minimum
    # S bucket: forward cost scales with the PADDED sequence axis (sampling
    # rows, attention padding), and a prefill-only replica gains nothing
    # from packing many prompts into one chunk — submitted requests beyond
    # the cap wait in the scheduler and ride the next whole-prompt chunk
    prefill_cfg = {
        "state_manager": dict(eng_cfg["state_manager"],
                              max_ragged_sequence_count=4),
        "kv_cache": dict(eng_cfg["kv_cache"]),
        "slo_classes": REPLAY_SLO_CLASSES}
    slo_assign = _assign_slo_classes(n_req)

    def measure(backend, scheds, arr, label):
        """Warm the batch-shape grid on every replica, then drive the trace
        wall-clock with a clean telemetry stream. Returns the leg report."""
        t0 = time.perf_counter()
        for mesh, sched in scheds:
            with mesh:
                _precompile_batch_grid(sched, n_req, sched.budget)
        print(f"fleet[{label}]: warmup/compile {time.perf_counter()-t0:.1f}s",
              file=sys.stderr)
        telemetry.reset()
        telemetry.configure(enabled=True,
                            chrome_trace_path=os.environ.get(
                                "DS_TPU_TELEMETRY_TRACE", ""))
        tm = telemetry.get_telemetry()
        wall = _drive_replay(backend, prompts, out_lens, arr,
                             slo_classes=slo_assign)
        results = backend.results()
        decoded = int(sum(len(v) for v in results.values()))
        ttft = tm.hist_percentiles("serving/ttft_s", (0.5, 0.99)) or (0.0, 0.0)
        tpot = tm.hist_percentiles("serving/tpot_s", (0.5, 0.99)) or (0.0, 0.0)
        return {"wall": wall, "decoded": decoded,
                "completed": len(results),
                "ttft": ttft, "tpot": tpot,
                "slo": _slo_classes_extra(tm),
                "handoff_p50": (tm.hist_percentiles("fleet/handoff_s",
                                                    (0.5,)) or (0.0,))[0]}

    # leg 1 — single replica at its saturation rate (the reference the
    # multiplier is judged against); built through the same replica path so
    # both legs pin pools identically
    from deepspeed_tpu.inference.v2.replica_group import build_replica
    mesh1, sched1 = build_replica(model, params, [jax.devices()[0]],
                                  engine_config=eng_cfg, token_budget=budget)

    class _Single:
        has_work = property(lambda self: sched1.has_work)

        def submit(self, uid, prompt, **kw):
            with mesh1:
                sched1.submit(uid, prompt, **kw)

        def step(self):
            with mesh1:
                return sched1.step()

        def results(self):
            return sched1.results()

    single = measure(_Single(), [(mesh1, sched1)], arrivals, "single")

    # leg 2 — SLO router over a disaggregated fleet at DOUBLE the offered
    # rate (same trace, arrival gaps halved)
    fleet = PrefillDecodeFleet(
        model, params, prefill_replicas=n_prefill, decode_replicas=n_decode,
        engine_config=prefill_cfg, token_budget=prefill_budget,
        decode_engine_config=eng_cfg, decode_token_budget=budget)
    fleet.warm_transport()
    router = SLORouter(fleet, slo_ttft_s=max(4.0, single["ttft"][1] * 8),
                       queue_limit=n_req)
    fl = measure(router, fleet.prefill + fleet.decode, arrivals * 0.5,
                 "router+disagg")

    tstats = fleet.transport.stats()
    single_rps = single["completed"] / single["wall"]
    fleet_rps = fl["completed"] / fl["wall"]
    rate_multiplier = fleet_rps / single_rps if single_rps else 0.0
    total = fl["decoded"] + prompt_total
    n_chips = jax.device_count()
    extra = {
        # fleet leg (the payload's headline numbers)
        "ttft_p50_s": round(fl["ttft"][0], 6),
        "ttft_p99_s": round(fl["ttft"][1], 6),
        "tpot_p50_s": round(fl["tpot"][0], 6),
        "tpot_p99_s": round(fl["tpot"][1], 6),
        "tokens_per_sec": round(total / fl["wall"], 1),
        "requests_per_sec": round(fleet_rps, 3),
        "rate_multiplier": round(rate_multiplier, 4),
        "offered_rate_req_per_s": rate * 2,
        "shed_rate": round(router.shed_rate, 6),
        "admitted": router.admitted, "queued": router.queued,
        "rejected": router.rejected,
        "affinity_hits": router.affinity_hits,
        # handoff accounting (KVPageTransport + telemetry must agree)
        "handoffs": tstats["handoffs"],
        "handoff_transfers": tstats["transfers"],
        "pages_shipped": tstats["pages_shipped"],
        "pages_bound": tstats["pages_bound"],
        "handoff_bytes": tstats["bytes_shipped"],
        "handoff_total_s": round(tstats["total_s"], 6),
        "handoff_p50_s": round(fl["handoff_p50"], 6),
        "prefill_replicas": n_prefill, "decode_replicas": n_decode,
        "prefill_token_budget": prefill_budget,
        "decode_token_budget": budget,
        # single-replica reference leg
        "single_ttft_p50_s": round(single["ttft"][0], 6),
        "single_ttft_p99_s": round(single["ttft"][1], 6),
        "single_tpot_p50_s": round(single["tpot"][0], 6),
        "single_tpot_p99_s": round(single["tpot"][1], 6),
        "single_requests_per_sec": round(single_rps, 3),
        "single_rate_req_per_s": rate,
        "single_wall_s": round(single["wall"], 2),
        "requests": n_req, "seed": args.seed, "arrival": args.arrival,
        "prompt_tokens_total": prompt_total,
        "decode_tokens_total": fl["decoded"],
        "wall_s": round(fl["wall"], 2), "chips": n_chips,
        "model": f"llama-{cfg.hidden_size}x{cfg.num_hidden_layers}",
    }
    if fl["slo"]:
        extra["slo_classes"] = fl["slo"]
        attain = _min_attainment(fl["slo"])
        if attain is not None:
            extra["slo_min_attainment"] = round(attain, 6)
    if single["slo"]:
        extra["single_slo_classes"] = single["slo"]
    _embed_telemetry(extra)
    payload = {
        "metric": "serving_fleet_replay_tokens_per_sec_per_chip",
        "value": round(total / fl["wall"] / max(n_chips, 1), 1),
        "unit": "tokens/s/chip (prefill+decode)",
        "vs_baseline": None,
        "extra": extra,
    }
    bench.emit(payload)
    return payload


def kvfabric_bench(args, on_tpu):
    """KV fabric microbench (``--fleet --two-process``): a prefix-mix trace
    (groups of requests sharing long prompt prefixes) runs four legs over
    int8 KV pools —

    1. monolithic single replica (the greedy parity reference),
    2. in-process fleet on the serialized ``wire`` codec, delta OFF
       (the no-delta wire-byte reference),
    3. same fleet with delta-shipping ON and ``FlowControl`` armed,
    4. ``TwoProcessFleet``: decode in a separate OS process, every page
       crossing a pipe as a framed, per-page-CRC32 wire message.

    Headline: serialized wire bytes per page over the fp32 device bytes
    they replace — the int8+scale wire row must stay under perf_gate's
    ``KVFABRIC_MAX_WIRE_FP32_RATIO``. The model pins head_dim=32 (2 heads
    on the tiny 64-wide trunk): the per-row overhead is hd+4 scale bytes
    over 4*hd fp32, and the ratchet needs hd > 13 to be satisfiable at
    all. Delta must ship measurably fewer bytes than leg 2, every leg must
    match leg 1 token-for-token (int8 pools quantize identically on both
    sides, so the wire is lossless end-to-end), and the two-process leg
    must complete every request."""
    import jax
    import numpy as np
    from deepspeed_tpu.inference.v2.fleet import (FlowControl,
                                                  PrefillDecodeFleet)
    from deepspeed_tpu.inference.v2.fleet.two_process import TwoProcessFleet
    from deepspeed_tpu.inference.v2.replica_group import build_replica
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=512, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=128,
                      scan_layers=True, remat=False)
    model = LlamaForCausalLM(cfg)
    ids = np.zeros((1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    eng_cfg = {"state_manager": {"max_ragged_sequence_count": 16,
                                 "max_ragged_batch_size": 64,
                                 "max_context": 96,
                                 "num_kv_blocks": 160,
                                 "kv_dtype": "int8"},
               "kv_cache": {"block_size": 8, "cache_dtype": "fp32"},
               "prefix_caching": True}
    max_new = 8

    # prefix-mix trace: pools of shared prefixes — the delta leg's savings
    # come from the decode pool already holding a group's prefix blocks
    # after its first member ships
    gen = np.random.default_rng(args.seed)
    n_pools = 4
    per_pool = 3
    prefixes = [gen.integers(1, cfg.vocab_size, 32).astype(np.int32)
                for _ in range(n_pools)]
    prompts = {}
    for g in range(n_pools):
        for i in range(per_pool):
            uid = g * per_pool + i
            suffix = gen.integers(1, cfg.vocab_size,
                                  4 + uid % 5).astype(np.int32)
            prompts[uid] = np.concatenate([prefixes[g], suffix])

    def drive(backend):
        for uid, p in prompts.items():
            backend.submit(uid, p, max_new_tokens=max_new,
                           temperature=0.0, seed=7)
        rounds = 0
        while backend.has_work:
            backend.step()
            rounds += 1
            if rounds > 4096:
                raise RuntimeError("kvfabric leg did not converge")
        return {u: np.asarray(v) for u, v in backend.results().items()}

    # leg 1 — monolithic reference
    mesh1, sched1 = build_replica(model, params, [jax.devices()[0]],
                                  engine_config=eng_cfg, token_budget=64)

    class _Single:
        has_work = property(lambda self: sched1.has_work)

        def submit(self, uid, prompt, **kw):
            with mesh1:
                sched1.submit(uid, prompt, **kw)

        def step(self):
            with mesh1:
                return sched1.step()

        def results(self):
            return sched1.results()

    ref = drive(_Single())

    def parity(out):
        return all(u in out and np.array_equal(ref[u], out[u])
                   for u in prompts)

    def fleet_leg(**kw):
        fleet = PrefillDecodeFleet(model, params, prefill_replicas=1,
                                   decode_replicas=1, engine_config=eng_cfg,
                                   token_budget=64, codec="wire", **kw)
        out = drive(fleet)
        return fleet, out

    # leg 2 — wire codec, delta OFF: the no-delta byte reference
    f_plain, out_plain = fleet_leg(delta_shipping=False)
    plain = f_plain.transport.stats()
    # fp32 equivalent of the SAME page traffic (pure shape math)
    kc = f_plain.prefill[0][1].engine._state.kv_cache
    n_layers, _, n_heads, bsz, hd = kc.k_pool.shape
    fp32_page = 2 * n_layers * n_heads * bsz * hd * 4
    wire_page = f_plain.transport.page_wire_cost(f_plain.prefill[0][1].engine)

    # leg 3 — delta-shipping ON + flow control
    flow = FlowControl(max_inflight_bytes=1 << 20)
    f_delta, out_delta = fleet_leg(delta_shipping=True, flow=flow)
    delta = f_delta.transport.stats()

    # leg 4 — two-process: decode across a real OS process boundary
    import dataclasses
    mc = dataclasses.asdict(cfg)
    tp = TwoProcessFleet(model, params, mc, engine_config=eng_cfg,
                         token_budget=64, delta_shipping=True)
    try:
        out_tp = drive(tp)
        tp_stats = tp.stats()
    finally:
        tp.close()
    tp_lost = [u for u in prompts if u not in out_tp or not len(out_tp[u])]
    tp_stats["lost_requests"] = len(tp_lost)

    ratio = wire_page / fp32_page
    extra = {
        "wire_fp32_ratio": round(ratio, 6),
        "wire_page_bytes": wire_page,
        "fp32_page_bytes": fp32_page,
        "head_dim": hd,
        "nodelta_wire_bytes": plain["wire_bytes_shipped"],
        "delta_wire_bytes": delta["wire_bytes_shipped"],
        "wire_bytes_saved": delta["wire_bytes_saved"],
        "pages_shipped": delta["pages_shipped"],
        "pages_delta_skipped": delta["pages_delta_skipped"],
        "crc_failures": plain["crc_failures"] + delta["crc_failures"],
        "failed_handoffs": plain["failed_handoffs"]
        + delta["failed_handoffs"],
        "handoffs": delta["handoffs"],
        "parity_nodelta": parity(out_plain),
        "parity_delta": parity(out_delta),
        "flow": flow.stats(),
        "two_process": dict(tp_stats, parity=parity(out_tp)),
        "requests": len(prompts), "prefix_pools": n_pools,
        "max_new_tokens": max_new, "seed": args.seed,
        "chips": jax.device_count(),
        "model": f"llama-{cfg.hidden_size}x{cfg.num_hidden_layers}"
                 f"-hd{hd}-int8kv",
    }
    _embed_telemetry(extra)
    payload = {
        "metric": "serving_kvfabric_wire_fp32_ratio",
        "value": round(ratio, 6),
        "unit": "serialized wire bytes / fp32 device bytes (lower=better)",
        "vs_baseline": None,
        "extra": extra,
    }
    bench.emit(payload)
    return payload


#: default chaos spec for --chaos with no argument. Step windows count
#: fleet rounds; fault hits within a round visit stepping replicas in
#: (prefill0, prefill1, decode0, ...) order, so with 2 prefill replicas the
#: third ``replica.lost`` hit at step 30 deterministically kills decode0
#: mid-trace. ``transport.drop:n2`` makes one handoff transfer fail (the
#: transport's retry absorbs it); ``replica.stall:once@step45`` wedges one
#: replica for a round (it skips WITHOUT heartbeating).
DEFAULT_CHAOS_SPEC = ("replica.lost:n3@step30-100000;"
                      "transport.drop:n2;"
                      "replica.stall:once@step45")


def _diurnal_arrivals(n_req, seed, base_rate, period_s, depth):
    """Non-homogeneous Poisson arrivals on a compressed diurnal cycle:
    instantaneous rate(t) = base_rate * (1 + depth*sin(2*pi*t/period_s)),
    realized by dividing seeded unit-exponential gaps by the local rate
    (inverse-intensity spacing). Same seed -> identical trace; peaks
    saturate the fleet, troughs idle it — the autoscaler's signal."""
    import numpy as np
    gen = np.random.default_rng(seed)
    gaps = gen.exponential(1.0, n_req)
    floor = max(base_rate * (1.0 - depth), 1e-3)
    t = 0.0
    out = np.empty(n_req)
    for i in range(n_req):
        r = base_rate * (1.0 + depth * np.sin(2.0 * np.pi * t / period_s))
        t += gaps[i] / max(r, floor)
        out[i] = t
    out -= out[0]
    return out


def chaos_replay_bench(args, on_tpu):
    """Elastic serving fleet under chaos (``--replay --chaos [--diurnal]``):
    ``SLORouter`` + ``PrefillDecodeFleet`` + ``FleetAutoscaler`` driven over
    a seeded (optionally diurnal) trace WITH fault injection armed for the
    whole measured leg — a decode replica dies mid-stream, a handoff
    transfer drops (retried), a replica stalls past a heartbeat. The fleet
    must route around the loss, re-admit the dead replica's in-flight
    requests bit-exactly, replace the lost capacity from the warm standby
    pool, and keep the interactive SLO class attained while ALL shedding
    lands on batch.

    Headline number: goodput per replica-second — completed requests'
    prompt+decode tokens divided by the integral of live replicas over the
    wall clock (re-prefill waste and over-provisioned idle replicas both
    drag it down). perf_gate's ``check_chaos_baseline`` ratchets it via
    onchip_results/serving_chaos_baseline.json."""
    import jax
    import numpy as np
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.inference.v2.fleet import (FleetAutoscaler,
                                                  PrefillDecodeFleet,
                                                  RequestRejected, SLORouter)
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.resilience import faults

    n_prefill = args.fleet_prefill
    n_decode = max(args.fleet_decode, 2)  # the chaos kill needs a survivor
    standby = 1  # pre-built warm capacity the autoscaler revives
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=args.prompt + args.new + 64,
                          remat=False)
        n_req = args.requests
        prompt_scale, new_scale = args.prompt // 2, args.new
        max_prompt, max_new = args.prompt, args.new * 4
        budget, base_rate = 256, args.rate
        period_s = args.diurnal_period or 30.0
    else:
        cfg = LlamaConfig.tiny(remat=False)
        n_req = min(args.requests, 48)
        prompt_scale, new_scale = 64, 4
        max_prompt, max_new = 192, 8
        # peak rate (base * (1+depth)) must exceed the steady fleet's
        # service capacity so the diurnal crest queues and the trough
        # drains — the autoscaler's whole signal
        budget, base_rate = 16, max(args.rate, 20.0)
        period_s = args.diurnal_period or 1.2
    prefill_budget = max(budget * 4, max_prompt)
    need = n_prefill + n_decode + standby
    if need > len(jax.devices()):
        raise RuntimeError(
            f"chaos replay needs {need} devices, have "
            f"{len(jax.devices())} (CPU runs force 8 host devices)")
    spec = args.chaos if args.chaos else DEFAULT_CHAOS_SPEC

    prompt_lens, out_lens, arrivals = make_workload(
        n_req, args.seed, arrival=args.arrival, rate=base_rate,
        burst_size=args.burst_size, prompt_scale=prompt_scale,
        new_scale=new_scale, max_prompt=max_prompt, max_new=max_new)
    if args.diurnal:
        arrivals = _diurnal_arrivals(n_req, args.seed + 1, base_rate,
                                     period_s, args.diurnal_depth)
    gen = np.random.default_rng(args.seed)
    prompts = [gen.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in prompt_lens]
    slo_assign = _assign_slo_classes(n_req)

    model = LlamaForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 8)).astype(np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    block = 32 if on_tpu else 8
    max_ctx = int(max_prompt) + int(max_new) + block
    eng_cfg = {
        "state_manager": {"max_ragged_sequence_count": max(4, n_req) + 1,
                          "max_ragged_batch_size": prefill_budget,
                          "max_context": max_ctx,
                          "num_kv_blocks":
                              max(64, (max_ctx // block + 2) * n_req)},
        "kv_cache": {"block_size": block,
                     "cache_dtype": "bf16" if on_tpu else "fp32"},
        "slo_classes": REPLAY_SLO_CLASSES}
    prefill_cfg = {
        "state_manager": dict(eng_cfg["state_manager"],
                              max_ragged_sequence_count=4),
        "kv_cache": dict(eng_cfg["kv_cache"]),
        "slo_classes": REPLAY_SLO_CLASSES}

    # build the fleet WITH the standby replica, warm every batch shape on
    # every engine (including the standby's), then retire the standby into
    # the warm pool — the autoscaler's mid-trace scale-up revives a fully
    # compiled engine, so elasticity costs a page-table reset, not a compile
    fleet = PrefillDecodeFleet(
        model, params, prefill_replicas=n_prefill,
        decode_replicas=n_decode + standby,
        engine_config=prefill_cfg, token_budget=prefill_budget,
        decode_engine_config=eng_cfg, decode_token_budget=budget)
    fleet.warm_transport()
    t0 = time.perf_counter()
    for mesh, sched in fleet.prefill + fleet.decode:
        with mesh:
            _precompile_batch_grid(sched, n_req, sched.budget)
    print(f"chaos: warmup/compile {time.perf_counter()-t0:.1f}s",
          file=sys.stderr)
    fleet.scale_down_decode(n_decode + standby - 1)  # idle -> warm pool

    router = SLORouter(fleet, slo_ttft_s=max(
        4.0, REPLAY_SLO_CLASSES["interactive"]["ttft_target_s"]),
        queue_limit=n_req)
    scaler = FleetAutoscaler(fleet, router, min_decode=n_decode,
                             max_decode=n_decode + standby,
                             up_queue_depth=2, up_occupancy=0.85,
                             down_idle_rounds=30, cooldown_rounds=15)

    telemetry.reset()
    telemetry.configure(enabled=True,
                        chrome_trace_path=os.environ.get(
                            "DS_TPU_TELEMETRY_TRACE", ""))
    tm = telemetry.get_telemetry()

    def drive():
        t_start = time.perf_counter()
        last = t_start
        replica_seconds = 0.0
        nxt = 0
        rounds = 0
        outcomes = []
        while nxt < n_req or router.has_work:
            now = time.perf_counter() - t_start
            while nxt < n_req and arrivals[nxt] <= now:
                outcomes.append(router.submit(
                    nxt, prompts[nxt], max_new_tokens=int(out_lens[nxt]),
                    slo_class=slo_assign[nxt]))
                nxt += 1
            if router.has_work:
                router.step()
                scaler.observe()
                rounds += 1
                if rounds > 200_000:
                    raise RuntimeError("chaos replay did not converge")
            elif nxt < n_req:
                time.sleep(min(float(arrivals[nxt]) - now, 0.05))
            t = time.perf_counter()
            replica_seconds += fleet.live_replica_count() * (t - last)
            last = t
        return time.perf_counter() - t_start, replica_seconds, outcomes

    faults.reset()
    faults.configure(spec)
    try:
        wall, replica_seconds, outcomes = drive()
        fault_trips = faults.trip_count()
    finally:
        faults.reset()

    results = router.results()
    rejected_uids = {o.uid for o in outcomes
                     if isinstance(o, RequestRejected)}
    served = [i for i in range(n_req) if i not in rejected_uids]
    decoded = int(sum(len(results.get(i, ())) for i in served))
    served_prompt = int(sum(int(prompt_lens[i]) for i in served))
    completed = sum(1 for i in served if len(results.get(i, ())) > 0)
    goodput = (served_prompt + decoded) / replica_seconds \
        if replica_seconds else 0.0

    census = fleet.page_census()
    rep = router.report()
    tstats = fleet.transport.stats()
    slo = _slo_classes_extra(tm)
    ttft = tm.hist_percentiles("serving/ttft_s", (0.5, 0.99)) or (0.0, 0.0)
    tpot = tm.hist_percentiles("serving/tpot_s", (0.5, 0.99)) or (0.0, 0.0)
    shed_by_class = rep["shed_by_class"]
    extra = {
        "goodput_tokens_per_replica_sec": round(goodput, 1),
        "wall_s": round(wall, 2),
        "replica_seconds": round(replica_seconds, 2),
        "requests": n_req, "completed": completed,
        "requests_lost": len(served) - completed,
        "decode_tokens_total": decoded,
        "prompt_tokens_total": served_prompt,
        # chaos + recovery accounting
        "chaos_spec": spec, "fault_trips": fault_trips,
        "replica_losses": fleet.replica_losses,
        "readmitted": fleet.readmitted,
        "handoff_retries": tstats["retry_trips"],
        "handoff_fallbacks": fleet.handoff_fallbacks,
        "failed_handoffs": tstats["failed_handoffs"],
        "leaked_pages": census["leaked_pages"],
        # elasticity (autoscaler actions during the measured leg only)
        "scale_ups": scaler.scale_ups, "scale_downs": scaler.scale_downs,
        "live_decode_end": len(fleet.live_decode_indices()),
        "decode_replicas": n_decode, "standby_replicas": standby,
        "prefill_replicas": n_prefill,
        # SLO precedence: batch absorbs ALL shedding
        "shed_by_class": shed_by_class,
        "interactive_sheds": shed_by_class.get("interactive", 0),
        "shed_rate": round(router.shed_rate, 6),
        "admitted": router.admitted, "rejected": router.rejected,
        "accounting": rep["accounting"],
        "ttft_p50_s": round(ttft[0], 6), "ttft_p99_s": round(ttft[1], 6),
        "tpot_p50_s": round(tpot[0], 6), "tpot_p99_s": round(tpot[1], 6),
        "diurnal": bool(args.diurnal),
        "diurnal_period_s": period_s,
        "diurnal_depth": args.diurnal_depth,
        "base_rate_req_per_s": base_rate,
        "arrival": "diurnal" if args.diurnal else args.arrival,
        "seed": args.seed, "chips": jax.device_count(),
        "prefill_token_budget": prefill_budget,
        "decode_token_budget": budget,
        "model": f"llama-{cfg.hidden_size}x{cfg.num_hidden_layers}",
    }
    if slo:
        extra["slo_classes"] = slo
        attain = _min_attainment(slo)
        if attain is not None:
            extra["slo_min_attainment"] = round(attain, 6)
        inter = _min_attainment({"interactive": slo["interactive"]}) \
            if "interactive" in slo else None
        if inter is not None:
            extra["interactive_attainment"] = round(inter, 6)
    _embed_telemetry(extra)
    payload = {
        "metric": "serving_chaos_goodput_tokens_per_replica_sec",
        "value": round(goodput, 1),
        "unit": "tokens/replica-s (completed prompt+decode, under faults)",
        "vs_baseline": None,
        "extra": extra,
    }
    bench.emit(payload)
    return payload


def replay_bench(args, on_tpu):
    """Wall-clock traffic replay; latency percentiles from the telemetry
    serving stream."""
    import jax
    import numpy as np
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.models.llama import LlamaConfig

    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=4,
                          max_position_embeddings=args.prompt + args.new + 64,
                          remat=False)
        n_req = args.requests
        prompt_scale, new_scale = args.prompt // 2, args.new
        max_prompt, max_new = args.prompt, args.new * 4
        budget, rate = 256, args.rate
    else:
        cfg = LlamaConfig.tiny(remat=False)
        n_req = min(args.requests, 6)
        prompt_scale, new_scale = 16, 3
        max_prompt, max_new = 48, 8
        budget, rate = 16, max(args.rate, 20.0)

    prompt_lens, out_lens, arrivals = make_workload(
        n_req, args.seed, arrival=args.arrival, rate=rate,
        burst_size=args.burst_size, prompt_scale=prompt_scale,
        new_scale=new_scale, max_prompt=max_prompt, max_new=max_new)
    model, sched = _build_stack(cfg, n_req, int(max_prompt), int(max_new),
                                budget, on_tpu,
                                slo_classes=REPLAY_SLO_CLASSES)
    slo_assign = _assign_slo_classes(n_req)
    gen = np.random.default_rng(args.seed)
    prompts = [gen.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in prompt_lens]

    # compile before the clock starts — replay measures serving latency,
    # not jit time
    t0 = time.perf_counter()
    sched.submit(10_000, prompts[0][:max(4, int(prompt_lens.min()))],
                 max_new_tokens=2)
    sched.run_to_completion()
    print(f"replay: warmup/compile {time.perf_counter()-t0:.1f}s",
          file=sys.stderr)

    # the replay's latency numbers COME from the serving telemetry stream;
    # (re)start it clean after warmup so compile never pollutes TTFT — even
    # when DS_TPU_TELEMETRY=1 enabled it earlier
    telemetry.reset()
    telemetry.configure(enabled=True,
                        chrome_trace_path=os.environ.get(
                            "DS_TPU_TELEMETRY_TRACE", ""))
    tm = telemetry.get_telemetry()

    wall = _drive_replay(sched, prompts, out_lens, arrivals,
                         slo_classes=slo_assign)

    decoded = sum(len(r.generated) for u, r in sched._requests.items()
                  if u != 10_000)
    total = decoded + int(prompt_lens.sum())
    n_chips = jax.device_count()
    ttft = tm.hist_percentiles("serving/ttft_s", (0.5, 0.99)) or (0.0, 0.0)
    tpot = tm.hist_percentiles("serving/tpot_s", (0.5, 0.99)) or (0.0, 0.0)
    serving = telemetry.summary()["serving"]
    kv_gauge = serving["gauges"].get("serving/kv_occupancy", {})
    extra = {
        "ttft_p50_s": round(ttft[0], 6), "ttft_p99_s": round(ttft[1], 6),
        "tpot_p50_s": round(tpot[0], 6), "tpot_p99_s": round(tpot[1], 6),
        "tokens_per_sec": round(total / wall, 1),
        "decode_tokens_per_sec": round(decoded / wall, 1),
        "peak_kv_occupancy": round(float(kv_gauge.get("peak", 0.0)), 6),
        "preemptions": int(serving["requests"].get("preempted", 0)),
        "requests": n_req, "seed": args.seed, "arrival": args.arrival,
        "rate_req_per_s": rate,
        "prompt_tokens_total": int(prompt_lens.sum()),
        "decode_tokens_total": int(decoded),
        "wall_s": round(wall, 2), "chips": n_chips,
        "model": f"llama-{cfg.hidden_size}x{cfg.num_hidden_layers}",
    }
    slo = _slo_classes_extra(tm)
    if slo:
        extra["slo_classes"] = slo
        attain = _min_attainment(slo)
        if attain is not None:
            extra["slo_min_attainment"] = round(attain, 6)
    _embed_telemetry(extra)
    payload = {
        "metric": "serving_replay_tokens_per_sec_per_chip",
        "value": round(total / wall / max(n_chips, 1), 1),
        "unit": "tokens/s/chip (prefill+decode)",
        "vs_baseline": None,
        "extra": extra,
    }
    bench.emit(payload)
    return payload


def w8a16_check(on_tpu):
    """Quantized-matmul hardware validation: W8A16 vs fp reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.inference.quantization.quantization import (
        QuantizedParameter)
    from deepspeed_tpu.ops.pallas.quantized_matmul import quantized_matmul

    rng = np.random.default_rng(0)
    results = []
    for (m, k, n) in ((256, 1024, 1024), (128, 2048, 512)):
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        w = rng.normal(size=(k, n)).astype(np.float32) / np.sqrt(k)
        qp = QuantizedParameter.from_array(w, num_bits=8, group_size=128)
        t0 = time.perf_counter()
        out_q = jax.block_until_ready(
            quantized_matmul(x, qp.q, qp.scale, qp.group_size,
                             interpret=not on_tpu))
        dt_q = time.perf_counter() - t0
        # kernel exactness vs the XLA dequant reference (quantization error
        # itself is a separate, known quantity)
        ref = jax.block_until_ready(x @ qp.dequantized(jnp.float32))
        err = float(jnp.max(jnp.abs(out_q.astype(jnp.float32) - ref))
                    / (jnp.max(jnp.abs(ref)) + 1e-9))
        results.append({"shape": [m, k, n], "rel_err": round(err, 4),
                        "first_call_s": round(dt_q, 3)})
    ok = all(r["rel_err"] < 0.05 for r in results)
    payload = {"metric": "w8a16_quantized_matmul_check",
               "value": 1.0 if ok else 0.0, "unit": "pass",
               "vs_baseline": None, "extra": {"cases": results}}
    bench.emit(payload)
    return payload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=512)
    ap.add_argument("--new", type=int, default=64)
    ap.add_argument("--replay", action="store_true",
                    help="traffic-replay mode: seeded heavy-tailed lengths + "
                         "arrival schedule; emits TTFT/TPOT percentiles")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arrival", choices=("poisson", "burst"),
                    default="poisson")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="mean arrival rate, requests/s")
    ap.add_argument("--burst-size", type=int, default=4)
    ap.add_argument("--prefix-mix", action="store_true",
                    help="with --replay: shared system-prompt pools, run the "
                         "same trace with prefix_caching off then on and "
                         "report the prefill-token/TTFT comparison")
    ap.add_argument("--prefix-pools", type=int, default=4,
                    help="number of shared prefix pools (--prefix-mix)")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="shared prefix length in tokens; 0 = per-platform "
                         "default (--prefix-mix)")
    ap.add_argument("--speculate", action="store_true",
                    help="draft-then-verify leg: the same seeded greedy "
                         "trace with speculation off then on; reports the "
                         "tokens/s multiplier, accept rate, and the greedy "
                         "bit-exactness flag")
    ap.add_argument("--long-context", action="store_true",
                    help="long-context KV tiering workload: seeded long "
                         "prompts over a shared prefix, fp vs int8 KV at an "
                         "equal HBM budget with the host-DRAM spill tier on")
    ap.add_argument("--longctx-max", type=int, default=131072,
                    help="max prompt length for the TPU --long-context leg "
                         "(CPU runs scale down automatically)")
    ap.add_argument("--fleet", action="store_true",
                    help="with --replay: single-replica saturation leg, then "
                         "SLORouter over a prefill/decode fleet at 2x the "
                         "offered rate")
    ap.add_argument("--fleet-prefill", type=int, default=2,
                    help="prefill replicas in the fleet leg (--fleet)")
    ap.add_argument("--fleet-decode", type=int, default=1,
                    help="decode replicas in the fleet leg (--fleet); decode "
                         "throughput is bounded by live sequences per round, "
                         "not budget, so 1 is usually right until the KV "
                         "working set outgrows one pool")
    ap.add_argument("--two-process", action="store_true",
                    help="with --fleet: the KV fabric microbench — wire "
                         "codec byte ratios, delta-shipping savings, and a "
                         "leg where decode runs in a SEPARATE OS process "
                         "with every KV page crossing a pipe as a framed "
                         "CRC32-checked wire message")
    ap.add_argument("--chaos", nargs="?", const="", default=None,
                    metavar="SPEC",
                    help="elastic-fleet chaos replay: drive the SLO router + "
                         "prefill/decode fleet + autoscaler with fault "
                         "injection armed (replica loss, handoff drops, "
                         "stalls). SPEC is a resilience.faults grammar "
                         "string; bare --chaos uses the default kill-one-"
                         "decode-replica spec. Implies --replay")
    ap.add_argument("--diurnal", action="store_true",
                    help="replace the arrival schedule with a seeded "
                         "diurnal cycle (sinusoidal rate modulation) so the "
                         "autoscaler sees crests that queue and troughs "
                         "that idle")
    ap.add_argument("--diurnal-period", type=float, default=0.0,
                    help="diurnal cycle period in seconds; 0 = per-platform "
                         "default")
    ap.add_argument("--diurnal-depth", type=float, default=0.85,
                    help="diurnal modulation depth in [0,1): rate swings "
                         "between base*(1-depth) and base*(1+depth)")
    args = ap.parse_args()
    if args.chaos is not None:
        args.replay = True

    if args.fleet or args.chaos is not None:
        # the fleet leg needs one device per replica; CPU runs present them
        # via forced host devices (inert when a real TPU backend is used) —
        # must be set before jax first initializes
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = \
                (flags + " --xla_force_host_platform_device_count=8").strip()

    # DS_TPU_TELEMETRY=1: same contract as bench.py — enable the unified
    # telemetry stream up front; summaries land in each payload's extra
    if os.environ.get("DS_TPU_TELEMETRY") == "1":
        from deepspeed_tpu import telemetry
        telemetry.configure(enabled=True,
                            chrome_trace_path=os.environ.get(
                                "DS_TPU_TELEMETRY_TRACE", ""))

    import jax
    from deepspeed_tpu.utils import compile_cache
    compile_cache.enable()
    # the CPU mode (tiny model, same metric names) stays until the benchmark
    # PR replaces this script: four tier-1 tests drive it as a subprocess
    on_tpu = jax.devices()[0].platform == "tpu"
    # a failed leg raises: no 0.0 line, no exit code 0
    if args.fleet and args.two_process:
        kvfabric_bench(args, on_tpu)
    elif args.speculate:
        speculate_bench(args, on_tpu)
    elif args.long_context:
        long_context_bench(args, on_tpu)
    elif args.replay:
        if args.chaos is not None:
            chaos_replay_bench(args, on_tpu)
        elif args.fleet:
            fleet_replay_bench(args, on_tpu)
        elif args.prefix_mix:
            prefix_mix_bench(args, on_tpu)
        else:
            replay_bench(args, on_tpu)
    else:
        serving_bench(args, on_tpu)
        w8a16_check(on_tpu)


if __name__ == "__main__":
    main()
