"""Proof that the two main paths start on a TPU: trains GPT-2-small and serves
Mistral-7B-width requests through the normal entry points, in ONE process.

    python chip_smoke.py             # one chip: train, then serve (fp + int8 KV)
    python chip_smoke.py --chips 4   # four chips: the sharded train step,
                                     # then a prefill/decode fleet on three

Sizes are fixed (``REAL``): no ladder, no retry, no child process. A
configuration that does not fit, a kernel that does not compile or a check
that fails is an exception and a non-zero exit. Without a TPU the script exits
non-zero before any phase. Each phase prints one JSON line of notes (sizes,
seconds around ``block_until_ready``, peak device bytes, kernel dispatch
records); the LAST line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}`` and nothing else.

The phases are functions of a size preset so that ``tests/test_chip_smoke.py``
can drive them at ``TINY`` on the CPU (``DS_TPU_PALLAS_INTERPRET=1``);
``main()`` always takes ``REAL`` and there is no switch that makes it pass on
a CPU.
"""

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# sizes

@dataclasses.dataclass(frozen=True)
class Preset:
    name: str
    seed: int
    # train: GPT2Config field overrides ({} = GPT2Config.small(), the
    # published 12 x 768 x 12 heads, vocab 50257, 1024 positions)
    gpt2: dict
    train_batch: int
    train_seq: int
    train_steps: int
    multichip_steps: int
    # serve: mistral_config overrides (REAL cuts DEPTH only, never a width)
    mistral: dict
    prompt_lens: tuple        # one request each
    new_tokens: int
    probe_lens: tuple         # prompts of the two logits-parity requests
    probe_decode: int         # forced decode steps compared after each probe
    block_size: int
    num_kv_blocks: int
    max_context: int
    max_seqs: int
    token_budget: int
    # --chips 4 leg (b): prefill + decode replica behind the router
    fleet_prompt_lens: tuple
    fleet_kv_blocks: int


REAL = Preset(
    name="real", seed=0,
    gpt2={}, train_batch=32, train_seq=1024, train_steps=5, multichip_steps=3,
    # Mistral-7B-v0.1 widths (4096 wide, 32/8 heads of 128, ffn 14336, vocab
    # 32000, window 4096); depth 32 -> 16 so that bf16 weights (7.0 GiB) and
    # a KV pool a deployment would size share one 16 GB chip: 1664 pages of
    # 64 tokens x 4 MiB (16 layers, k+v, 8 heads of 128, bf16) = 6.5 GiB, and
    # the compiler reports 15.75 GiB usable with ~1 GiB of program scratch
    mistral={"num_hidden_layers": 16},
    prompt_lens=(128, 256, 384, 512, 640, 768, 896, 1024), new_tokens=32,
    probe_lens=(300, 700), probe_decode=4,
    block_size=64, num_kv_blocks=1664, max_context=2048,
    max_seqs=8, token_budget=512,
    # 1 GiB pools: the engine allocates a pool on the default device before
    # the replica moves it to its own chip, beside device 0's weights
    fleet_prompt_lens=(200, 456, 712, 968), fleet_kv_blocks=256)

TINY = Preset(
    name="tiny", seed=0,
    gpt2=dict(vocab_size=512, n_positions=128, n_embd=64, n_layer=2, n_head=4),
    train_batch=4, train_seq=128, train_steps=5, multichip_steps=3,
    mistral=dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, max_position_embeddings=128,
                 sliding_window=64),
    prompt_lens=(9, 17, 24, 33), new_tokens=4,
    probe_lens=(13, 27), probe_decode=2,
    block_size=8, num_kv_blocks=48, max_context=64,
    max_seqs=4, token_budget=16,
    fleet_prompt_lens=(9, 17, 24, 33), fleet_kv_blocks=48)


# tolerances, each with its reason ------------------------------------------

#: first loss vs ln(vocab): random init predicts near-uniform next tokens
FIRST_LOSS_TOL = 1.0
#: engine loss (bf16 activations, flash kernel, chunked CE) vs the float32
#: ``mha_reference`` forward at highest matmul precision on the same initial
#: parameters. bf16 keeps 8 mantissa bits (2^-9 relative rounding); the loss
#: is a mean over batch*seq tokens, so rounding averages out and what is left
#: is the systematic part, well under 0.5% of a loss of ~11.
TRAIN_REF_LOSS_TOL = 0.05
#: sharded (dp2 x tp2, ZeRO-3) vs single-device losses, same seed and batch:
#: only the order of bf16 reductions differs (row-split matmuls sum partial
#: products across tp, gradients across dp) — the bound __graft_entry__ uses.
MULTICHIP_LOSS_RTOL = 0.05
#: engine logits (bf16 weights AND activations through the paged cache) vs
#: the float32 full forward, as RMS error over RMS of the reference logits.
#: Each bf16 rounding is 2^-9 relative; ~6 matmuls per layer over 16 residual
#: layers accumulate in quadrature to ~2% — 5% leaves room for the tails.
SERVE_REF_REL_RMS_TOL = 0.05
#: int8 pages vs fp pages on the same engine path: symmetric per-row int8
#: (scale = rowmax/127) adds at most 1/254 of each K/V row's max per element,
#: the same order as a bf16 rounding, and attention averages it over keys.
SERVE_INT8_REL_RMS_TOL = 0.05
#: where the fleet and the monolithic replica part ways (they batch the same
#: rows differently, and bf16 matmuls round by batch shape), both tokens must
#: be greedy choices up to that noise: within this of the float32 reference's
#: top logit at that position. The serve phase measured |engine - reference|
#: <= 0.05 per logit (my chip run, PR 22) and two engines double it; logits
#: spread with std ~1.3 over 32000 entries, so 0.25 admits a handful of
#: near-tied candidates, never an arbitrary token.
NEAR_TIE_TOL = 0.25


class SmokeFailure(AssertionError):
    """A phase's check failed."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(line):
    print(json.dumps(line), flush=True)


# ---------------------------------------------------------------------------
# shared helpers

@contextlib.contextmanager
def reference_attention():
    """Route the models' ``mha`` through ``mha_reference`` (plain XLA einsum
    attention) for the reference forwards. The models import ``mha`` at call
    time, so swapping the module attribute is enough."""
    from deepspeed_tpu.ops import flash_attention as fa
    kernel_mha = fa.mha
    fa.mha = fa.mha_reference
    try:
        yield
    finally:
        fa.mha = kernel_mha


def device_memory(devices=None):
    """[(bytes_in_use, peak_bytes_in_use)] per device; None where the backend
    reports no stats (CPU)."""
    import jax
    out = []
    for d in devices or jax.devices():
        st = d.memory_stats()
        out.append(None if not st else
                   (int(st.get("bytes_in_use", 0)),
                    int(st.get("peak_bytes_in_use", 0))))
    return out


def peak_bytes():
    mem = device_memory()[0]
    return None if mem is None else mem[1]


def dispatch_stats():
    """{(kernel, outcome, reason): count} recorded by sharded_kernel_call."""
    from deepspeed_tpu import telemetry
    return dict(telemetry.get_telemetry().dispatch_stats)


def check_dispatch(allowed_fallbacks=("no_mesh", "trivial_mesh"), since=None):
    """No kernel call on the path took a quiet way out: no shape-based flash
    reference fallback, no shard_map veto, no fallback other than the
    single-device ones (the dense paged-attention twin's is such a record:
    ``ops.registry.takes_kernel``)."""
    from deepspeed_tpu.ops import flash_attention as fa
    check(not fa._warned_shapes,
          f"flash attention fell back to XLA for {sorted(map(str, fa._warned_shapes))}")
    since = since or {}
    bad = {k: n for k, n in dispatch_stats().items()
           if n > since.get(k, 0) and (
               k[1] == "veto" or
               (k[1] == "fallback" and k[2] not in allowed_fallbacks))}
    check(not bad, f"kernel dispatch left the kernel path: {bad}")


def check_kernel_in_program(lowered_text, kernel, what):
    """The program dispatched ``kernel`` and — compiled for a TPU — carries a
    Mosaic custom call. Interpret mode (the CPU rehearsal) inlines the kernel
    body, so only the dispatch record can be checked there."""
    from deepspeed_tpu.ops import registry
    check(kernel in registry.active_kernel_configs(),
          f"{what}: {kernel} was never dispatched")
    if not registry.pallas_interpret():
        check("tpu_custom_call" in lowered_text,
              f"{what}: no tpu_custom_call in the lowered program")


def release_device_memory():
    """Drop compiled programs and whatever garbage still pins device buffers
    so the next phase can allocate; returns bytes still in use on device 0."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()
    mem = device_memory()[0]
    return None if mem is None else mem[0]


def kernel_configs():
    from deepspeed_tpu.ops import registry
    return registry.active_kernel_configs()


# ---------------------------------------------------------------------------
# phase: train

TRAIN_CONFIG = {
    # bench.py's configuration: bf16, ZeRO-1, AdamW, clip 1.0, remat "dots",
    # fused grad+apply step
    "gradient_accumulation_steps": 1,
    "bf16": {"enabled": True},
    "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
    "zero_optimization": {"stage": 1},
    "gradient_clipping": 1.0,
    "fused_step": True,
    "activation_checkpointing": {"policy": "dots"},
}


def _gpt2(p):
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    cfg = GPT2Config(**{**p.gpt2, "scan_layers": True, "remat": True})
    check(p.train_seq <= cfg.n_positions, "train_seq exceeds n_positions")
    return cfg, GPT2LMHeadModel(cfg)


def _train_batch(p, vocab):
    import numpy as np
    ids = np.random.default_rng(p.seed).integers(
        0, vocab, size=(p.train_batch, p.train_seq)).astype(np.int32)
    return {"input_ids": ids, "labels": ids}


def _engine_steps(engine, batch, steps):
    """``steps`` forward/backward/step rounds, each timed around
    ``block_until_ready``. Returns (losses, seconds per step)."""
    import jax
    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = engine(batch)
        engine.backward(loss)
        engine.step()
        jax.block_until_ready(loss)
        step_s.append(time.perf_counter() - t0)
        check(engine.was_step_applied(), "optimizer step was not applied")
        losses.append(float(jax.device_get(loss)))
    return losses, step_s


def _lowered_train_step(engine, batch):
    """The engine's fused step, lowered for the state and batch it runs on
    (StableHLO text; nothing is compiled or executed)."""
    lr = engine._schedule_fn(engine.global_steps)
    return engine._fused_step_fn.lower(engine.state,
                                       engine._shard_batch(batch), lr)


def phase_train(p):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.parallel import groups
    from deepspeed_tpu.parallel.topology import MeshTopology

    cfg, model = _gpt2(p)
    batch = _train_batch(p, cfg.vocab_size)
    groups.reset()
    one_row = {k: v[:1] for k, v in batch.items()}
    params = jax.jit(model.init)(jax.random.PRNGKey(p.seed), one_row)["params"]

    # the plain reference: float32 model, einsum attention, highest precision
    ref_model = GPT2LMHeadModel(dataclasses.replace(
        cfg, dtype=jnp.float32, remat=False))
    with reference_attention(), jax.default_matmul_precision("highest"):
        ref_loss = float(jax.jit(
            lambda pr, b: ref_model.apply({"params": pr}, b))(params, batch))

    # one chip, whatever the host holds (the driver's machine has one)
    # [0]: the optimizer and scheduler shims it also returns point back at the
    # engine, and a name bound to them would keep its state on the device
    engine = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        mesh=MeshTopology(dp=1, devices=jax.devices()[:1]),
        config={"train_micro_batch_size_per_gpu": p.train_batch,
                **TRAIN_CONFIG})[0]
    del params
    losses, step_s = _engine_steps(engine, batch, p.train_steps)

    expected = math.log(cfg.vocab_size)
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(abs(losses[0] - expected) < FIRST_LOSS_TOL,
          f"first loss {losses[0]:.4f} not within {FIRST_LOSS_TOL} of "
          f"ln(vocab)={expected:.4f}")
    check(losses[-1] < losses[0],
          f"loss did not fall over {p.train_steps} steps: {losses}")
    check(abs(losses[0] - ref_loss) <= TRAIN_REF_LOSS_TOL,
          f"engine loss {losses[0]:.5f} vs float32 reference {ref_loss:.5f} "
          f"differ by more than {TRAIN_REF_LOSS_TOL}")
    check_kernel_in_program(_lowered_train_step(engine, batch).as_text(),
                            "flash_mha", "train step")
    check_dispatch()

    line = {"phase": "train", "preset": p.name,
            "model": {"n_layer": cfg.n_layer, "n_embd": cfg.n_embd,
                      "n_head": cfg.n_head, "vocab": cfg.vocab_size},
            "batch": p.train_batch, "seq": p.train_seq,
            "losses": [round(x, 5) for x in losses],
            "ref_loss": round(ref_loss, 5),
            "ref_loss_abs_err": round(abs(losses[0] - ref_loss), 5),
            "first_step_s": round(step_s[0], 3),
            "step_s": [round(s, 4) for s in step_s[1:]],
            # first step minus a steady step: trace + compile (or cache read)
            "compile_s": round(step_s[0] - min(step_s[1:]), 3),
            "peak_bytes": peak_bytes(),
            "host_sync_count": engine.host_sync_count,
            "kernel_configs": kernel_configs()}
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(engine.state))
    del engine
    groups.reset()
    left = line["bytes_in_use_after_release"] = release_device_memory()
    check(left is None or left < state_bytes,
          f"the engine's state ({state_bytes} bytes) was not released: "
          f"{left} bytes still in use")
    emit(line)
    return line


# ---------------------------------------------------------------------------
# phase: serve

def _mistral(p):
    import jax.numpy as jnp

    from deepspeed_tpu.models.mistral import MistralForCausalLM, mistral_config
    cfg = mistral_config(**p.mistral, dtype=jnp.bfloat16)
    return cfg, MistralForCausalLM(cfg)


def _seeded_bf16_params(model, seed):
    """Random weights created directly in bfloat16 on the device, leaf by
    leaf (flax's ``model.init`` would make them float32: 15 GB at REAL).
    Matrices draw N(0, 1/fan_in) per layer slice, tables N(0, 0.02^2), norm
    scales are ones (float32, as the model declares them)."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 128), jnp.int32))["params"]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))

    def make(path, sds, key):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.endswith("scale"):
            return jnp.ones(sds.shape, jnp.float32)
        if sds.ndim == 3:        # stacked [L, in, out] Dense kernels
            std = 1.0 / math.sqrt(sds.shape[1])
            return jax.jit(lambda ks: jax.lax.map(
                lambda k: jax.random.normal(k, sds.shape[1:], jnp.bfloat16)
                * jnp.bfloat16(std), ks))(jax.random.split(key, sds.shape[0]))
        check(sds.ndim == 2 and ("embed_tokens" in name or "lm_head" in name),
              f"unexpected parameter {name} {sds.shape}")
        return jax.jit(lambda k: jax.random.normal(k, sds.shape, jnp.bfloat16)
                       * jnp.bfloat16(0.02))(key)

    flat = [make(path, sds, k) for (path, sds), k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, flat)


def _probe_sequences(p, vocab):
    """The two logits-parity requests: prompt + ``probe_decode`` forced
    continuation tokens each (teacher-forced, so the plain forward and the
    engine see the same tokens whatever either would have sampled)."""
    import numpy as np
    rng = np.random.default_rng(p.seed + 1)
    return [rng.integers(0, vocab, size=n + p.probe_decode).astype(np.int32)
            for n in p.probe_lens]


def _reference_logits(cfg, params, seqs, pad_to):
    """The plain reference: full forward of the float32 model through
    ``mha_reference`` at highest matmul precision on the same weights ->
    [len(seq), vocab] float32 logits per sequence. All sequences pad to one
    length (a multiple of ``pad_to``) so the forward compiles once; causal
    masking keeps the pad out of the rows returned."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.mistral import MistralForCausalLM
    ref_model = MistralForCausalLM(dataclasses.replace(
        cfg, dtype=jnp.float32, remat=False))
    T = max(len(s) for s in seqs)
    T += (-T) % pad_to
    fwd = jax.jit(lambda pr, ids: ref_model.apply({"params": pr}, ids))
    out = []
    with reference_attention(), jax.default_matmul_precision("highest"):
        for s in seqs:
            ids = np.zeros((1, T), np.int32)
            ids[0, :len(s)] = s
            out.append(np.asarray(fwd(params, ids)[0, :len(s)], np.float32))
    return out


def _reference_probe_logits(p, cfg, params, seqs):
    """Per probe sequence the reference logits at the last prompt position
    and after each forced decode token, [probe_decode + 1, vocab]."""
    return [logits[n_prompt - 1:]
            for logits, n_prompt in zip(_reference_logits(cfg, params, seqs, 8),
                                        p.probe_lens)]


def _engine_probe_logits(p, engine, seqs, uid0):
    """The same sequences through ``engine.put`` (which returns logits):
    prompts in SplitFuse chunks, both requests sharing every round, so one
    request decodes through the paged cache while the other still prefills;
    then the forced decode tokens one at a time."""
    import numpy as np
    chunk = p.token_budget // len(seqs)
    feeds, rows = {}, {}
    for i, (s, n_prompt) in enumerate(zip(seqs, p.probe_lens)):
        uid = uid0 + i
        cuts = list(range(0, n_prompt, chunk)) + [n_prompt]
        pieces = [(s[a:b], b == n_prompt) for a, b in zip(cuts, cuts[1:])]
        pieces += [(s[n_prompt + j:n_prompt + j + 1], True)
                   for j in range(p.probe_decode)]
        feeds[uid], rows[uid] = pieces, []
    while any(feeds.values()):
        uids = [u for u, f in feeds.items() if f]
        now = [feeds[u].pop(0) for u in uids]
        logits = engine.put(uids, [toks for toks, _ in now])
        for u, (_, keep), row in zip(uids, now, logits):
            if keep:
                rows[u].append(np.asarray(row, np.float32))
    for u in feeds:
        engine.flush(u)
    return [np.stack(rows[uid0 + i]) for i in range(len(seqs))]


def _rel_rms(got, want):
    import numpy as np
    got = np.concatenate([g.ravel() for g in got]).astype(np.float64)
    want = np.concatenate([w.ravel() for w in want]).astype(np.float64)
    check(np.isfinite(got).all(), "non-finite logits")
    return (float(np.sqrt(np.mean((got - want) ** 2))
                  / np.sqrt(np.mean(want ** 2))),
            float(np.max(np.abs(got - want))))


def _serve_requests(p, sched, uid0, vocab):
    import numpy as np
    rng = np.random.default_rng(p.seed + 2)
    for i, n in enumerate(p.prompt_lens):
        sched.submit(uid0 + i, rng.integers(0, vocab, size=n).astype(np.int32),
                     max_new_tokens=p.new_tokens)
    t0 = time.perf_counter()
    outputs = sched.run_to_completion()   # returns host tokens: synchronous
    dt = time.perf_counter() - t0
    for i in range(len(p.prompt_lens)):
        got = len(outputs[uid0 + i])
        check(got == p.new_tokens,
              f"request {uid0 + i} returned {got} tokens, not {p.new_tokens}")
    return dt, {i: [int(t) for t in outputs[uid0 + i]]
                for i in range(len(p.prompt_lens))}


def _lowered_decode(engine, cfg):
    """The ragged forward lowered at a decode round's shape (4 slots x 8)."""
    import jax.numpy as jnp
    S, Q = 4, 8
    return engine._ragged_forward.lower(
        engine._model_config, engine._params, engine._state.cache_view(),
        jnp.zeros((S, Q), jnp.int32), jnp.ones((S,), jnp.int32),
        jnp.zeros((S,), jnp.int32),
        {"kv": jnp.zeros((S, engine._max_blocks_per_seq), jnp.int32)})


def _serve_once(p, cfg, model, params, probes, kv_dtype):
    """One engine + scheduler life: the requests (cold, then again warm), the
    logits probes, the decode program's text. Returns (notes, probe logits)."""
    import jax

    from deepspeed_tpu.inference.v2 import InferenceEngineV2, engine_v2
    from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler

    engine = InferenceEngineV2(model, params, config={
        "state_manager": {"max_ragged_sequence_count": p.max_seqs,
                          "max_ragged_batch_size": p.token_budget,
                          "max_context": p.max_context,
                          "num_kv_blocks": p.num_kv_blocks,
                          "kv_dtype": kv_dtype},
        "kv_cache": {"block_size": p.block_size}})
    kv = engine._state.kv_cache
    kv_bytes = sum(x.nbytes for x in jax.tree.leaves((kv.fwd_k, kv.fwd_v)))
    sched = SplitFuseScheduler(engine)
    cold_s, tokens = _serve_requests(p, sched, 0, cfg.vocab_size)
    warm_s, tokens2 = _serve_requests(p, sched, 100, cfg.vocab_size)
    check(tokens == tokens2, "greedy decode of the same prompts differed "
                             "between the cold and the warm run")
    check(engine.free_blocks == p.num_kv_blocks,
          f"KV blocks leaked: {engine.free_blocks} free of {p.num_kv_blocks}")
    t0 = time.perf_counter()
    logits = _engine_probe_logits(p, engine, probes, 1000)
    probe_s = time.perf_counter() - t0
    check_kernel_in_program(_lowered_decode(engine, cfg).as_text(),
                            "paged_mha", f"decode program ({kv_dtype} pages)")
    check_dispatch()
    notes = {"kv_dtype": kv_dtype, "kv_pool_bytes": int(kv_bytes),
             "requests_cold_s": round(cold_s, 3),
             "requests_warm_s": round(warm_s, 3),
             "compile_s": round(cold_s - warm_s, 3),
             "probe_s": round(probe_s, 3),
             "programs_compiled": engine_v2.packed_forward._cache_size(),
             "host_sync_count": engine.host_sync_count,
             "peak_bytes": peak_bytes(),
             "first_request_tokens": tokens[0][:8]}
    return notes, logits


def phase_serve(p):
    import jax

    cfg, model = _mistral(p)
    t0 = time.perf_counter()
    params = _seeded_bf16_params(model, p.seed)
    jax.block_until_ready(params)
    weights_s = time.perf_counter() - t0
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    probes = _probe_sequences(p, cfg.vocab_size)
    t0 = time.perf_counter()
    ref_logits = _reference_probe_logits(p, cfg, params, probes)
    ref_s = time.perf_counter() - t0
    release_device_memory()      # the float32 reference program

    base = {"preset": p.name,
            "model": {"layers": cfg.num_hidden_layers,
                      "hidden": cfg.hidden_size,
                      "heads": cfg.num_attention_heads,
                      "kv_heads": cfg.num_key_value_heads,
                      "head_dim": cfg.head_dim,
                      "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size,
                      "window": cfg.sliding_window},
            "weight_bytes": int(weight_bytes),
            "num_kv_blocks": p.num_kv_blocks, "block_size": p.block_size,
            "requests": len(p.prompt_lens), "prompt_lens": list(p.prompt_lens),
            "new_tokens": p.new_tokens}
    lines = []

    notes, fp_logits = _serve_once(p, cfg, model, params, probes, "fp")
    rel, worst = _rel_rms(fp_logits, ref_logits)
    check(rel <= SERVE_REF_REL_RMS_TOL,
          f"engine logits vs float32 reference: relative RMS error {rel:.4f} "
          f"> {SERVE_REF_REL_RMS_TOL} (max abs {worst:.4f})")
    lines.append({"phase": "serve", **base, **notes,
                  "weights_s": round(weights_s, 3),
                  "reference_s": round(ref_s, 3),
                  "ref_rel_rms_err": round(rel, 5),
                  "ref_max_abs_err": round(worst, 5),
                  "kernel_configs": kernel_configs()})
    lines[-1]["bytes_in_use_after_release"] = release_device_memory()
    emit(lines[-1])

    notes, q_logits = _serve_once(p, cfg, model, params, probes, "int8")
    rel, worst = _rel_rms(q_logits, fp_logits)
    check(rel <= SERVE_INT8_REL_RMS_TOL,
          f"int8-page logits vs fp-page logits: relative RMS error {rel:.4f} "
          f"> {SERVE_INT8_REL_RMS_TOL} (max abs {worst:.4f})")
    lines.append({"phase": "serve_int8", **base, **notes,
                  "fp_rel_rms_err": round(rel, 5),
                  "fp_max_abs_err": round(worst, 5),
                  "kernel_configs": kernel_configs()})
    del params
    lines[-1]["bytes_in_use_after_release"] = release_device_memory()
    emit(lines[-1])
    return lines


# ---------------------------------------------------------------------------
# phase: four chips (--chips 4)

def _run_engine_steps(model, topo, batch, config, steps, inspect=False):
    """``steps`` engine steps on ``topo`` from the seed (no parameters handed
    in: the engine creates them born-sharded). Returns (losses, notes)."""
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.parallel import groups
    groups.reset()
    engine = deepspeed_tpu.initialize(model=model, mesh=topo,
                                      config=dict(config))[0]
    losses, step_s = _engine_steps(engine, batch, steps)
    notes = {"first_step_s": round(step_s[0], 3),
             "step_s": [round(s, 4) for s in step_s[1:]]}
    if inspect:
        devices = list(topo.mesh.devices.flat)
        state = engine.state
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                (state.params, state.master, state.opt_state))[0]:
            if not hasattr(leaf, "addressable_shards"):
                continue
            on = {s.device for s in leaf.addressable_shards}
            check(len(on) == len(devices),
                  f"state leaf {jax.tree_util.keystr(path)} {leaf.shape} "
                  f"lives on {len(on)} of {len(devices)} devices")
        mem = device_memory(devices)
        if mem[0] is not None:
            used = [m[0] for m in mem]
            check(min(used) > 0, f"a device holds nothing: {used}")
            check(max(used) <= 1.5 * min(used),
                  f"device memory is not spread evenly: {used}")
            notes["bytes_in_use"] = used
            notes["peak_bytes"] = [m[1] for m in mem]
        lowered = _lowered_train_step(engine, batch)
        check_kernel_in_program(lowered.as_text(), "flash_mha",
                                "sharded train step")
        hlo = lowered.compile().as_text()
        found = [c for c in ("all-gather", "all-reduce", "reduce-scatter")
                 if c in hlo]
        check(found, "the compiled sharded step has no collective")
        notes["collectives"] = found
    del engine
    groups.reset()
    release_device_memory()
    return losses, notes


def phase_multichip(p):
    import jax

    from deepspeed_tpu.parallel.topology import MeshTopology
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs four devices, found {len(devs)}")
    cfg, model = _gpt2(p)
    batch = _train_batch(p, cfg.vocab_size)
    config = {**TRAIN_CONFIG, "train_batch_size": p.train_batch,
              "zero_optimization": {"stage": 3,
                                    "stage3_param_persistence_threshold": 0}}

    before = dispatch_stats()
    sharded, notes4 = _run_engine_steps(
        model, MeshTopology(dp=2, tp=2, devices=devs[:4]), batch, config,
        p.multichip_steps, inspect=True)
    during = dispatch_stats()
    took_kernel = [k for k, n in during.items()
                   if k[0] == "flash_mha" and k[1] == "sharded"
                   and n > before.get(k, 0)]
    check(took_kernel, "flash_mha was not dispatched inside shard_map on the "
                       f"dp2 x tp2 mesh: {during}")
    # on the mesh every fallback is a fault, the single-device ones included
    check_dispatch(allowed_fallbacks=(), since=before)

    single, notes1 = _run_engine_steps(
        model, MeshTopology(dp=1, devices=devs[:1]), batch, config,
        p.multichip_steps)
    check_dispatch(since=during)
    for i, (a, b) in enumerate(zip(sharded, single)):
        check(math.isfinite(a) and math.isfinite(b),
              f"non-finite loss at step {i}: {a} / {b}")
        check(abs(a - b) <= MULTICHIP_LOSS_RTOL * max(1.0, abs(b)),
              f"step {i}: sharded loss {a:.5f} vs single-device {b:.5f} "
              f"(rtol {MULTICHIP_LOSS_RTOL})")
    line = {"phase": "multichip_zero3_dp2_tp2", "preset": p.name,
            "batch": p.train_batch, "seq": p.train_seq,
            "sharded_losses": [round(x, 5) for x in sharded],
            "single_losses": [round(x, 5) for x in single],
            "max_abs_diff": round(max(abs(a - b)
                                      for a, b in zip(sharded, single)), 5),
            "sharded": notes4, "single": notes1,
            "flash_dispatch": ["/".join(k) for k in took_kernel],
            "kernel_configs": kernel_configs()}
    emit(line)
    return line


def _same_or_near_tie(p, cfg, params, requests, got, want):
    """Every request's tokens from ``got`` and ``want`` are identical, or at
    the FIRST position where they differ (same context on both sides) both
    tokens are within ``NEAR_TIE_TOL`` of the top logit of the plain float32
    forward. Past that position the two streams legitimately differ."""
    import numpy as np
    split = {}
    for uid, prompt in requests.items():
        a, b = [int(t) for t in got[uid]], [int(t) for t in want[uid]]
        check(len(a) == len(b) == p.new_tokens,
              f"request {uid}: {len(a)} / {len(b)} tokens, not {p.new_tokens}")
        j = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if j is not None:
            split[uid] = (j, np.concatenate([prompt, a[:j]]).astype(np.int32),
                          (a[j], b[j]))
    notes = {"identical_requests": len(requests) - len(split), "split": {}}
    if not split:
        return notes
    rows = _reference_logits(cfg, params,
                             [ctx for _, ctx, _ in split.values()], 128)
    for (uid, (j, _, cands)), logits in zip(split.items(), rows):
        gaps = [round(float(logits[-1].max() - logits[-1][t]), 4)
                for t in cands]
        notes["split"][str(uid)] = {"at": j, "tokens": list(cands),
                                    "gap_to_top": gaps}
        check(max(gaps) <= NEAR_TIE_TOL,
              f"request {uid} token {j}: {cands[0]} vs {cands[1]} are "
              f"{gaps} below the reference's top logit — not a near-tie "
              f"(tolerance {NEAR_TIE_TOL})")
    return notes


def phase_fleet(p):
    """Leg (b): one prefill and one decode replica of the serve model on two
    chips behind ``SLORouter(PrefillDecodeFleet(...))`` — KV pages ship chip
    to chip by ``device_put`` — against a monolithic replica on a third:
    the same tokens, or a near-tie where they part (``_same_or_near_tie``)."""
    import jax
    import numpy as np

    from deepspeed_tpu.inference.v2.fleet import (PrefillDecodeFleet,
                                                  RequestAdmitted, SLORouter)
    from deepspeed_tpu.inference.v2.replica_group import build_replica
    devs = jax.devices()
    check(len(devs) >= 3, f"the fleet leg needs three devices, found {len(devs)}")
    cfg, model = _mistral(p)
    params = _seeded_bf16_params(model, p.seed)
    engine_config = {
        "state_manager": {"max_ragged_sequence_count": p.max_seqs,
                          "max_ragged_batch_size": p.token_budget,
                          "max_context": p.max_context,
                          "num_kv_blocks": p.fleet_kv_blocks},
        "kv_cache": {"block_size": p.block_size}}
    rng = np.random.default_rng(p.seed + 3)
    requests = {uid: rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
                for uid, n in enumerate(p.fleet_prompt_lens)}

    # plain decode on both sides of the comparison. A decode replica
    # speculates by default, and on the chip the verify forward's chunk
    # shape rounds bf16 differently from one-token decode: the first --chips
    # 4 run of this leg accepted a drafted near-tie the monolithic replica
    # decided the other way (request 3, token 29 of 32). Bit-exactness under
    # speculation is pinned on the CPU only.
    fleet = PrefillDecodeFleet(model, params, prefill_replicas=1,
                               decode_replicas=1, engine_config=engine_config,
                               token_budget=p.token_budget,
                               speculative_default=False)
    router = SLORouter(fleet, slo_ttft_s=600.0, prefix_affinity=False)
    t0 = time.perf_counter()
    for uid, prompt in requests.items():
        outcome = router.submit(uid, prompt, max_new_tokens=p.new_tokens)
        check(isinstance(outcome, RequestAdmitted),
              f"request {uid} was not admitted: {outcome}")
    got = router.run_to_completion()
    fleet_s = time.perf_counter() - t0
    tr = fleet.transport
    check(tr.pages_shipped > 0 and tr.pages_shipped == tr.pages_bound,
          f"pages shipped {tr.pages_shipped} != bound {tr.pages_bound}")
    check(fleet.handoff_fallbacks == 0,
          f"{fleet.handoff_fallbacks} handoffs fell back to re-prefill")
    on = [[d.id for d in jax.tree.leaves(sched.engine._params)[0].devices()]
          for _, sched in fleet.prefill + fleet.decode]

    mesh, sched = build_replica(model, params, [devs[2]],
                                engine_config=engine_config,
                                token_budget=p.token_budget)
    t0 = time.perf_counter()
    with mesh:
        for uid, prompt in requests.items():
            sched.submit(uid, prompt, max_new_tokens=p.new_tokens)
        want = sched.run_to_completion()
    mono_s = time.perf_counter() - t0
    parity = _same_or_near_tie(p, cfg, params, requests, got, want)
    check_dispatch()
    line = {"phase": "fleet_prefill_decode", "preset": p.name,
            "model_layers": cfg.num_hidden_layers,
            "requests": len(requests),
            "prompt_lens": list(p.fleet_prompt_lens),
            "new_tokens": p.new_tokens, "num_kv_blocks": p.fleet_kv_blocks,
            "replica_devices": on, "monolithic_device": devs[2].id,
            "pages_shipped": tr.pages_shipped, "pages_bound": tr.pages_bound,
            "fleet_s": round(fleet_s, 3), "monolithic_s": round(mono_s, 3),
            **parity,
            "memory": device_memory(devs[:3])}
    del fleet, router, sched, params
    release_device_memory()
    emit(line)
    return line


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only what exists across chips — the "
                         "sharded train step and the prefill/decode fleet, "
                         "each with the single-chip run it is compared with")
    args = ap.parse_args(argv)

    for var in ("DS_TPU_PALLAS_INTERPRET", "DS_TPU_DISABLE_PALLAS",
                "DS_TPU_ASSUME_TPU"):
        if os.environ.get(var):
            print(f"chip_smoke: {var} is set; the smoke runs the kernels as "
                  f"a user's program would", file=sys.stderr)
            return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devs[0].platform!r} "
              f"({devs[0].device_kind})", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but only {len(devs)} "
              f"device(s)", file=sys.stderr)
        return 2

    from deepspeed_tpu import telemetry
    from deepspeed_tpu.utils import compile_cache
    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    cache_dir = compile_cache.enable()
    entries_before = compile_cache.entry_count()
    # dispatch records (sharded / fallback / veto) are only kept when on
    telemetry.configure(enabled=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_multichip(REAL)
        phase_fleet(REAL)
    else:
        phase_train(REAL)
        phase_serve(REAL)
    emit({"phase": "cache", "dir": cache_dir,
          "entries_before": entries_before,
          "entries_after": compile_cache.entry_count(),
          "hits": cache["hits"], "misses": cache["misses"],
          "total_s": round(time.perf_counter() - t0, 1)})
    emit({"ok": True, "device": {"platform": devs[0].platform,
                                 "kind": devs[0].device_kind,
                                 "count": len(devs)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
