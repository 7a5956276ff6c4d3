"""Kimi-Linear on the normal serving path at a tiny size:
``InferenceEngineV2`` built by ``engine_factory.build_engine`` over the one
``DSStateManager`` with a one-leaf paged group of the MLA layers alone, a slot
group of two leaves (convolution tails, the KDA matrix state) and a counter
group, against the plain reference's full forward
(``benchmark/references/kimi_linear.py``, the state token by token) in LOGITS,
on seeded weights; the two KDA twins and kernels against the recurrence.

Float32 throughout (``KimiLinearConfig.tiny``): hidden 128, 2 KDA heads of 32,
4 MLA heads of 32 | 16 on a latent of 128, 16 experts of width 128, 3 a token,
one shared, 4 layers (KDA + dense, KDA + experts, MLA + experts, KDA +
experts); block 4.
"""

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import kimi_linear as reference
from deepspeed_tpu.inference.v2 import engine_v2
from deepspeed_tpu.inference.v2.engine_factory import (
    build_engine, resolve_cache_groups, resolve_forward_fn, resolve_verify_fn)
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.model_implementations import moe_layer
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
from deepspeed_tpu.models import kimi_linear as model_file
from deepspeed_tpu.models.kimi_linear import KimiLinearConfig, KimiLinearForCausalLM
from deepspeed_tpu.ops.pallas import kda

#: |logit - reference logit|. Both sides are float32 and differ in the order
#: of sums only (the chunk form and the one-step update against the token by
#: token recurrence, pages and the absorbed form against one full pass, the
#: dispatch-combine einsum against a plain sum over experts): the program
#: reads 1.5e-6 at logits of ~1. The decay left out moves the reference
#: itself by 1.3, beta by 1.2, rotary applied to the MLA by 0.58, all of which
#: this limit has to refuse.
TOLERANCE = 3e-5

ENGINE = {"state_manager": {"max_ragged_sequence_count": 4, "max_ragged_batch_size": 16,
                            "max_context": 128, "num_kv_blocks": 64},
          "kv_cache": {"block_size": 4, "cache_dtype": "fp32"}}


def reference_config(cfg):
    ref = {k: getattr(cfg, k) for k in (
        "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "first_k_dense_replace", "num_shared_experts", "num_experts_per_token",
        "moe_intermediate_size", "routed_scaling_factor", "rms_norm_eps")}
    ref["rope_theta"] = 10000
    ref["num_experts"] = cfg.experts_in_tree
    ref["num_experts_published"] = cfg.num_experts
    ref["linear_attn_config"] = {
        "full_attn_layers": list(cfg.full_attn_layers),
        "kda_layers": [l + 1 for l in cfg.kda_layers], "num_heads": cfg.kda_num_heads,
        "head_dim": cfg.kda_head_dim, "short_conv_kernel_size": cfg.short_conv_kernel_size}
    if cfg.experts_held:
        ref["experts_held"] = dict(zip(("first", "count"), cfg.experts_held))
    return ref


@pytest.fixture(scope="module")
def served():
    cfg = KimiLinearConfig.tiny()
    model = KimiLinearForCausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ref_cfg = reference_config(cfg)
    rng = np.random.default_rng(0)
    ids = {uid: rng.integers(0, cfg.vocab_size, 60).astype(np.int32) for uid in range(4)}
    want = {uid: np.asarray(reference.full_logits(ref_cfg, params, jnp.asarray(t)))
            for uid, t in ids.items()}
    return cfg, model, params, ref_cfg, ids, want


def _engine(served, **over):
    _, model, params = served[:3]
    return build_engine(model, params, {**ENGINE, **over})


def _feed(engine, uid, tokens, chunks, start=0):
    """Put ``tokens`` of ``uid`` in ``chunks``; {position: logits after it}."""
    pos, got = start, {}
    for n in chunks:
        got[pos + n - 1] = engine.put([uid], [tokens[pos:pos + n]])[0]
        pos += n
    return got


def _worst(got, want):
    return max(float(np.max(np.abs(row - want[p]))) for p, row in got.items())


# -- the family on the normal path ---------------------------------------------

def test_the_factory_resolves_the_family(served):
    cfg, model = served[:2]
    assert resolve_forward_fn(model).__module__.endswith("model_implementations.kimi_linear")
    assert resolve_verify_fn(model) is None
    pages, slots, counters = resolve_cache_groups(model)
    assert (pages.name, pages.layers, pages.kv_heads, pages.head_dim, pages.leaves,
            pages.value_dim, pages.window) == ("kv", 1, 1, 256, 1, 128, None)
    assert slots.name == "state" and slots.leaves == (
        ("conv", (3, 4, 3 * 64), "float32"), ("kda", (3, 2, 32, 32), "float32"))
    assert counters.fields == moe_layer.COUNTS
    engine = _engine(served)
    assert isinstance(engine, InferenceEngineV2) and not engine.verify_supported
    groups = engine.kv_stats()["groups"]
    assert set(groups) == {"kv", "state"} and groups["state"]["total"] == 4
    # the published sizes are the defaults: 7 MLA planes of 640 columns beside
    # 20 KDA layers' slots of three tails of 4,096 (in a whole tile of FOUR rows
    # a slot, the last of zeros) and 32 states of 128 x 128
    full = KimiLinearConfig()
    pages, slots, _ = KimiLinearForCausalLM.cache_groups(full)
    assert (pages.layers, pages.head_dim, pages.value_dim) == (7, 640, 512)
    assert slots.leaves == (("conv", (20, 4, 12288), "bfloat16"),
                            ("kda", (20, 32, 128, 128), "float32"))
    assert [full.layer_kind(l) for l in range(4)] == ["kda", "kda", "kda", "mla"]
    assert full.layer_kind(26) == "mla" and full.num_expert_layers == 26
    assert full.softmax_scale == pytest.approx(192 ** -0.5)


def test_an_engine_built_alone_prepares_the_tree_as_build_engine_does(served):
    cfg, model, params, _, ids, _ = served
    alone, built = InferenceEngineV2(model, params, ENGINE), _engine(served)
    assert "kv_b_proj" in params["layers_2"]["self_attn"]
    assert "q_proj" in params["layers_0"]["self_attn"]
    for engine in (alone, built):
        mla, mixer = (engine._params[f"layers_{l}"]["self_attn"] for l in (2, 0))
        assert "kv_b_proj" not in mla and mla["w_uk"].ndim == mla["w_uv"].ndim == 3
        assert not {"q_proj", "k_proj", "v_proj", "q_conv", "f_a_proj", "b_proj"} & set(mixer)
        assert mixer["qkv_proj"].shape == (128, 3 * 64) and mixer["conv"].shape == (4, 3 * 64)
        assert mixer["gates_proj"].shape == (128, 2 * 32 + 2)
    for a, b in zip(jax.tree.leaves(alone._params), jax.tree.leaves(built._params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(alone.put([0], [ids[0][:9]]), built.put([0], [ids[0][:9]]))
    # a tree of shapes gives a tree of shapes (a compile for a described chip)
    from deepspeed_tpu.inference.v2.model_implementations.kimi_linear import prepare_params
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    prepared = prepare_params(cfg, shapes)
    assert jax.tree.map(lambda a: a.shape, prepared) \
        == jax.tree.map(lambda a: a.shape, built._params)


def test_from_hf_reads_the_published_keys_and_refuses_what_is_not_served():
    import json
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs", "kimi-linear-l16-ep16.json")) as f:
        hf = json.load(f)
    share = hf["experts_held"]
    cfg = KimiLinearConfig.from_hf(hf, num_experts=hf["num_experts_published"],
                                   experts_held=(share["first"], share["count"]))
    assert (cfg.num_hidden_layers, cfg.num_experts, cfg.experts_in_tree, cfg.kv_lora_rank,
            cfg.latent_row_width, cfg.vocab_size, cfg.kda_num_heads, cfg.kda_head_dim) \
        == (16, 256, 16, 512, 640, 163840, 32, 128)
    assert cfg.mla_layers == (3, 7, 11, 15) and len(cfg.kda_layers) == 12
    assert cfg.is_dense(0) and not cfg.is_dense(1)
    with pytest.raises(ValueError, match="q_lora_rank"):
        KimiLinearConfig.from_hf({**hf, "q_lora_rank": 1536})
    with pytest.raises(ValueError, match="mla_use_nope"):
        KimiLinearConfig.from_hf({**hf, "mla_use_nope": False})
    with pytest.raises(ValueError, match="num_expert_group"):
        KimiLinearConfig.from_hf({**hf, "num_expert_group": 8})
    lin = dict(hf["linear_attn_config"], kda_layers=[1, 2, 3])
    with pytest.raises(ValueError, match="every layer once"):
        KimiLinearConfig.from_hf({**hf, "linear_attn_config": lin})
    with pytest.raises(ValueError, match="experts_held"):
        KimiLinearConfig.tiny(experts_held=(12, 8))


def test_the_reference_lists_the_tree_the_program_holds(served):
    cfg, _, params, ref_cfg = served[:4]
    for c, r in ((cfg, ref_cfg), (dataclasses.replace(cfg, experts_held=(4, 8)), None)):
        r = r or reference_config(c)
        ours = [(p, s, f, jnp.dtype(d).name, st)
                for p, s, f, d, st in model_file.param_spec(c, jnp.bfloat16)]
        theirs = [(p, s, f, jnp.dtype(d).name, st) for p, s, f, d, st in reference.param_spec(r)]
        assert ours == theirs
    flat = {"/".join(k.key for k in path) for path, _ in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    assert flat == {"/".join(p) for p, *_ in model_file.param_spec(cfg)}
    # the decay's two leaves are mapped by the same function on both sides
    raw = jnp.linspace(-1, 1, 9)
    for ours, theirs in zip(model_file.gate_leaves(raw, raw), reference.gate_leaves(raw, raw)):
        np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))
    a_log, dt_bias = model_file.gate_leaves(raw, raw)
    np.testing.assert_allclose(np.exp(a_log), np.linspace(1, 16, 9), rtol=1e-6)
    np.testing.assert_allclose(jax.nn.softplus(dt_bias), 10.0 ** np.linspace(-3, -1, 9),
                               rtol=1e-4)


# -- (a) prefill in chunks then decode, against the full forward ----------------

@pytest.mark.parametrize("chunks", [
    (16,),                              # a prompt in one chunk
    (16, 16, 9),                        # in several: state, tails and pages carried over
    (16, 16, 5) + (1,) * 20,            # then decode through slots and pages
    (3, 1, 7, 2, 16, 1, 1, 8, 1),       # ragged lengths
], ids=["one-chunk", "chunks", "chunks-then-decode", "ragged"])
def test_chunked_prefill_then_decode_agrees_with_the_full_forward(served, chunks):
    ids, want = served[4], served[5]
    engine = _engine(served)
    assert _worst(_feed(engine, 0, ids[0], chunks), want[0]) < TOLERANCE


def test_chunks_longer_than_the_chunk_forms_chunk_carry_the_state_inside(served):
    """A dispatch of 100 tokens is two chunks of 64 inside ``kda_chunk_ref``
    (the second padded), then a third dispatch starts from what they left."""
    cfg, model, params, ref_cfg = served[:4]
    ids = np.random.default_rng(3).integers(0, cfg.vocab_size, 150).astype(np.int32)
    want = np.asarray(reference.full_logits(ref_cfg, params, jnp.asarray(ids)))
    engine = build_engine(model, params, {**ENGINE, "state_manager": dict(
        ENGINE["state_manager"], max_ragged_batch_size=128, max_context=256)})
    assert _worst(_feed(engine, 0, ids, (100, 5, 1, 1, 40, 1, 1)), want) < TOLERANCE


def test_a_chunk_past_the_rules_crossing_up_projects_in_the_walk_and_agrees_too(served):
    """The MLA layer's read without positions in its second form: at the tiny
    widths the up-projecting read is the lesser from 43 queries a head
    (``kanana2.up_projects``), so chunks of 64 take it (``rope=None``: the
    position columns go in as projected), the tokens left and the decode rows
    stay absorbed over the same plane, and the logits agree within the
    tolerance; the dispatch's counts say which form it took."""
    cfg, model, params, ref_cfg = served[:4]
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, 160).astype(np.int32)
    want = np.asarray(reference.full_logits(ref_cfg, params, jnp.asarray(ids)))
    engine = build_engine(model, params, {**ENGINE, "state_manager": dict(
        ENGINE["state_manager"], max_ragged_batch_size=64, max_context=256)})
    pos, got, forms = 0, {}, []
    for n in (64, 64, 22, 1, 1):
        got[pos + n - 1] = engine.put([0], [ids[pos:pos + n]])[0]
        forms.append((engine.last_counts["latent_up_tokens"],
                      engine.last_counts["latent_absorbed_tokens"]))
        pos += n
    assert _worst(got, want) < TOLERANCE
    assert forms == [(64, 0), (64, 0), (0, 22), (0, 1), (0, 1)]


@pytest.mark.parametrize("rectangle", [False, True], ids=["by-class", "one-rectangle"])
def test_rows_of_different_lengths_with_padding_advance_each_row_by_its_own(
        served, monkeypatch, rectangle):
    """Rounds of four rows of 1-8 real tokens. As the engine dispatches them:
    the rows of one token together as [4, 1] (the one-step update), every
    other row alone as [1, 16] (the chunk form, padded). As ONE [4, 8]
    rectangle: a padded position that advanced the state or shifted the
    convolution's columns would show in the row's next logits."""
    if rectangle:
        monkeypatch.setattr(engine_v2, "dispatch_rows", lambda lengths, short:
                            [(list(range(len(lengths))), 4, 8)])
    ids, want = served[4], served[5]
    engine = _engine(served, state_manager=dict(ENGINE["state_manager"],
                                                max_ragged_batch_size=32))
    pos = {u: 0 for u in range(4)}
    worst = 0.0
    for lengths in [(8, 3, 1, 5), (1, 8, 2, 7), (4, 1, 8, 1), (2, 6, 1, 3), (1, 1, 1, 1)]:
        out = engine.put(list(range(4)), [ids[u][pos[u]:pos[u] + n]
                                          for u, n in enumerate(lengths)])
        ones = lengths.count(1)
        assert engine.last_batch_shapes == (
            [(4, 8)] if rectangle else [(4, 1)] + [(1, 16)] * (4 - ones))
        for u, n in enumerate(lengths):
            pos[u] += n
            worst = max(worst, float(np.max(np.abs(out[u] - want[u][pos[u] - 1]))))
    assert worst < TOLERANCE


@pytest.mark.parametrize("rows", [4, 3], ids=["full", "one-padded-row"])
def test_a_decode_round_of_one_token_rows_advances_slots_and_pages(served, rows):
    ids, want = served[4], served[5]
    engine = _engine(served)
    uids = list(range(rows))
    pos = {}
    for u in uids:
        pos[u] = 7 + 3 * u                       # prompts of 7, 10, 13, 16 tokens
        engine.put([u], [ids[u][:pos[u]]])
    worst = 0.0
    for _ in range(30):
        out = engine.put(uids, [ids[u][pos[u]:pos[u] + 1] for u in uids])
        assert engine.last_batch_shapes == [(4, 1)]
        for u in uids:
            pos[u] += 1
            worst = max(worst, float(np.max(np.abs(out[u] - want[u][pos[u] - 1]))))
    assert worst < TOLERANCE
    for u in uids:
        seq = engine._state.get_sequence(u)
        assert seq.seen_tokens == pos[u] and len(seq.kv_blocks) == -(-pos[u] // 4)
    assert len({engine._state.get_sequence(u).slot for u in uids}) == rows


def test_through_the_pallas_kernels_in_interpret_mode_the_logits_agree_too(monkeypatch):
    """Heads of 128 (what the kernels tile) and a block of 8: a chunk goes
    through ``kda_chunk`` and ``paged_mla``, decode rows through ``kda_step``,
    and agree with the reference as they do through the twins."""
    from deepspeed_tpu import telemetry
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("DS_TPU_DISABLE_PALLAS", raising=False)
    cfg = KimiLinearConfig.tiny(kda_head_dim=128, num_hidden_layers=3, full_attn_layers=(3,))
    model = KimiLinearForCausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, 40).astype(np.int32)
    want = np.asarray(reference.full_logits(reference_config(cfg), params, jnp.asarray(ids)))
    telemetry.reset()
    telemetry.configure(enabled=True)
    try:
        engine = build_engine(model, params, {
            **ENGINE, "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})
        assert _worst(_feed(engine, 0, ids, (16, 11) + (1,) * 4), want) < TOLERANCE
        taken = {k[:2] for k in telemetry.get_telemetry().dispatch_stats}
    finally:
        telemetry.configure(enabled=False)
        telemetry.reset()
    assert ("kda_step", "fallback") not in taken and ("kda_chunk", "fallback") not in taken, taken


# -- (b) the twins and the kernels against the recurrence ------------------------

def _recurrence(q, k, v, g, beta, S0):
    """Token by token: q, k, g [R, T, H, dk], v [R, T, H, dv], beta [R, T, H]."""
    def step(S, xs):
        q, k, v, g, b = xs
        S = S * jnp.exp(g)[..., None]
        u = b[..., None] * (v - jnp.einsum("rhcd,rhc->rhd", S, k))
        S = S + k[..., None] * u[:, :, None, :]
        return S, jnp.einsum("rhcd,rhc->rhd", S, q)
    tm = lambda a: jnp.swapaxes(a, 0, 1)
    S, o = jax.lax.scan(step, S0, (tm(q), tm(k), tm(v), tm(g), tm(beta)))
    return tm(o), S


def _gates(R, T, H, dk, dv, strong, q_len, seed=0):
    """Seeded inputs of the state's update; ``strong``: A = 16 and dt in 0.1-1
    a channel, a decay of e^-1.6 to e^-16 a token, -G past 88 within six;
    ``strong == "alike"``: the seeded decays and keys that are nearly ONE
    direction (k_t . k_i ~ 0.98, as a stream with a large common part makes
    them), so that ``A``'s entries are near ``b`` everywhere below the
    diagonal and an inverse by repeated squaring over the chunk cancels
    powers of 1e17."""
    rng = np.random.default_rng(seed)
    n = lambda *s: rng.standard_normal(s).astype(np.float32)
    unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q, k, v = unit(n(R, T, H, dk)) * dk ** -0.5, unit(n(R, T, H, dk)), n(R, T, H, dv)
    if strong == "alike":
        k = unit(0.9 * n(1, 1, H, dk) + 0.1 * n(R, T, H, dk))
        strong = False
    if strong:
        g = -16.0 * rng.uniform(0.1, 1.0, (R, T, H, dk))
    else:
        g = -rng.uniform(1, 16, (1, 1, H, 1)) * np.exp(
            rng.uniform(np.log(1e-3), np.log(1e-1), (R, T, H, dk)))
    beta = 1 / (1 + np.exp(-n(R, T, H)))
    valid = np.arange(T)[None, :] < np.asarray(q_len)[:, None]
    g = np.where(valid[..., None, None], g, 0.0)
    beta = np.where(valid[..., None], beta, 0.0)
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)], jnp.asarray(valid)


def _pool(n, H, dk, dv, seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((n, H, dk, dv)), jnp.float32)


@pytest.mark.parametrize("strong", [False, True, "alike"],
                         ids=["seeded-gates", "strong-gates", "keys-alike"])
def test_the_chunk_form_and_the_step_agree_with_the_recurrence(strong):
    """Rows of 150, 70 and 0 tokens (two and a half chunks; a chunk and a
    bit; nothing): state carried across chunks inside one call and across two
    calls, a fresh row in a used slot (``keep`` 0), a row of no tokens whose
    slot stays as it was, another sequence's slot untouched."""
    R, T, H, dk, dv = 3, 150, 2, 32, 64
    q_len = jnp.asarray([150, 70, 0])
    (q, k, v, g, beta), valid = _gates(R, T, H, dk, dv, strong, q_len)
    pool, slots, keep = _pool(5, H, dk, dv), jnp.asarray([3, 1, 4]), jnp.asarray([1, 0, 1])
    S0 = jnp.where((keep != 0)[:, None, None, None], pool[slots], 0.0)
    want_o, want_S = _recurrence(q, k, v, g, beta, S0)
    scale = float(jnp.max(jnp.abs(want_o)))
    o, after = kda.kda_chunk_ref(q, k, v, g, beta, pool, slots, keep, q_len)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(after)))
    assert float(jnp.max(jnp.abs(jnp.where(valid[..., None, None], o - want_o, 0)))) < 1e-5 * max(scale, 1)
    assert float(jnp.max(jnp.abs(after[slots] - want_S))) < 1e-5
    np.testing.assert_array_equal(np.asarray(after[4]), np.asarray(pool[4]))   # q_len 0
    for other in (0, 2):
        np.testing.assert_array_equal(np.asarray(after[other]), np.asarray(pool[other]))
    # in two calls: 90 positions, then the rest from what the first left
    cut = lambda a, lo, hi: a[:, lo:hi]
    first = [cut(a, 0, 90) for a in (q, k, v, g, beta)]
    rest = [cut(a, 90, T) for a in (q, k, v, g, beta)]
    o1, mid = kda.kda_chunk_ref(*first, pool, slots, keep, jnp.minimum(q_len, 90))
    o2, end = kda.kda_chunk_ref(*rest, mid, slots, jnp.ones_like(keep),
                                jnp.maximum(q_len - 90, 0))
    both = jnp.concatenate([o1, o2], 1)
    assert float(jnp.max(jnp.abs(jnp.where(valid[..., None, None], both - want_o, 0)))) < 1e-5 * max(scale, 1)
    assert float(jnp.max(jnp.abs(end[slots][:2] - want_S[:2]))) < 1e-5
    # the one-step update, token by token from the same start
    state, outs = pool, []
    for t in range(8):
        o_t, state = kda.kda_step_ref(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t],
                                      state, slots, keep if t == 0 else jnp.ones_like(keep))
        outs.append(o_t)
    want8_o, want8_S = _recurrence(*[a[:, :8] for a in (q, k, v, g, beta)], S0)
    got8 = jnp.stack(outs, 1)
    assert float(jnp.max(jnp.abs(jnp.where(valid[:, :8, None, None], got8 - want8_o, 0)))) < 1e-5 * max(scale, 1)
    assert float(jnp.max(jnp.abs(state[slots][:2] - want8_S[:2]))) < 1e-5


def test_a_factorisation_against_the_chunks_start_overflows_at_the_strong_gates():
    """What the sub-chunks are for: ``(K * Gam)(K / Gam)^T`` over a whole
    chunk, the textbook form, is not finite at gates the test above passes."""
    (q, k, v, g, beta), _ = _gates(1, 64, 2, 32, 64, True, jnp.asarray([64]))
    G = jnp.cumsum(jnp.swapaxes(g, 1, 2), axis=2)              # [R, H, C, dk]
    kk = jnp.swapaxes(k, 1, 2)
    naive = jnp.einsum("rhtc,rhic->rhti", kk * jnp.exp(G), kk * jnp.exp(-G))
    assert not bool(jnp.all(jnp.isfinite(naive)))
    assert float(-G.min()) > 88.0


@pytest.mark.parametrize("strong", [False, True, "alike"],
                         ids=["seeded-gates", "strong-gates", "keys-alike"])
def test_the_pallas_kernels_agree_with_their_twins_in_interpret_mode(strong):
    R, T, H, dk, dv = 3, 100, 2, 128, 128
    q_len = jnp.asarray([100, 40, 0])
    (q, k, v, g, beta), valid = _gates(R, T, H, dk, dv, strong, q_len, seed=2)
    pool, slots, keep = _pool(5, H, dk, dv), jnp.asarray([3, 1, 4]), jnp.asarray([1, 0, 1])
    assert kda.chunk_is_supported(H, dk, dv) and kda.step_is_supported(H, dk, dv)
    assert not kda.chunk_is_supported(2, 32, 32) and not kda.step_is_supported(2, 32, 32)
    want_o, want_pool = kda.kda_chunk_ref(q, k, v, g, beta, pool, slots, keep, q_len)
    o, after = kda.kda_chunk(q, k, v, g, beta, pool, slots, keep, q_len, interpret=True)
    assert float(jnp.max(jnp.abs(jnp.where(valid[..., None, None], o - want_o, 0)))) < 2e-5
    assert float(jnp.max(jnp.abs(after - want_pool))) < 2e-5
    # a position past q_len reads zero; a chunk wholly past it is skipped
    assert float(jnp.max(jnp.abs(o[1, 64:]))) == 0.0 and float(jnp.max(jnp.abs(o[2]))) == 0.0
    one = lambda a: a[:, 0]
    want_o, want_pool = kda.kda_step_ref(one(q), one(k), one(v), one(g), one(beta),
                                         pool, slots, keep)
    o, after = kda.kda_step(one(q), one(k), one(v), one(g), one(beta), pool, slots, keep,
                            interpret=True)
    assert float(jnp.max(jnp.abs(o - want_o))) < 2e-6
    assert float(jnp.max(jnp.abs(after - want_pool))) < 2e-6
    # blocks of whole sublane tiles of heads, else every head a step
    assert [kda._step_heads(H, 16) for H in (32, 24, 16, 8, 2)] == [16, 8, 16, 8, 2]


def test_the_step_kernel_in_blocks_of_heads_agrees_with_every_head_a_step():
    R, H, dk, dv = 2, 16, 8, 128
    (q, k, v, g, beta), _ = _gates(R, 1, H, dk, dv, False, jnp.asarray([1, 1]), seed=4)
    pool, slots, keep = _pool(3, H, dk, dv), jnp.asarray([2, 0]), jnp.asarray([1, 0])
    one = lambda a: a[:, 0]
    want_o, want_pool = kda.kda_step_ref(one(q), one(k), one(v), one(g), one(beta),
                                         pool, slots, keep)
    for heads in (8, 16):
        o, after = kda.kda_step(one(q), one(k), one(v), one(g), one(beta), pool, slots, keep,
                                heads=heads, interpret=True)
        assert float(jnp.max(jnp.abs(o - want_o))) < 2e-6
        assert float(jnp.max(jnp.abs(after - want_pool))) < 2e-6


# -- (c) the shares of the experts add up ------------------------------------------

def test_the_shares_partial_sums_add_up_to_the_uncut_expert_layer(served):
    """16 experts in 4 shares of 4: each share routes over all 16, computes
    its own experts' part; the shared expert is every share's, counted once.
    The parts add up to the uncut reference's expert layer."""
    cfg, _, params, ref_cfg = served[:4]
    lp = params["layers_1"]
    moe = lp["moe"]
    h = jnp.asarray(np.random.default_rng(5).standard_normal((24, cfg.hidden_size)),
                    jnp.float32)
    c = reference._c(ref_cfg)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    with jax.default_matmul_precision("highest"):
        # x + MoE(norm(x)) with unit norm scales read at x = h: the layer's own y
        whole, _ = reference._moe(c, "f32", (), p,
                                  lambda j: (moe["w1"][j], moe["w3"][j], moe["w2"][j]), h)
        normed = reference._rms(h, p["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
    total, counted = jnp.zeros_like(h), np.zeros(4, np.int64)
    shared = tuple(moe["shared"][n] for n in ("w1", "w2", "w3"))
    for first in range(0, 16, 4):
        y, counts = moe_layer.moe_ffn(
            normed, moe["router"]["kernel"], moe["w1"][first:first + 4],
            moe["w2"][first:first + 4], moe["w3"][first:first + 4],
            k=cfg.num_experts_per_token, dtype=jnp.float32, scoring="sigmoid",
            score_bias=moe["router"]["bias"], routed_scale=cfg.routed_scaling_factor,
            shared=shared if first == 0 else None, experts_held=(first, 4), counts=True)
        total = total + y
        counted += np.asarray(counts)
    assert float(jnp.max(jnp.abs(h + total - whole))) < TOLERANCE
    # every routed row landed on exactly one share's experts
    assert counted[0] == 4 * 24 * 3 and counted[2] == 24 * 3 and counted[1] == 0


# -- (d) slots: free, reuse, preempt, resume -------------------------------------------

def test_a_slot_reused_after_flush_starts_from_zero_state(served):
    ids, want = served[4], served[5]
    engine = _engine(served)
    _feed(engine, 0, ids[0], (16, 16))
    slot = engine._state.get_sequence(0).slot
    engine.flush(0)
    got = _feed(engine, 1, ids[1], (16, 5, 1, 1))
    assert engine._state.get_sequence(1).slot == slot
    assert float(jnp.max(jnp.abs(engine._state.slot_pools["kda"][:, slot]))) > 0
    assert _worst(got, want[1]) < TOLERANCE


def test_preempt_then_resume_reproduces_the_uninterrupted_logits(served):
    ids, want = served[4], served[5]
    engine = _engine(served)
    got = _feed(engine, 0, ids[0], (16, 16, 3))
    got2 = _feed(engine, 2, ids[2], (9,))
    held = engine._state.get_sequence(2).slot
    before = jax.tree.map(lambda pool: np.asarray(pool[:, held]), engine._state.slot_pools)
    engine.preempt(0)
    seq = engine._state.get_sequence(0)
    assert seq.is_swapped and seq.slot is None
    assert not engine.can_schedule([0], [1]).success
    # someone else takes the slot and the pages meanwhile
    assert _worst(_feed(engine, 1, ids[1], (16, 9)), want[1]) < TOLERANCE
    assert engine.further_groups_fit_resume(0)
    engine.resume(0)
    assert seq.slot is not None and seq.slot not in (
        engine._state.get_sequence(1).slot, held)
    got.update(_feed(engine, 0, ids[0], (1,) * 10, start=35))
    assert _worst(got, want[0]) < TOLERANCE
    # the sequence that only stood by: its slot as it was, and it goes on right
    after = jax.tree.map(lambda pool: np.asarray(pool[:, held]), engine._state.slot_pools)
    for name in before:
        np.testing.assert_array_equal(before[name], after[name])
    got2.update(_feed(engine, 2, ids[2], (5, 1, 1), start=9))
    assert _worst(got2, want[2]) < TOLERANCE
    for uid in (0, 1, 2):
        engine.flush(uid)
    assert all(g["free"] == g["total"] for g in engine.kv_stats()["groups"].values())
    assert engine.swap_stats == {"swap_outs": 1, "swap_ins": 1}


def test_a_slot_taken_off_and_put_back_by_the_state_manager_keeps_its_four_rows(served):
    """``swap_out_sequence`` / ``swap_in_sequence`` themselves, between two
    decode rounds: they index ``pool[:, slot]`` and never look inside a slot,
    so the tails' tile of four rows (three tails, one of zeros) lands on the
    host and comes back, into ANOTHER slot, value for value, and the sequence
    goes on as if it had stayed."""
    ids, want = served[4], served[5]
    engine = _engine(served)
    state = engine._state
    got = _feed(engine, 0, ids[0], (16, 6, 1, 1))
    seq = state.get_sequence(0)
    first = seq.slot
    rows = lambda slot: {name: np.asarray(pool[:, slot])
                         for name, pool in state.slot_pools.items()}
    before = rows(first)
    assert before["conv"].shape == (3, 4, 3 * 64)
    assert np.abs(before["conv"][:, :3]).min() > 0 and not before["conv"][:, 3].any()
    state.swap_out_sequence(0)
    assert seq.is_swapped and seq.slot is None
    assert [leaf.shape for leaf in seq.group_swap["state"]] == [(3, 4, 192), (3, 2, 32, 32)]
    # another sequence takes the freed slot and writes its own tails there
    _feed(engine, 1, ids[1], (9, 1))
    assert state.get_sequence(1).slot == first
    assert np.abs(rows(first)["conv"] - before["conv"]).max() > 0
    state.swap_in_sequence(0)
    assert seq.slot not in (None, first)
    after = rows(seq.slot)
    for name in before:
        np.testing.assert_array_equal(before[name], after[name])
    got.update(_feed(engine, 0, ids[0], (1, 1, 9, 1), start=24))
    assert _worst(got, want[0]) < TOLERANCE


def _conv_inputs(ref_cfg, params, ids):
    """{KDA layer's index among them: [T, 3 W]}: what the reference's three
    convolutions of that layer read (``h W_q``, ``h W_k``, ``h W_v`` side by
    side), from the reference's own pieces layer by layer."""
    c, out = reference._c(ref_cfg), []
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"].astype(jnp.float32)[jnp.asarray(ids)]
        for l in range(ref_cfg["num_hidden_layers"]):
            p = reference._f32(params[f"layers_{l}"])
            if reference.is_mla(ref_cfg, l):
                x = reference._mla(c, "f32", (), p, x)
            else:
                h = reference._rms(x, p["input_layernorm"]["scale"], c["rms_norm_eps"])
                out.append(np.concatenate([np.asarray(reference.matmul(
                    h, p["self_attn"][n + "_proj"]["kernel"], "f32")) for n in "qkv"], 1))
                x = reference._kda(c, "f32", (), p, x)
            if l < c["first_k_dense_replace"]:
                x = reference._dense(c, "f32", p, x)
            else:
                m = p["moe"]
                x, _ = reference._moe(c, "f32", (), p, lambda j, m=m: (
                    m["w1"][j], m["w3"][j], m["w2"][j]), x)
    return dict(enumerate(out))


@pytest.mark.parametrize("chunks", [(5,), (8, 5, 2), (2, 1, 6)],
                         ids=["padded-chunk", "shorter-than-the-taps", "from-zero"])
def test_a_slots_tails_are_the_last_three_inputs_of_q_k_and_v(served, chunks):
    """After every dispatch (5 tokens in a chunk of 16, then 2: fewer than
    the taps; 2 from a slot that starts at zero, then a decode row) a slot's
    first ``taps - 1`` rows are the reference's inputs of the q, k and v
    convolutions at the sequence's last three positions, zeros before its
    start, q, k and v side by side: a padded position never shifts them. The
    fourth row, which fills the tile, stays zero."""
    cfg, _, params, ref_cfg, ids, _ = served
    engine = _engine(served)
    inputs = _conv_inputs(ref_cfg, params, ids[2][:sum(chunks)])
    W, pos = cfg.kda_width, 0
    for n in chunks:
        engine.put([2], [ids[2][pos:pos + n]])
        pos += n
        if n > 1:
            assert engine.last_batch_shapes[0][1] > n        # padded positions
        slot = engine._state.get_sequence(2).slot
        row = np.asarray(engine._state.slot_pools["conv"][:, slot])
        assert row.shape == (len(inputs), 4, 3 * W) and not row[:, 3].any()
        for m, x in inputs.items():
            want = np.concatenate([np.zeros((3, 3 * W), np.float32), x[:pos]])[-3:]
            assert np.max(np.abs(row[m, :3] - want)) < TOLERANCE, (m, pos)


def test_admission_needs_a_slot(served):
    engine = _engine(served, state_manager=dict(ENGINE["state_manager"],
                                                max_tracked_sequences=16))
    ids = served[4]
    for uid in range(4):
        engine.put([uid], [ids[uid][:5]])
    verdict = engine.can_schedule([9], [4])
    assert not verdict.success and verdict.reason == "no free state slot"
    engine.flush(2)
    assert engine.can_schedule([9], [4]).success


def test_what_this_model_cannot_do_yet_is_refused(served):
    _, model, params = served[:3]
    with pytest.raises(ValueError, match="prefix_caching"):
        build_engine(model, params, {**ENGINE, "prefix_caching": True})
    with pytest.raises(ValueError, match="speculative.enabled"):
        build_engine(model, params, {**ENGINE, "speculative": {"enabled": True}})
    with pytest.raises(ValueError, match="int8"):
        build_engine(model, params, {**ENGINE, "state_manager": dict(
            ENGINE["state_manager"], kv_dtype="int8")})


# -- (e) the comparison is tight enough -------------------------------------------------

@pytest.mark.parametrize("term", ["decay", "nope", "beta", "bias", "routed_scale"])
def test_a_changed_term_fails_the_tolerance(served, term):
    """The decay left out (``a_t = 1``), rotary applied to the MLA (the
    positions this model does not have), ``b_t = 1``, selection without the
    bias, the scale left out: each moves the reference's own logits by far
    more than the comparison allows, so a program that computed it would fail."""
    _, _, params, ref_cfg, ids, want = served
    other = np.asarray(reference.full_logits(ref_cfg, params, jnp.asarray(ids[0]),
                                             leave_out=(term,)))
    assert np.max(np.abs(other - want[0])) > 100 * TOLERANCE


def test_a_dropped_state_or_dropped_tails_fail_the_tolerance(served):
    ids, want = served[4], served[5]
    for leaf in ("kda", "conv"):
        engine = _engine(served)
        _feed(engine, 0, ids[0], (16,))
        pools = dict(engine._state.slot_pools)
        pools[leaf] = jnp.zeros_like(pools[leaf])
        engine._state.slot_pools = pools
        got = _feed(engine, 0, ids[0], (16,), start=16)
        assert _worst(got, want[0]) > 10 * TOLERANCE, leaf


def test_latent_mla_without_positions_builds_no_table(served, monkeypatch):
    """``rope=None``: neither the forward nor ``latent_mla`` touches the
    rotary tables or ``rotary_apply``."""
    from deepspeed_tpu.inference.v2.model_implementations import kanana2
    from deepspeed_tpu.models import llama

    def refuse(*a, **k):
        raise AssertionError("a rotary table or a rotation in a model without positions")

    for name in ("rotary_apply",):
        monkeypatch.setattr(kanana2, name, refuse)
    for name in ("rotary_tables", "rope_frequencies", "rotary_apply"):
        monkeypatch.setattr(llama, name, refuse)
    cfg, model, params = served[:3]
    other = dataclasses.replace(cfg, rms_norm_eps=1.5e-5)      # traced anew
    engine = build_engine(KimiLinearForCausalLM(other), params, ENGINE)
    engine.put([0], [served[4][0][:9]])
    engine.put([0], [served[4][0][9:10]])


# -- what a dispatch reports --------------------------------------------------------------

def _captured(trace_dir, run):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(trace_dir))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans += [(e.name[3:], e.start_ns, dict(e.stats))
                      for e in line.events if e.name.startswith("ds/")]
    return sorted(spans, key=lambda s: s[1])


def test_spans_carry_the_kda_counts_and_the_device_counts_the_experts(served, tmp_path):
    cfg, _, _, _, ids, want = served
    sched = SplitFuseScheduler(_engine(served))
    sched.submit(50, ids[3][:5], max_new_tokens=2)
    sched.run_to_completion()                       # compile outside the capture
    before = {k: getattr(sched, k) for k in (
        "kda_step_rows", "kda_chunk_tokens", "state_slots", "dispatches", "real_tokens",
        "expert_rows")}
    counted = sched._engine.device_counters()

    def run():
        sched.submit(51, ids[0][:37], max_new_tokens=6)
        sched.submit(52, ids[1][:9], max_new_tokens=12)
        sched.run_to_completion()

    spans = _captured(tmp_path, run)
    builds = [a for n, _, a in spans if n == "serving/build"]
    assert len(builds) == sched.dispatches - before["dispatches"] > 0
    for a in builds:
        step = a["chunk_bucket"] == 1
        assert a["kda_step_rows"] == (a["real_tokens"] if step else 0)
        assert a["kda_chunk_tokens"] == (0 if step else a["real_tokens"])
        assert a["kda_layers"] == 3 and 1 <= a["state_slots"] <= 2
        assert a["expert_rows"] == a["real_tokens"] * 3 * 3 and a["expert_rows_padded"] == 0
        assert a["latent_pages"] > 0 and a["latent_row_bytes"] == 256 * 4
        # the MLA layers' read: every dispatch here is under the rule's crossing
        assert (a["latent_up_tokens"], a["latent_absorbed_tokens"]) == (0, a["real_tokens"])
    assert any(a["kda_step_rows"] for a in builds) and any(a["kda_chunk_tokens"] for a in builds)
    moved = lambda key: getattr(sched, key) - before[key]
    assert sum(a["kda_step_rows"] for a in builds) == moved("kda_step_rows")
    assert sum(a["kda_chunk_tokens"] for a in builds) == moved("kda_chunk_tokens")
    assert moved("kda_step_rows") + moved("kda_chunk_tokens") == moved("real_tokens")
    assert sum(a["state_slots"] for a in builds) == moved("state_slots")
    admits = {a["uid"]: a for n, _, a in spans if n == "serving/admit"}
    assert set(admits) == {51, 52} and admits[51]["slot"] != admits[52]["slot"]
    # the device counted every routed row, all on experts this tree holds
    after = sched._engine.device_counters()
    assert set(after) == set(moe_layer.COUNTS)
    assert after["routed_rows"] - counted["routed_rows"] == moved("expert_rows")
    assert after["held_rows"] - counted["held_rows"] == moved("expert_rows")
    assert after["zero_rows"] == 0 and after["experts_hit"] > counted["experts_hit"]
    # greedy decode follows the reference's argmax through the scheduler too
    out = sched.results()[51]
    assert list(out[:1]) == [int(np.argmax(want[0][36]))]
    assert all(g["free"] == g["total"] for g in sched.kv_stats()["groups"].values())
