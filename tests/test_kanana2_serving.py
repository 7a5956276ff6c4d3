"""Kanana-2 (a DeepSeek-V3 tree) on the normal serving path at a tiny size:
``InferenceEngineV2`` built by ``engine_factory.build_engine`` over the one
``DSStateManager`` with ONE paged group of one leaf (a latent row a token),
latent attention in every layer, a leading dense layer and sigmoid-routed
expert layers with a shared expert, against the plain reference's full forward
(``benchmark/references/kanana2.py``) in LOGITS, on seeded weights.

Float32 throughout (``Kanana2Config.tiny``): hidden 128, 4 heads of 32 | 16,
latent 128 (a row of 144 values in 256 columns), 16 experts of width 128, 3 a
token, 2 shared, 3 layers (one dense, two expert); block 4.
"""

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import kanana2 as reference
from deepspeed_tpu.inference.v2.engine_factory import (
    build_engine, resolve_cache_groups, resolve_forward_fn, resolve_verify_fn)
from deepspeed_tpu.inference.v2 import engine_v2
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.model_implementations import kanana2, moe_layer
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
from deepspeed_tpu.models import kanana2 as model_file
from deepspeed_tpu.models.kanana2 import Kanana2Config, Kanana2ForCausalLM

#: |logit - reference logit|. Both sides are float32 and differ in the order
#: of sums only (pages, chunks, the absorbed form and the dispatch-combine
#: einsum against one full pass in the first form with a plain sum over
#: experts): the program reads 1.5e-6 at logits of ~1. The ``k_pe`` term left
#: out moves the reference itself by 1.0, the bias by 0.54, the scale by 0.55,
#: int8 matmuls by 0.40 and bfloat16 pages the program by 3.4e-3, all of which
#: this limit has to refuse.
TOLERANCE = 3e-5

ENGINE = {"state_manager": {"max_ragged_sequence_count": 4, "max_ragged_batch_size": 16,
                            "max_context": 128, "num_kv_blocks": 64},
          "kv_cache": {"block_size": 4, "cache_dtype": "fp32"}}


def reference_config(cfg):
    ref = {k: getattr(cfg, k) for k in (
        "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "first_k_dense_replace", "n_shared_experts", "num_experts_per_tok",
        "moe_intermediate_size", "routed_scaling_factor", "rms_norm_eps", "rope_theta")}
    ref["n_routed_experts"] = cfg.experts_in_tree
    ref["n_routed_experts_published"] = cfg.n_routed_experts
    if cfg.experts_held:
        ref["experts_held"] = dict(zip(("first", "count"), cfg.experts_held))
    return ref


def _share(params, cfg, first, count):
    """The tree of the share ``[first, first + count)`` of a whole tree."""
    out = dict(params)
    for l in range(cfg.first_k_dense_replace, cfg.num_hidden_layers):
        layer = dict(params[f"layers_{l}"])
        layer["moe"] = {**layer["moe"], **{n: layer["moe"][n][first:first + count]
                                           for n in ("w1", "w2", "w3")}}
        out[f"layers_{l}"] = layer
    return out


@pytest.fixture(scope="module")
def served():
    cfg = Kanana2Config.tiny()
    model = Kanana2ForCausalLM(cfg)
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


def test_the_factory_resolves_the_family(served):
    cfg, model = served[:2]
    assert resolve_forward_fn(model).__module__.endswith("model_implementations.kanana2")
    assert resolve_verify_fn(model) is None
    (group,) = resolve_cache_groups(model)
    assert (group.name, group.layers, group.kv_heads, group.head_dim, group.leaves,
            group.value_dim, group.window) == ("kv", 3, 1, 256, 1, 128, None)
    engine = _engine(served)
    assert isinstance(engine, InferenceEngineV2) and not engine.verify_supported
    assert not engine._state.has_further_groups and engine._state.one_leaf
    # the published sizes are the defaults: a row of 576 values in 640 columns
    full = Kanana2Config()
    (group,) = Kanana2ForCausalLM.cache_groups(full)
    assert (group.layers, group.head_dim, group.value_dim) == (48, 640, 512)
    assert full.qk_head_dim == 192 and full.num_expert_layers == 47
    assert full.softmax_scale == pytest.approx(192 ** -0.5)


def test_an_engine_built_alone_prepares_the_tree_as_build_engine_does(served):
    """``InferenceEngineV2(model, tree)`` cuts ``kv_b_proj`` itself (it served
    the uncut tree to a forward that reads ``w_uk`` until the engine took the
    family's ``prepare_params`` over from ``build_engine``): the same leaves,
    the same logits, the caller's tree uncut."""
    cfg, model, params, _, ids, _ = served
    alone, built = InferenceEngineV2(model, params, ENGINE), _engine(served)
    assert "kv_b_proj" in params["layers_0"]["self_attn"]
    for engine in (alone, built):
        attn = engine._params["layers_0"]["self_attn"]
        assert "kv_b_proj" not in attn and attn["w_uk"].ndim == attn["w_uv"].ndim == 3
    for a, b in zip(jax.tree.leaves(alone._params), jax.tree.leaves(built._params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(alone.put([0], [ids[0][:9]]), built.put([0], [ids[0][:9]]))


def test_from_hf_reads_the_published_keys_and_refuses_what_is_not_served():
    import json
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs", "kanana2-l12-ep8.json")) as f:
        hf = json.load(f)
    share = hf["experts_held"]
    cfg = Kanana2Config.from_hf(hf, n_routed_experts=hf["n_routed_experts_published"],
                                experts_held=(share["first"], share["count"]))
    assert (cfg.num_hidden_layers, cfg.n_routed_experts, cfg.experts_in_tree,
            cfg.kv_lora_rank, cfg.latent_row_width, cfg.vocab_size) \
        == (12, 128, 16, 512, 640, 128256)
    with pytest.raises(ValueError, match="q_lora_rank"):
        Kanana2Config.from_hf({**hf, "q_lora_rank": 1536})
    with pytest.raises(ValueError, match="n_group"):
        Kanana2Config.from_hf({**hf, "n_group": 8})
    with pytest.raises(ValueError, match="experts_held"):
        Kanana2Config.tiny(experts_held=(12, 8))


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


@pytest.mark.parametrize("chunks", [
    (16,),                              # a prompt in one chunk
    (16, 16, 9),                        # in several: latent pages carried over
    (16, 16, 5) + (1,) * 20,            # then decode through the pages
    (3, 1, 7, 2, 16, 1, 1, 8, 1),       # ragged lengths
])
def test_chunked_prefill_then_decode_agrees_with_the_full_forward(served, chunks):
    ids, want = served[4], served[5]
    engine = _engine(served)
    assert _worst(_feed(engine, 0, ids[0], chunks), want[0]) < TOLERANCE


def test_chunks_and_decode_rows_through_the_pallas_walk_agree_too(served, monkeypatch):
    """A block the kernel tiles (8 tokens) and interpret mode on: a chunk's
    rows and decode rows are both read by ``paged_mla`` itself, absorbed, and
    agree with the reference as they do through the dense twin."""
    from deepspeed_tpu import telemetry
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("DS_TPU_DISABLE_PALLAS", raising=False)
    cfg, _, params, _, ids, want = served
    telemetry.reset()
    telemetry.configure(enabled=True)
    try:
        # another config, so that the layers are traced anew with the kernel on
        other = dataclasses.replace(cfg, max_position_embeddings=513)
        engine = build_engine(Kanana2ForCausalLM(other), params, {
            **ENGINE, "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})
        assert _worst(_feed(engine, 0, ids[0], (16, 16, 5) + (1,) * 6), want[0]) < TOLERANCE
        taken = {k[:2] for k in telemetry.get_telemetry().dispatch_stats}
    finally:
        telemetry.configure(enabled=False)
        telemetry.reset()
    # the walk's own dispatch records it under the paged kernel's name
    assert ("paged_mha", "tuning") in taken and ("paged_mla", "fallback") not in taken, taken


def _long_prompt(served, n=160):
    cfg, _, params, ref_cfg = served[:4]
    ids = np.random.default_rng(7).integers(0, cfg.vocab_size, n).astype(np.int32)
    return ids, np.asarray(reference.full_logits(ref_cfg, params, jnp.asarray(ids)))


LONG = {**ENGINE, "state_manager": dict(ENGINE["state_manager"], max_ragged_batch_size=64,
                                        max_context=256)}


def test_a_chunk_past_the_rules_crossing_up_projects_in_the_walk_and_agrees_too(served, tmp_path):
    """At the tiny widths (latent 128, heads of 32 + 32) the up-projecting
    read is the lesser from 43 queries a head: a prompt fed in chunks of 64
    takes it (``_latent_attention_up``'s dense twin here), the 22 tokens left
    and the decode rows stay absorbed over the pages both forms wrote, and
    the logits agree with the reference within the tolerance the absorbed
    form alone is held to. Each ``serving/build`` span says which form its
    dispatch's tokens took."""
    cfg = served[0]
    assert [kanana2.up_projects(cfg, Q) for Q in (1, 16, 32, 64, 128)] == \
        [False, False, False, True, True]
    ids, want = _long_prompt(served)
    engine = build_engine(served[1], served[2], LONG)
    got = {}
    spans = _captured(tmp_path, lambda: got.update(
        _feed(engine, 0, ids, (64, 64, 22) + (1,) * 3)))
    assert _worst(got, want) < TOLERANCE
    builds = [a for name, _, a in spans if name == "serving/build"]
    assert [(int(a["chunk_bucket"]), int(a["latent_up_tokens"]),
             int(a["latent_absorbed_tokens"])) for a in builds] == \
        [(64, 64, 0), (64, 64, 0), (32, 0, 22), (1, 0, 1), (1, 0, 1), (1, 0, 1)]
    assert all(int(a["latent_up_tokens"]) + int(a["latent_absorbed_tokens"])
               == int(a["real_tokens"]) for a in builds)


def test_the_up_projecting_walk_itself_serves_a_chunk_in_interpret_mode(monkeypatch):
    """Heads of 128 + 128 columns on a latent of 512 (the published widths,
    a narrow stream and one expert layer): a chunk of 256 goes through
    ``paged_mla``'s ``up`` itself, interpreted, no dense twin is taken, and
    the logits agree with the reference."""
    from deepspeed_tpu import telemetry
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("DS_TPU_DISABLE_PALLAS", raising=False)
    cfg = Kanana2Config.tiny(num_hidden_layers=2, num_attention_heads=2, kv_lora_rank=512,
                             qk_nope_head_dim=128, v_head_dim=128, max_position_embeddings=514)
    model = Kanana2ForCausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    ids = np.random.default_rng(11).integers(0, cfg.vocab_size, 300).astype(np.int32)
    want = np.asarray(reference.full_logits(reference_config(cfg), params, jnp.asarray(ids)))
    assert kanana2.up_projects(cfg, 256) and not kanana2.up_projects(cfg, 128)
    telemetry.reset()
    telemetry.configure(enabled=True)
    try:
        engine = build_engine(model, params, {
            "state_manager": {"max_ragged_sequence_count": 4, "max_ragged_batch_size": 256,
                              "max_context": 512, "num_kv_blocks": 64},
            "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})
        assert _worst(_feed(engine, 0, ids, (256, 40, 1, 1)), want) < TOLERANCE
        taken = {k[:2] for k in telemetry.get_telemetry().dispatch_stats}
    finally:
        telemetry.configure(enabled=False)
        telemetry.reset()
    assert ("paged_mha", "tuning") in taken and ("paged_mla", "fallback") not in taken, taken


def test_sequences_batched_together_agree_and_free_their_pages(served):
    ids, want = served[4], served[5]
    engine = _engine(served)
    for uid in range(4):
        assert _worst(_feed(engine, uid, ids[uid], (16, 4)), want[uid]) < TOLERANCE
    for pos in range(20, 30):                     # a [4, 1] dispatch a step
        rows = engine.put(list(range(4)), [ids[u][pos:pos + 1] for u in range(4)])
        for u in range(4):
            assert float(np.max(np.abs(rows[u] - want[u][pos]))) < TOLERANCE
    groups = engine.kv_stats()["groups"]
    assert set(groups) == {"kv"} and groups["kv"]["leaves"] == 1
    assert groups["kv"]["total"] - groups["kv"]["free"] == 4 * -(-30 // 4)
    for uid in range(4):
        engine.flush(uid)
    assert engine.kv_stats()["groups"]["kv"]["free"] == 64


def test_what_the_tolerance_refuses(served):
    """bfloat16 pages, the ``k_pe`` term left out, the bias left out, int8
    matmuls: each moves the logits by far more than ``TOLERANCE``."""
    cfg, model, params, ref_cfg, ids, want = served
    engine = build_engine(model, params, {**ENGINE, "kv_cache": {
        "block_size": 4, "cache_dtype": "bf16"}})
    assert _worst(_feed(engine, 0, ids[0], (16, 16, 8)), want[0]) > 20 * TOLERANCE
    for term in ("k_pe", "bias", "routed_scale"):
        got = np.asarray(reference.full_logits(ref_cfg, params, jnp.asarray(ids[0]),
                                               leave_out=(term,)))
        assert float(np.max(np.abs(got - want[0]))) > 100 * TOLERANCE, term
    low = np.asarray(reference.full_logits(ref_cfg, params, jnp.asarray(ids[0]), "int8"))
    assert float(np.max(np.abs(low - want[0]))) > 100 * TOLERANCE


def test_the_latent_group_is_one_pool_of_tokens_x_width_x_itemsize(served):
    cfg = served[0]
    engine = _engine(served)
    kv = engine._state.kv_cache
    assert kv.v_pool is None and kv.leaves == 1 and len(kv.fwd) == 1
    pages, bs, width = 64 + 1, 4, cfg.latent_row_width          # a trash page
    assert kv.k_pool.shape == (cfg.num_hidden_layers, pages, 1, bs, width)
    want = cfg.num_hidden_layers * pages * bs * width * 4       # float32 pages here
    assert kv.pool_bytes == want == engine.kv_stats()["groups"]["kv"]["bytes"]
    assert sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(engine._state.cache_view())) == want
    # a K and V pair of the same rows would be twice that
    from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
    pair = BlockedKVCache(cfg.num_hidden_layers, 64, bs, 1, width, "fp32")
    assert pair.pool_bytes == 2 * want
    # at the published sizes in bfloat16: 1,280 B a token and layer
    (g,) = Kanana2ForCausalLM.cache_groups(Kanana2Config())
    assert g.leaves * g.kv_heads * g.head_dim * 2 == 1280


def test_what_a_one_leaf_group_cannot_do_yet_is_refused_by_its_declaration(served):
    """The refusals follow from the group the model declares (``leaves=1``),
    not from a family's name."""
    _, model, params = served[:3]
    sm = ENGINE["state_manager"]
    for over, match in (
            ({"prefix_caching": True}, "prefix_caching is not supported"),
            ({"speculative": {"enabled": True}}, "speculative.enabled"),
            ({"state_manager": dict(sm, kv_dtype="int8")}, "kv_dtype int8"),
            ({"state_manager": dict(sm, host_kv_blocks=8)}, "host_kv_blocks"),
            ({"state_manager": dict(sm, host_kv_blocks=8, nvme_kv_blocks=8)},
             "host_kv_blocks|nvme_kv_blocks")):
        with pytest.raises(ValueError, match=match):
            build_engine(model, params, {**ENGINE, **over})
    engine = _engine(served)
    engine.put([0], [served[4][0][:6]])
    with pytest.raises(ValueError, match="page export is not supported"):
        engine.export_pages(0)
    with pytest.raises(ValueError, match="page import is not supported"):
        engine.import_pages_many({"n": 0, "k": None, "v": None, "seqs": []})
    with pytest.raises(ValueError, match="rollback is not supported"):
        engine.rollback(0, 1)
    with pytest.raises(RuntimeError, match="no verify forward"):
        engine._forward_device([0], [served[4][0][6:8]], verify_k=2)
    kv = engine._state.kv_cache
    with pytest.raises(ValueError, match="one leaf"):
        kv.export_blocks([0])
    from deepspeed_tpu.inference.v2.ragged.cache_groups import PagedGroup
    from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
    with pytest.raises(ValueError, match="kv_dtype int8"):
        BlockedKVCache(1, 4, 4, 1, 128, kv_dtype="int8", leaves=1)
    with pytest.raises(ValueError, match="value_dim"):
        PagedGroup("kv", 1, 1, 128, leaves=1)
    with pytest.raises(ValueError, match="value_dim"):
        PagedGroup("kv", 1, 1, 128, value_dim=64)


def test_a_preempted_sequence_takes_its_one_leaf_to_the_host_and_back(served):
    ids, want = served[4], served[5]
    engine = _engine(served)
    got = _feed(engine, 0, ids[0], (16, 16, 3))
    engine.preempt(0)
    assert engine.kv_stats()["groups"]["kv"]["free"] == 64
    assert _worst(_feed(engine, 1, ids[1], (16, 9)), want[1]) < TOLERANCE
    engine.resume(0)
    got.update(_feed(engine, 0, ids[0], (1,) * 10, start=35))
    assert _worst(got, want[0]) < TOLERANCE


# -- the router ---------------------------------------------------------------

def _router_case(seed=0, T=64, D=32, E=16):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(D, E)) / np.sqrt(D), jnp.float32)
    return x, wg


def test_the_bias_changes_the_selection_and_never_the_weights():
    x, wg = _router_case()
    E, k, scale = wg.shape[1], 3, 2.448
    scores = np.asarray(jax.nn.sigmoid(x @ wg))
    zero = jnp.zeros(E)
    bias = jnp.asarray(np.where(np.arange(E) == 5, 0.4, 0.0), jnp.float32)
    w0, i0 = moe_layer.sigmoid_router(x, wg, zero, k, scale)
    w1, i1 = moe_layer.sigmoid_router(x, wg, bias, k, scale)
    i0, i1, w1 = np.asarray(i0), np.asarray(i1), np.asarray(w1)
    assert (np.sort(i0, -1) != np.sort(i1, -1)).any(1).sum() > 5      # it selects
    assert (i1 == 5).any(1).sum() > (i0 == 5).any(1).sum()
    # the weights are the UNBIASED scores of the chosen, normalised over all
    # k chosen, times the scale: they sum to the scale, bias or not
    chosen = np.take_along_axis(scores, i1, -1)
    np.testing.assert_allclose(w1, chosen / chosen.sum(-1, keepdims=True) * scale, rtol=1e-6)
    np.testing.assert_allclose(w1.sum(-1), scale, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w0).sum(-1), scale, rtol=1e-6)
    # the reference's router says the same
    c = {"num_experts_per_tok": k, "routed_scaling_factor": scale}
    p = {"moe": {"router": {"kernel": wg, "bias": bias}}}
    with jax.default_matmul_precision("highest"):
        gate, idx = reference.router(c, "f32", (), p, x)
    assert (np.sort(np.asarray(idx), -1) == np.sort(i1, -1)).all()
    np.testing.assert_allclose(np.take_along_axis(np.asarray(gate), i1, -1), w1, rtol=1e-5)


def _layer_case(E=16, k=3, D=128, F=128, T=24, seed=1):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]), jnp.float32)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.05, 0.05, E), jnp.float32)
    return dict(x=x, wg=n(D, E), w1=n(E, D, F), w2=n(E, F, D), w3=n(E, D, F), bias=bias,
                shared=(n(D, 2 * F), n(2 * F, D), n(D, 2 * F)), k=k)


def _moe(case, held=None, shared=True, valid=None, **kw):
    cut = (lambda w: w) if held is None else (lambda w: w[held[0]:held[0] + held[1]])
    return moe_layer.moe_ffn(
        case["x"], case["wg"], cut(case["w1"]), cut(case["w2"]), cut(case["w3"]),
        k=case["k"], dtype=jnp.float32, valid=valid, scoring="sigmoid",
        score_bias=case["bias"], routed_scale=2.448,
        shared=case["shared"] if shared else None, experts_held=held, **kw)


@pytest.mark.parametrize("backend", ["einsum", "gmm"])
def test_the_shares_add_up_to_the_uncut_layer(monkeypatch, backend):
    """The outputs of all eight shares of a layer, the shared expert counted
    once, sum to the whole layer's, which is the plain reference's uncut
    layer; in the einsum oracle and in the grouped GEMM (interpret mode)."""
    if backend == "gmm":
        monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    case = _layer_case()
    whole = np.asarray(_moe(case, force_einsum=backend == "einsum"))
    routed = sum(np.asarray(_moe(case, held=(first, 2), shared=False,
                                 force_einsum=backend == "einsum"))
                 for first in range(0, 16, 2))
    only_shared = np.asarray(_moe(case, held=(0, 2), force_einsum=True)) \
        - np.asarray(_moe(case, held=(0, 2), shared=False, force_einsum=True))
    np.testing.assert_allclose(routed + only_shared, whole, atol=2e-5)
    # the uncut reference layer (its residual taken off; its norm made the identity)
    c = {"num_experts_per_tok": case["k"], "routed_scaling_factor": 2.448,
         "rms_norm_eps": 0.0, "held": (0, 16)}
    s1, s2, s3 = case["shared"]
    p = {"post_attention_layernorm": {"scale": 1.0},
         "moe": {"router": {"kernel": case["wg"], "bias": case["bias"]},
                 "shared": {"w1": s1, "w2": s2, "w3": s3}}}
    x = case["x"] / jnp.sqrt(jnp.mean(case["x"] ** 2, -1, keepdims=True))
    with jax.default_matmul_precision("highest"):
        ref, _ = reference._moe(c, "f32", (), p,
                                lambda j: (case["w1"][j], case["w3"][j], case["w2"][j]), x)
    mine = np.asarray(_moe(dict(case, x=x), force_einsum=backend == "einsum"))
    np.testing.assert_allclose(mine, np.asarray(ref - x), atol=2e-5)


def test_a_padded_slot_and_a_row_that_is_not_held_take_no_gemm_rows(monkeypatch):
    """What the grouped GEMM is handed: group sizes that count the rows of
    valid tokens whose expert is held, and nothing else."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    case = _layer_case()
    valid = jnp.arange(24) < 17                       # 7 padded slots
    held = (6, 4)
    _, idx = moe_layer.sigmoid_router(case["x"], case["wg"], case["bias"], case["k"], 2.448)
    idx = np.asarray(idx)
    lands = (idx >= 6) & (idx < 10) & np.asarray(valid)[:, None]
    seen = []
    real = gg._gmm

    def spy(lhs, rhs, group_sizes, tiling, interpret):
        seen.append(np.asarray(group_sizes))
        return real(lhs, rhs, group_sizes, tiling, interpret)

    monkeypatch.setattr(gg, "_gmm", spy)
    with jax.disable_jit():
        out = np.asarray(_moe(case, held=held, valid=valid))
    assert len(seen) == 3 and all(g.shape == (4,) for g in seen)
    want = np.bincount(idx[lands] - 6, minlength=4)
    assert all((g == want).all() for g in seen) and want.sum() == lands.sum() < 17 * 3
    assert not out[17:].any() and np.isfinite(out).all()
    # a token none of whose experts are held takes the shared expert alone
    none = ~lands.any(1) & np.asarray(valid)
    assert none.any()
    alone = np.asarray(_moe(case, held=held, valid=valid, force_einsum=True)) \
        - np.asarray(_moe(case, held=held, valid=valid, shared=False, force_einsum=True))
    np.testing.assert_allclose(out[none], alone[none], atol=2e-5)
    np.testing.assert_allclose(
        out, np.asarray(_moe(case, held=held, valid=valid, force_einsum=True)), atol=2e-5)


def test_softmax_callers_are_as_they_were():
    """Mixtral's and Mellum2's call (no new argument) routes by softmax,
    top-k, renormalised, over every expert, with no shared expert."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    case = _layer_case()
    got = moe_layer.moe_ffn(case["x"], case["wg"], case["w1"], case["w2"], case["w3"],
                            k=3, dtype=jnp.float32, force_einsum=True)
    vals, idx = gg.topk_router(case["x"], case["wg"], 3)
    want = moe_layer._moe_ffn_einsum(case["x"], vals, idx, jnp.ones(24, bool),
                                     case["w1"], case["w2"], case["w3"], jnp.float32)
    assert (np.asarray(got) == np.asarray(want)).all()
    with pytest.raises(ValueError, match="unknown router scoring"):
        moe_layer.moe_ffn(case["x"], case["wg"], case["w1"], case["w2"], case["w3"],
                          k=3, dtype=jnp.float32, scoring="tanh")


def test_a_share_of_the_model_agrees_with_the_reference_given_the_same_share(served):
    """The engine told which experts it holds (a range that does not start at
    0) against the reference's full forward under the same share: the partial
    result goes on to the next layer in both."""
    cfg, _, params, _, ids, whole = served
    held = dataclasses.replace(cfg, experts_held=(5, 6))
    tree = _share(params, cfg, 5, 6)
    want = np.asarray(reference.full_logits(reference_config(held), tree, jnp.asarray(ids[0])))
    assert float(np.max(np.abs(want - whole[0]))) > 1e-2          # a share is not the whole
    engine = build_engine(Kanana2ForCausalLM(held), tree, ENGINE)
    got = _feed(engine, 0, ids[0], (16, 16, 5) + (1,) * 8)
    assert _worst(got, want) < TOLERANCE


# -- spans and counters ---------------------------------------------------------

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


def test_scheduler_serves_and_its_counters_equal_the_spans_sums(served, tmp_path):
    """Through ``SplitFuseScheduler`` under a share of the experts: greedy
    streams start at the reference's argmax, and the plain counters equal the
    sums of the ``serving/build`` spans' attributes: ``latent_pages``, and
    ``expert_rows`` (rows ROUTED: real tokens x 3 x the two expert layers,
    whatever lands on the experts held)."""
    cfg, _, params, _, ids, _ = served
    held = dataclasses.replace(cfg, experts_held=(8, 4))
    tree = _share(params, cfg, 8, 4)
    engine = build_engine(Kanana2ForCausalLM(held), tree, ENGINE)
    sched = SplitFuseScheduler(engine)
    prompts = {u: ids[u][:20 + 5 * u] for u in range(4)}

    def run():
        for u, p in prompts.items():
            sched.submit(u, p, max_new_tokens=12)
        sched.run_to_completion()

    spans = _captured(tmp_path, run)
    builds = [a for name, _, a in spans if name == "serving/build"]
    total = lambda key: sum(int(a[key]) for a in builds)
    assert builds and sched.dispatches == len(builds)
    assert all(int(a["experts_held"]) == 4 and int(a["experts_routed_over"]) == 16
               and int(a["latent_row_bytes"]) == 256 * 4 for a in builds)
    assert sched.real_tokens == total("real_tokens")
    assert sched.expert_rows == total("expert_rows") == sched.real_tokens * 3 * 2
    assert sched.expert_rows_padded == total("expert_rows_padded") == 0
    assert sched.latent_pages == total("latent_pages") > 0
    assert sched.live_pages == total("live_pages") > 0
    assert all(int(a["latent_pages"]) >= int(a["live_pages"]) for a in builds)
    want = {u: np.asarray(reference.full_logits(reference_config(held), tree,
                                                jnp.asarray(p))) for u, p in prompts.items()}
    for u, p in prompts.items():
        if u in sched._requests:
            assert sched._requests[u].generated[0] == int(np.argmax(want[u][len(p) - 1]))


def test_a_program_lowers_one_function_a_layer_kind(served, monkeypatch):
    """The layers of a dispatch call TWO lowered functions, the dense layer
    and the expert layer (``kanana2._layer`` is a jit of its own with a
    static ``dense``), not one inlined copy a layer."""
    import re
    cfg, _, params = served[:3]
    deep = dataclasses.replace(cfg, num_hidden_layers=5)
    engine = build_engine(Kanana2ForCausalLM(deep),
                          Kanana2ForCausalLM(deep).init_params(jax.random.PRNGKey(1)), ENGINE)
    program, got = engine_v2.packed_forward, []

    class Captured(Exception):
        pass

    def spy(*args):
        got.extend(args)
        raise Captured

    monkeypatch.setattr(engine_v2, "packed_forward", spy)
    with pytest.raises(Captured):
        engine.put([0, 1], [np.zeros(1, np.int32)] * 2)
    text = program.lower(*got).as_text()
    assert len(set(re.findall(r"func\.func private @(_layer\w*)", text))) == 2
    assert len(re.findall(r"call @_layer", text)) == 5
    # W_UK and W_UV were cut out of kv_b_proj when the engine was built
    attn = got[3]["layers_1"]["self_attn"]
    assert "kv_b_proj" not in attn and attn["w_uk"].shape == (128, 4, 32) == attn["w_uv"].shape
