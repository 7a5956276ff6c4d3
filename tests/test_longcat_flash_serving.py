"""LongCat-Flash-Chat on the normal serving path at a tiny size:
``InferenceEngineV2`` built by ``engine_factory.build_engine`` over the one
``DSStateManager`` with ONE paged group of one leaf whose planes are the
layers' two attentions, beside a counter group; shortcut-connected double
layers (two latent attentions with a low-rank query, two dense FFNs, one
expert layer whose router's last columns are identity experts), against the
plain reference's full forward (``benchmark/references/longcat_flash.py``) in
LOGITS, on seeded weights.

Float32 throughout (``LongcatFlashConfig.tiny``): hidden 256, 4 heads of 32 |
16, latent 128 (a row of 144 values in 256 columns; ``s_kv`` = sqrt(2)), a
query rank of 64 (``s_q`` = 2), dense FFNs of 256, 16 experts of width 128 and
8 zero experts, 4 a token, 2 double layers; block 4.
"""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import longcat_flash as reference
from deepspeed_tpu.inference.v2 import engine_v2
from deepspeed_tpu.inference.v2.engine_factory import (
    build_engine, resolve_cache_groups, resolve_forward_fn, resolve_report_fn,
    resolve_verify_fn)
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.model_implementations import moe_layer
from deepspeed_tpu.inference.v2.ragged.cache_groups import (
    CounterGroup, PagedGroup)
from deepspeed_tpu.models import longcat_flash as model_file
from deepspeed_tpu.models.longcat_flash import (
    COUNTER_FIELDS, LongcatFlashConfig, LongcatFlashForCausalLM, dense_forward)

#: |logit - reference logit|. Both sides are float32 and differ in the order
#: of sums only (pages, chunks, the absorbed form, the scales folded into the
#: norms and the dispatch-combine einsum against one full pass in the first
#: form with a plain sum over experts): the program reads ~1e-6 at logits of
#: ~1. The zero experts' term left out moves the reference itself by 0.3+, a
#: scale by 0.2+, int8 matmuls by 0.1+ and bfloat16 pages the program by
#: ~3e-3, all of which this limit has to refuse.
TOLERANCE = 3e-5

ENGINE = {"state_manager": {"max_ragged_sequence_count": 4, "max_ragged_batch_size": 16,
                            "max_context": 128, "num_kv_blocks": 64},
          "kv_cache": {"block_size": 4, "cache_dtype": "fp32"}}

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference_config(cfg):
    ref = {k: getattr(cfg, k) for k in (
        "vocab_size", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
        "num_layers", "num_attention_heads", "kv_lora_rank", "q_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "zero_expert_num", "moe_topk",
        "routed_scaling_factor", "rms_norm_eps", "rope_theta", "mla_scale_q_lora",
        "mla_scale_kv_lora")}
    ref["n_routed_experts"] = cfg.experts_in_tree
    ref["n_routed_experts_published"] = cfg.n_routed_experts
    if cfg.experts_held:
        ref["experts_held"] = dict(zip(("first", "count"), cfg.experts_held))
    return ref


def _share(params, cfg, first, count):
    """The tree of the share ``[first, first + count)`` of a whole tree."""
    out = dict(params)
    for l in range(cfg.num_layers):
        layer = dict(params[f"layers_{l}"])
        layer["moe"] = {**layer["moe"], **{n: layer["moe"][n][first:first + count]
                                           for n in ("w1", "w2", "w3")}}
        out[f"layers_{l}"] = layer
    return out


@pytest.fixture(scope="module")
def served():
    cfg = LongcatFlashConfig.tiny()
    model = LongcatFlashForCausalLM(cfg)
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


# -- the family ---------------------------------------------------------------

def test_the_factory_resolves_the_family(served):
    cfg, model = served[:2]
    assert resolve_forward_fn(model).__module__.endswith("model_implementations.longcat_flash")
    assert resolve_verify_fn(model) is None
    paged, counters = resolve_cache_groups(model)
    assert (paged.name, paged.layers, paged.kv_heads, paged.head_dim, paged.leaves,
            paged.value_dim, paged.window) == ("kv", 2 * 2, 1, 256, 1, 128, None)
    assert isinstance(counters, CounterGroup) and counters.fields == COUNTER_FIELDS
    assert COUNTER_FIELDS == moe_layer.COUNTS + ("dispatches",)
    engine = _engine(served)
    assert isinstance(engine, InferenceEngineV2) and not engine.verify_supported
    # the counter group is no sequence's: no further group, nothing refused for it
    assert not engine._state.has_further_groups and engine._state.one_leaf
    # the published sizes are the defaults: 56 planes of a 640-column row
    full = LongcatFlashConfig()
    paged, _ = LongcatFlashForCausalLM.cache_groups(full)
    assert (paged.layers, paged.head_dim, paged.value_dim) == (56, 640, 512)
    assert full.qk_head_dim == 192 and full.router_width == 768
    assert full.q_scale == 2.0 and full.kv_scale == pytest.approx(12 ** 0.5)
    assert full.softmax_scale == pytest.approx(192 ** -0.5)


def test_from_hf_reads_the_published_keys_and_refuses_what_is_not_served():
    with open(os.path.join(HERE, "benchmark", "configs", "longcat-flash-l4-ep32.json")) as f:
        hf = json.load(f)
    share = hf["experts_held"]
    cfg = LongcatFlashConfig.from_hf(hf, n_routed_experts=hf["n_routed_experts_published"],
                                     experts_held=(share["first"], share["count"]))
    assert (cfg.num_layers, cfg.n_routed_experts, cfg.experts_in_tree, cfg.zero_expert_num,
            cfg.moe_topk, cfg.latent_row_width, cfg.vocab_size, cfg.num_attention_heads) \
        == (4, 512, 16, 256, 12, 640, 16384, 64)
    with pytest.raises(ValueError, match="rope_scaling"):
        LongcatFlashConfig.from_hf({**hf, "rope_scaling": {"factor": 10}})
    with pytest.raises(ValueError, match="norm_topk_prob"):
        LongcatFlashConfig.from_hf({**hf, "norm_topk_prob": True})
    with pytest.raises(ValueError, match="identity"):
        LongcatFlashConfig.from_hf({**hf, "zero_expert_type": "copy"})
    with pytest.raises(ValueError, match="experts_held"):
        LongcatFlashConfig.tiny(experts_held=(12, 8))


def test_the_configuration_files_bytes_follow_from_its_keys():
    """The arithmetic the configuration file and the cell's ``why`` state,
    recomputed from the file's own keys through the program's parameter list."""
    with open(os.path.join(HERE, "benchmark", "configs", "longcat-flash-l4-ep32.json")) as f:
        hf = json.load(f)
    share = hf["experts_held"]
    cfg = LongcatFlashConfig.from_hf(hf, n_routed_experts=hf["n_routed_experts_published"],
                                     experts_held=(share["first"], share["count"]))
    size = lambda rows: sum(int(np.prod(s)) for _, s, *_ in rows)
    rows = model_file.param_spec(cfg)
    layer0 = [r for r in rows if r[0][0] == "layers_0"]
    matrices = lambda part: size([r for r in layer0 if r[0][1] == part and len(r[1]) > 1])
    assert matrices("self_attn_0") == 90_570_752 == matrices("self_attn_1")
    assert matrices("mlps_0") == 226_492_416 == 3 * 6144 * 12288
    experts = size([r for r in layer0 if r[0][1] == "moe" and r[4]])
    assert experts == 16 * 37_748_736 == 603_979_776
    router = 6144 * 768
    outside = size(layer0) - experts
    assert outside - router == 2 * 90_570_752 + 2 * 226_492_416 + 768 + 4 * 6144 + 2 * (1536 + 512)
    weights_bytes = 2 * size([r for r in rows if len(r[1]) > 1])
    assert 10.34e9 < weights_bytes < 10.35e9                    # "10.35 GB of weights"
    assert f"{4 * 2 * size(layer0) / 4e9:.2f}" == "2.49"        # a layer, GB
    sm, kv = hf["engine"]["state_manager"], hf["engine"]["kv_cache"]
    paged, _ = LongcatFlashForCausalLM.cache_groups(cfg)
    pool = (sm["num_kv_blocks"] + 1) * kv["block_size"] * paged.layers * paged.head_dim * 2
    assert 3.2e9 < pool < 3.22e9 and 13.5e9 < weights_bytes + pool < 13.6e9
    assert weights_bytes + pool > 0.75 * 16e9
    # a whole layer's 512 experts: what no chip holds
    assert f"{2 * (outside + 512 * 37_748_736) / 1e9:.1f}" == "39.9"


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
    assert model_file.ROUTER_BIAS_STD == reference.ROUTER_BIAS_STD


def test_the_dense_forward_agrees_with_the_reference(served):
    cfg, _, params, ref_cfg, ids, want = served
    got = np.asarray(dense_forward(cfg, params, jnp.asarray(ids[0])))
    assert float(np.max(np.abs(got - want[0]))) < TOLERANCE
    held = dataclasses.replace(cfg, experts_held=(4, 8))
    got = np.asarray(dense_forward(held, _share(params, cfg, 4, 8), jnp.asarray(ids[1])))
    ref = np.asarray(reference.full_logits(reference_config(held), _share(params, cfg, 4, 8),
                                           jnp.asarray(ids[1])))
    assert float(np.max(np.abs(got - ref))) < TOLERANCE
    assert float(np.max(np.abs(ref - want[1]))) > 100 * TOLERANCE   # a share is not the whole


# -- through the engine and the latent cache ----------------------------------

@pytest.mark.parametrize("chunks", [
    (16,),                              # a prompt in one chunk
    (16, 16, 9),                        # in several: both planes' pages carried over
    (16, 16, 5) + (1,) * 20,            # then decode through the pages
    (3, 1, 7, 2, 16, 1, 1, 8, 1),       # ragged lengths
])
def test_chunked_prefill_then_decode_agrees_with_the_full_forward(served, chunks):
    ids, want = served[4], served[5]
    engine = _engine(served)
    assert _worst(_feed(engine, 0, ids[0], chunks), want[0]) < TOLERANCE


def test_a_chunk_past_the_rules_crossing_up_projects_in_the_walk_and_agrees_too(served):
    """At the tiny widths the up-projecting read is the lesser from 43
    queries a head (``kanana2.up_projects``): a prompt fed in chunks of 64
    takes it in BOTH attentions of every double layer, the tokens left and
    the decode rows stay absorbed over the same planes, and the logits agree
    within the tolerance; the dispatch's counts say which form it took."""
    cfg, model, params, ref_cfg = served[:4]
    ids = np.random.default_rng(7).integers(0, cfg.vocab_size, 160).astype(np.int32)
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


def test_a_share_of_the_experts_through_the_engine_agrees_with_the_references_share(served):
    cfg, _, params, _, ids, _ = served
    held = dataclasses.replace(cfg, experts_held=(8, 4))
    tree = _share(params, cfg, 8, 4)
    want = np.asarray(reference.full_logits(reference_config(held), tree, jnp.asarray(ids[2])))
    engine = build_engine(LongcatFlashForCausalLM(held), tree, ENGINE)
    assert _worst(_feed(engine, 0, ids[2], (16, 7) + (1,) * 5), want) < TOLERANCE


def test_chunks_and_decode_rows_through_the_pallas_walk_and_the_grouped_gemm(served, monkeypatch):
    """A block the kernel tiles (8 tokens) and interpret mode on: both
    attentions read through ``paged_mla`` itself and the experts run the
    grouped GEMM; no dense fallback is taken."""
    from deepspeed_tpu import telemetry
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("DS_TPU_DISABLE_PALLAS", raising=False)
    cfg, _, params, _, ids, want = served
    telemetry.reset()
    telemetry.configure(enabled=True)
    try:
        other = dataclasses.replace(cfg, max_position_embeddings=513)   # traced anew
        engine = build_engine(LongcatFlashForCausalLM(other), params, {
            **ENGINE, "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})
        assert _worst(_feed(engine, 0, ids[0], (16, 16, 5) + (1,) * 6), want[0]) < TOLERANCE
        taken = set(telemetry.get_telemetry().dispatch_stats)
    finally:
        telemetry.configure(enabled=False)
        telemetry.reset()
    # both kernels resolved their tiles; the one "fallback" either records is
    # the registry's note that no mesh is live (one device), not a dense twin
    assert {k[:2] for k in taken} >= {("paged_mha", "tuning"), ("moe_ffn_gmm", "tuning")}, taken
    assert not [k for k in taken if k[0] == "paged_mla"], taken
    assert {k[2] for k in taken if k[1] == "fallback"} == {"no_mesh"}, taken


def test_sequences_batched_together_agree_and_free_both_planes(served):
    cfg, ids, want = served[0], served[4], served[5]
    engine = _engine(served)
    for uid in range(4):
        assert _worst(_feed(engine, uid, ids[uid], (16, 4)), want[uid]) < TOLERANCE
    for pos in range(20, 30):                     # a [4, 1] dispatch a step
        rows = engine.put(list(range(4)), [ids[u][pos:pos + 1] for u in range(4)])
        for u in range(4):
            assert float(np.max(np.abs(rows[u] - want[u][pos]))) < TOLERANCE
    groups = engine.kv_stats()["groups"]
    assert set(groups) == {"kv"} and groups["kv"]["leaves"] == 1
    # a page index spans every plane: a sequence holds ceil(30 / 4) of them
    assert groups["kv"]["total"] - groups["kv"]["free"] == 4 * -(-30 // 4)
    for uid in range(4):
        engine.flush(uid)
    assert engine.kv_stats()["groups"]["kv"]["free"] == 64


def test_the_latent_group_has_two_planes_a_layer(served):
    cfg = served[0]
    engine = _engine(served)
    kv = engine._state.kv_cache
    assert kv.v_pool is None and kv.leaves == 1 and len(kv.fwd) == 1
    pages, bs, width = 64 + 1, 4, cfg.latent_row_width
    assert kv.k_pool.shape == (2 * cfg.num_layers, pages, 1, bs, width)
    want = 2 * cfg.num_layers * pages * bs * width * 4            # float32 pages here
    assert kv.pool_bytes == want == engine.kv_stats()["groups"]["kv"]["bytes"]
    view = engine._state.cache_view()
    assert set(view) == {"kv", "counters"}
    assert view["counters"].shape == (len(COUNTER_FIELDS),) and view["counters"].dtype == jnp.int32
    # both planes of a layer are written: after a chunk, a token's rows differ
    # between the planes and neither is zero
    engine.put([0], [served[4][0][:8]])
    pool = np.asarray(engine._state.kv_cache.k_pool)
    blocks = engine._state.get_sequence(0).kv_blocks[:2]
    rows = pool[:, blocks, 0]                                      # [planes, 2, bs, W]
    assert all(np.abs(rows[p]).max() > 0 for p in range(2 * cfg.num_layers))
    assert np.abs(rows[0] - rows[1]).max() > 1e-3
    # at the published sizes in bfloat16: 1,280 B a token and plane, 8 planes in the cell
    paged, _ = LongcatFlashForCausalLM.cache_groups(LongcatFlashConfig(num_layers=4))
    assert paged.layers * paged.leaves * paged.kv_heads * paged.head_dim * 2 == 8 * 1280


def test_a_preempted_sequence_takes_both_planes_to_the_host_and_back(served):
    ids, want = served[4], served[5]
    engine = _engine(served)
    got = _feed(engine, 0, ids[0], (16, 16, 3))
    before = engine.device_counters()
    engine.preempt(0)
    assert engine.kv_stats()["groups"]["kv"]["free"] == 64
    assert engine.device_counters() == before          # the counters are no sequence's
    assert _worst(_feed(engine, 1, ids[1], (16, 9)), want[1]) < TOLERANCE
    engine.resume(0)
    got.update(_feed(engine, 0, ids[0], (1,) * 10, start=35))
    assert _worst(got, want[0]) < TOLERANCE


def test_what_the_tolerance_refuses(served):
    cfg, model, params, ref_cfg, ids, want = served
    engine = build_engine(model, params, {**ENGINE, "kv_cache": {
        "block_size": 4, "cache_dtype": "bf16"}})
    assert _worst(_feed(engine, 0, ids[0], (16, 16, 8)), want[0]) > 20 * TOLERANCE
    for term in ("zero_experts", "k_pe", "routed_scale", "q_scale", "kv_scale"):
        got = np.asarray(reference.full_logits(ref_cfg, params, jnp.asarray(ids[0]),
                                               leave_out=(term,)))
        assert float(np.max(np.abs(got - want[0]))) > 100 * TOLERANCE, term
    low = np.asarray(reference.full_logits(ref_cfg, params, jnp.asarray(ids[0]), "int8"))
    assert float(np.max(np.abs(low - want[0]))) > 100 * TOLERANCE


# -- the counter group ----------------------------------------------------------

def test_the_device_counters_agree_with_a_numpy_count_of_the_same_routing(served):
    """Every dispatch adds its expert layers' counts on the device; the same
    tokens through the reference's router, counted in NumPy, give the same
    numbers, layer by layer summed."""
    cfg, _, params, ref_cfg, ids, _ = served
    held = dataclasses.replace(cfg, experts_held=(4, 8))
    tree = _share(params, cfg, 4, 8)
    engine = build_engine(LongcatFlashForCausalLM(held), tree, ENGINE)
    assert engine.device_counters() == dict.fromkeys(COUNTER_FIELDS, 0)
    chunks = (16, 16, 5) + (1,) * 3
    _feed(engine, 0, ids[3], chunks)
    got = engine.device_counters()
    n = sum(chunks)
    # the reference's hidden states before each expert layer: its router's picks
    picks = []
    c = reference._c(reference_config(held))
    with jax.default_matmul_precision("highest"):
        x = tree["embed_tokens"].astype(jnp.float32)[ids[3][:n]]
        for l in range(cfg.num_layers):
            p = reference._f32(tree[f"layers_{l}"])
            a0 = x + reference._attention(c, "f32", (), p["self_attn_0"], reference._rms(
                x, p["input_layernorm_0"]["scale"], cfg.rms_norm_eps))
            h0 = reference._rms(a0, p["post_attention_layernorm_0"]["scale"], cfg.rms_norm_eps)
            picks.append(np.asarray(reference.router(c, "f32", (), p, h0)[1]))
            m = p["moe"]
            x, _ = reference._double_layer(
                c, "f32", (), p, lambda j, m=m: (m["w1"][j], m["w3"][j], m["w2"][j]), x)
    picks = np.stack(picks)                                        # [layers, n, k]
    lands = (picks >= 4) & (picks < 12)
    assert got["routed_rows"] == picks.size == n * cfg.moe_topk * cfg.num_layers
    assert got["zero_rows"] == int((picks >= 16).sum()) > 0
    assert got["held_rows"] == int(lands.sum()) > 0
    assert got["dispatches"] == len(chunks)
    # held experts hit: per dispatch and layer, the distinct held experts its rows landed on
    hit, pos = 0, 0
    for q in chunks:
        for l in range(cfg.num_layers):
            part, ok = picks[l, pos:pos + q], lands[l, pos:pos + q]
            hit += len(set(part[ok].tolist()))
        pos += q
    assert got["experts_hit"] == hit
    # what the host's report says of the same dispatches: the rows routed, no more
    adds, rides = resolve_report_fn(LongcatFlashForCausalLM(held))(held, n, 16)
    assert adds == {"expert_rows": got["routed_rows"], "expert_rows_padded": 0,
                    "latent_up_tokens": 0, "latent_absorbed_tokens": n}
    assert rides == {"experts_held": 8, "experts_routed_over": 24, "zero_experts": 8,
                     "kv_planes": 4}


def test_a_round_fetches_no_counter_and_a_family_without_the_group_gets_no_leaf(served):
    from deepspeed_tpu.models.kanana2 import Kanana2Config, Kanana2ForCausalLM
    engine = _engine(served)
    engine.put([0], [served[4][0][:6]])
    assert engine.host_sync_count == 1               # the round's one fetch, as any family's
    counts = engine.device_counters()
    assert engine.host_sync_count == 2 and counts["dispatches"] == 1   # on demand, counted
    assert counts["routed_rows"] == 6 * 4 * 2
    model = Kanana2ForCausalLM(Kanana2Config.tiny())
    tree = model.init_params(jax.random.PRNGKey(1))
    other = build_engine(model, tree, ENGINE)
    syncs = other.host_sync_count
    assert other.device_counters() == {} and set(other._state.cache_view()) == {"kv"}
    assert other.host_sync_count == syncs            # nothing to fetch, nothing counted
    assert other._state.counter_group is None
    with pytest.raises(ValueError, match="one counter group"):
        InferenceEngineV2(model, tree, ENGINE, cache_groups=(
            PagedGroup("kv", 3, 1, 256, leaves=1, value_dim=128),
            CounterGroup("a", ("x",)), CounterGroup("b", ("y",))))
    with pytest.raises(ValueError, match="names its fields"):
        CounterGroup("c", ("x", "x"))


# -- the expert layer with experts that compute nothing ----------------------------

def _layer_case(E=16, Z=8, k=4, D=128, F=128, T=24, seed=1):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]), jnp.float32)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.01, 0.01, E + Z), jnp.float32)
    return dict(x=x, wg=n(D, E + Z), w1=n(E, D, F), w2=n(E, F, D), w3=n(E, D, F), bias=bias,
                k=k, E=E, Z=Z)


def _moe(case, held=None, valid=None, zero=True, **kw):
    cut = (lambda w: w) if held is None else (lambda w: w[held[0]:held[0] + held[1]])
    wg = case["wg"] if zero else case["wg"][:, :case["E"]]
    return moe_layer.moe_ffn(
        case["x"], wg, cut(case["w1"]), cut(case["w2"]), cut(case["w3"]),
        k=case["k"], dtype=jnp.float32, valid=valid, scoring="softmax_bias",
        score_bias=case["bias"][:wg.shape[1]], routed_scale=6.0, experts_held=held,
        zero_experts=case["Z"] if zero else 0, **kw)


def _reference_layer(case, leave_out=()):
    c = {"moe_topk": case["k"], "routed_scaling_factor": 6.0, "held": (0, case["E"]),
         "real_experts": case["E"]}
    p = {"moe": {"router": {"kernel": case["wg"], "bias": case["bias"]}}}
    with jax.default_matmul_precision("highest"):
        return reference._moe(c, "f32", leave_out, p,
                              lambda j: (case["w1"][j], case["w3"][j], case["w2"][j]),
                              case["x"])


@pytest.mark.parametrize("backend", ["einsum", "gmm"])
def test_the_shares_add_up_to_the_uncut_layer(monkeypatch, backend):
    """Over all ``n_routed_experts / count`` shares, the real experts' parts
    summed and the zero experts' part counted ONCE equal the uncut reference's
    whole expert layer; in the einsum oracle and in the grouped GEMM."""
    if backend == "gmm":
        monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    einsum = backend == "einsum"
    case = _layer_case()
    whole = np.asarray(_moe(case, force_einsum=einsum))
    ref, counts = _reference_layer(case)
    np.testing.assert_allclose(whole, np.asarray(ref), atol=2e-5)
    shares = [np.asarray(_moe(case, held=(first, 4), force_einsum=einsum))
              for first in range(0, 16, 4)]
    zero_part = np.asarray(ref) - np.asarray(_reference_layer(case, ("zero_experts",))[0])
    assert np.abs(zero_part).max() > 0.05
    # every share carries the zero experts' term: 4 shares, 3 copies too many
    np.testing.assert_allclose(sum(shares) - 3 * zero_part, whole, atol=2e-5)
    # the real experts' parts alone, summed, beside the term counted once
    real_parts = [s - zero_part for s in shares]
    np.testing.assert_allclose(sum(real_parts) + zero_part, np.asarray(ref), atol=2e-5)
    # and the counts of the whole layer are the reference's
    _, mine = _moe(case, force_einsum=einsum, counts=True)
    near, routed, zero, held = (int(v) for v in counts)
    assert [int(v) for v in mine[:3]] == [routed, zero, held] and routed == 24 * 4
    assert zero + held == routed and 0 < zero < routed


def test_a_zero_row_a_padded_slot_and_a_row_of_another_share_take_no_gemm_rows(monkeypatch):
    """What the grouped GEMM is handed: group sizes that count the rows of
    valid tokens whose REAL expert is held, and nothing else; the einsum
    oracle agrees with it."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    case = _layer_case()
    valid = jnp.arange(24) < 17                       # 7 padded slots
    held = (6, 4)
    _, idx = moe_layer.softmax_bias_router(case["x"], case["wg"], case["bias"], case["k"], 6.0)
    idx = np.asarray(idx)
    lands = (idx >= 6) & (idx < 10) & np.asarray(valid)[:, None]
    seen = []
    real = gg._gmm

    def spy(lhs, rhs, group_sizes, tiling, interpret):
        seen.append(np.asarray(group_sizes))
        return real(lhs, rhs, group_sizes, tiling, interpret)

    monkeypatch.setattr(gg, "_gmm", spy)
    with jax.disable_jit():
        out, counts = _moe(case, held=held, valid=valid, counts=True)
    out = np.asarray(out)
    assert len(seen) == 3 and all(g.shape == (4,) for g in seen)
    want = np.bincount(idx[lands] - 6, minlength=4)
    assert all((g == want).all() for g in seen) and want.sum() == lands.sum() < 17 * 4
    zero_rows = int(((idx >= 16) & np.asarray(valid)[:, None]).sum())
    assert [int(v) for v in counts] == [17 * 4, zero_rows, int(lands.sum()),
                                        int((want > 0).sum())]
    assert zero_rows > 0 and not out[17:].any() and np.isfinite(out).all()
    oracle = np.asarray(_moe(case, held=held, valid=valid, force_einsum=True))
    np.testing.assert_allclose(out, oracle, atol=2e-5)


def test_a_token_with_every_pick_a_zero_expert_returns_its_weights_times_x():
    """A bias that puts the zero columns first: every token's 4 picks are
    zero experts, no GEMM row exists, and the layer returns ``6 sum(p) x``; a
    padded slot still returns 0."""
    case = _layer_case()
    bias = jnp.where(jnp.arange(24) >= 16, 1.0, 0.0)
    valid = jnp.arange(24) < 20
    out, counts = moe_layer.moe_ffn(
        case["x"], case["wg"], case["w1"], case["w2"], case["w3"], k=4, dtype=jnp.float32,
        valid=valid, force_einsum=True, scoring="softmax_bias", score_bias=bias,
        routed_scale=6.0, zero_experts=8, counts=True)
    probs = np.asarray(jax.nn.softmax(case["x"] @ case["wg"], -1))
    top = np.sort(probs[:, 16:], -1)[:, -4:].sum(-1)              # the 4 largest zero columns
    want = 6.0 * top[:, None] * np.asarray(case["x"])
    np.testing.assert_allclose(np.asarray(out)[:20], want[:20], rtol=2e-5, atol=1e-6)
    assert not np.asarray(out)[20:].any()
    assert [int(v) for v in counts] == [80, 80, 0, 0]
    # the bias selects and never weighs: the weights are 6 p of the chosen, not renormalised
    w, idx = moe_layer.softmax_bias_router(case["x"], case["wg"], bias, 4, 6.0)
    np.testing.assert_allclose(np.asarray(w), 6 * np.take_along_axis(probs, np.asarray(idx), -1),
                               rtol=1e-5)
    assert (np.asarray(idx) >= 16).all()


# -- what the other families trace is what they traced ------------------------------

def _signature(closed):
    """A hash of every equation's primitive, name stack (the device scopes the
    metrics read) and output types, through every inner jaxpr."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            out.append((eqn.primitive.name, str(eqn.source_info.name_stack),
                        tuple(str(v.aval) for v in eqn.outvars)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(closed.jaxpr)
    return hashlib.sha1(repr(out).encode()).hexdigest()[:16], len(out)


def _dispatch_signature(model, toks):
    params = model.init_params(jax.random.PRNGKey(0))
    engine = build_engine(model, params, ENGINE)
    program, got = engine_v2.packed_forward, []

    class _Captured(Exception):
        pass

    def spy(*args):
        got.extend(args)
        raise _Captured

    engine_v2.packed_forward = spy
    try:
        with pytest.raises(_Captured):
            engine.put(list(range(len(toks))), toks)
    finally:
        engine_v2.packed_forward = program
    forward, cfg, layout, p, cache, packed, kept, vk = got
    return _signature(jax.make_jaxpr(
        lambda p, c, pk, k: program(forward, cfg, layout, p, c, pk, k, vk))(
            p, cache, packed, kept))


def _moe_case(seed=0):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    return n(24, 128), n(128, 16), n(16, 128, 128), n(16, 128, 128), n(16, 128, 128), n


#: taken at the parent commit (a7dc6ca, jax 0.9.0) by the same functions: a
#: dispatch's whole program of the two families that share ``latent_mla`` and
#: ``moe_ffn``, and ``moe_ffn`` alone with the new arguments absent. The three
#: whole programs were taken again at PR 53, which changed the one thing of
#: them every family shares, the cache write (``paged_layer._write`` under the
#: scope ``paged_write``: a1565ca2c07c750a / 826, fa7b7d2973cc4098 / 840 and
#: 50e9f48bf200d09d / 2394 before it); the three of ``moe_ffn`` are a7dc6ca's
PARENT = {"kanana2_chunk": ("4956e3d714e82ca1", 832),
          "kanana2_held_decode": ("ffefcb20a8f34553", 849),
          "mellum2_chunk": ("823e6bcb59b7c837", 2394),
          "moe_softmax": ("504b164bf310cd8e", 66),
          "moe_sigmoid_held_shared": ("ddc4a2af4b31a839", 98),
          "moe_gmm": ("ade9818f1c68fcc4", 1097)}


@pytest.mark.parametrize("what", sorted(PARENT))
def test_the_other_families_trace_what_they_traced_before(what, monkeypatch):
    from deepspeed_tpu.models.kanana2 import Kanana2Config, Kanana2ForCausalLM
    from deepspeed_tpu.models.mellum2 import Mellum2Config, Mellum2ForCausalLM
    if what == "kanana2_chunk":
        got = _dispatch_signature(Kanana2ForCausalLM(Kanana2Config.tiny()),
                                  [np.zeros(9, np.int32)])
    elif what == "kanana2_held_decode":
        got = _dispatch_signature(Kanana2ForCausalLM(Kanana2Config.tiny(experts_held=(4, 8))),
                                  [np.zeros(1, np.int32)] * 3)
    elif what == "mellum2_chunk":
        got = _dispatch_signature(Mellum2ForCausalLM(Mellum2Config.tiny()),
                                  [np.zeros(9, np.int32)])
    else:
        x, wg, w1, w2, w3, n = _moe_case()
        if what == "moe_sigmoid_held_shared":
            shared = (n(128, 256), n(256, 128), n(128, 256))
            fn = lambda x: moe_layer.moe_ffn(
                x, wg, w1[4:12], w2[4:12], w3[4:12], k=3, dtype=jnp.float32,
                valid=jnp.arange(24) < 20, force_einsum=True, scoring="sigmoid", score_bias=jnp.zeros(16),
                routed_scale=2.5, shared=shared, experts_held=(4, 8))
        else:
            if what == "moe_gmm":
                monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
            fn = lambda x: moe_layer.moe_ffn(x, wg, w1, w2, w3, k=2, dtype=jnp.float32,
                                             valid=jnp.arange(24) < 20,
                                             force_einsum=what == "moe_softmax")
        got = _signature(jax.make_jaxpr(fn)(x))
    assert got == PARENT[what]


def test_the_layer_is_traced_once_for_all_layers_under_its_scopes(served, monkeypatch):
    """ONE ``_layer`` body for every layer (a traced plane), and the device
    scopes the cell's metrics read."""
    from deepspeed_tpu.inference.v2.model_implementations import longcat_flash as impl
    cfg, model, params = served[:3]
    deeper = dataclasses.replace(cfg, num_layers=3, max_position_embeddings=514)
    model = LongcatFlashForCausalLM(deeper)
    engine = build_engine(model, model.init_params(jax.random.PRNGKey(2)), ENGINE)
    bodies, real = [], moe_layer.moe_ffn
    monkeypatch.setattr(impl.moe_layer, "moe_ffn",
                        lambda *a, **kw: bodies.append(1) or real(*a, **kw))
    engine.put([0], [served[4][0][:9]])
    assert len(bodies) == 1                      # three layers, one traced body
    text = impl.ragged_forward.lower(
        deeper, engine._params, engine._state.cache_view(),
        jnp.zeros((1, 16), jnp.int32), jnp.full((1,), 9, jnp.int32), jnp.zeros((1,), jnp.int32),
        {"kv": jnp.zeros((1, 32), jnp.int32)}).as_text(debug_info=True)
    for scope in ("scmoe_layer/mla_attn_0/mla_q", "scmoe_layer/mla_attn_1/mla_read",
                  "scmoe_layer/mla_attn_0/mla_latent_write", "scmoe_layer/mla_attn_1/mla_out",
                  "scmoe_layer/dense_ffn_0", "scmoe_layer/dense_ffn_1",
                  "scmoe_layer/moe_ffn/moe_router", "scmoe_layer/moe_ffn/moe_zero",
                  "scmoe_layer/moe_ffn/moe_counts"):
        assert scope in text, scope
