"""The language model of Keye-VL-2.0 on the normal serving path at a tiny
size: ``InferenceEngineV2`` built by ``engine_factory.build_engine`` over the
one ``DSStateManager`` with ONE paged group whose page keeps an indexer's key
beside K and V, learned sparse attention in every layer (a query reads the
``index_topk`` cached tokens its indexer picks) and softmax-routed experts,
against the plain reference's full forward
(``benchmark/references/keye_vl2.py``) in LOGITS, on seeded weights.

Float32 throughout (``KeyeVL2Config.tiny``): hidden 128, 4 heads over 2 KV
heads of 128, an indexer of 4 heads of 16 and one key head, ``index_topk`` 24
(so that every row past 24 tokens is sparse), 8 experts of width 128, 2 a
token, 2 layers; block 4.
"""

import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import keye_vl2 as reference
from deepspeed_tpu.inference.v2 import engine_v2
from deepspeed_tpu.inference.v2.engine_factory import (
    build_engine, resolve_cache_groups, resolve_forward_fn, resolve_verify_fn)
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.ragged.ragged_manager import selected_tokens
from deepspeed_tpu.inference.v2.model_implementations import moe_layer
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
from deepspeed_tpu.models import keye_vl2 as model_file
from deepspeed_tpu.models.keye_vl2 import KeyeVL2Config, KeyeVL2ForCausalLM, mrope_tables
from deepspeed_tpu.models.llama import rope_frequencies, rotary_apply, rotary_tables

#: |logit - reference logit|. Both sides are float32 and differ in the order
#: of sums only (pages, chunks, a threshold against ``top_k``'s set, the
#: dispatch-combine einsum against a plain sum over experts): the program
#: reads ~2e-6 at logits of ~1. The selection left out moves the reference
#: itself by 0.3, a window in its place by 0.3, int8 matmuls by 0.2, all of
#: which this limit has to refuse.
TOLERANCE = 3e-5

ENGINE = {"state_manager": {"max_ragged_sequence_count": 4, "max_ragged_batch_size": 16,
                            "max_context": 128, "num_kv_blocks": 64},
          "kv_cache": {"block_size": 4, "cache_dtype": "fp32"}}


def reference_config(cfg):
    ref = {k: getattr(cfg, k) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_experts_per_tok",
        "moe_intermediate_size", "rms_norm_eps", "rope_theta")}
    ref["num_experts"] = cfg.experts_in_tree
    ref["num_experts_published"] = cfg.num_experts
    ref["rope_scaling"] = {"mrope_section": list(cfg.mrope_section)}
    ref["sa_config"] = {"indexer_num_heads": cfg.indexer_num_heads,
                        "indexer_head_dim": cfg.indexer_head_dim,
                        "topk": cfg.index_topk}
    if cfg.experts_held:
        ref["experts_held"] = dict(zip(("first", "count"), cfg.experts_held))
    return ref


def _share(params, cfg, first, count):
    """The tree of the share ``[first, first + count)`` of a whole tree."""
    out = dict(params)
    for l in range(cfg.num_hidden_layers):
        layer = dict(params[f"layers_{l}"])
        layer["moe"] = {**layer["moe"], **{n: layer["moe"][n][first:first + count]
                                           for n in ("w1", "w2", "w3")}}
        out[f"layers_{l}"] = layer
    return out


@pytest.fixture(scope="module")
def served():
    cfg = KeyeVL2Config.tiny()
    model = KeyeVL2ForCausalLM(cfg)
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
    assert resolve_forward_fn(model).__module__.endswith("model_implementations.keye_vl2")
    assert resolve_verify_fn(model) is None
    (group,) = resolve_cache_groups(model)
    assert (group.name, group.layers, group.kv_heads, group.head_dim, group.leaves,
            group.index_dim, group.window) == ("kv", 2, 2, 128, 2, 128, None)
    assert not group.kv_pair
    engine = _engine(served)
    assert isinstance(engine, InferenceEngineV2) and not engine.verify_supported
    assert not engine._state.has_further_groups and engine._state.indexed
    assert not engine._state.one_leaf
    # the published sizes are the defaults: K and V of 4 heads of 128 and an
    # index key of 64 values in 128 columns, 2,304 B a token and layer
    full = KeyeVL2Config()
    (group,) = KeyeVL2ForCausalLM.cache_groups(full)
    assert (group.layers, group.kv_heads, group.head_dim, group.index_dim) == (48, 4, 128, 128)
    assert 2 * (2 * group.kv_heads * group.head_dim + group.index_dim) == 2304
    assert full.index_topk == 2048 and full.mrope_section == (16, 24, 24)
    assert full.index_weight_scale == pytest.approx(16 ** -0.5 * 64 ** -0.5)


def test_from_hf_reads_the_published_keys_and_refuses_what_is_not_served():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs", "keye-vl2-l6-ep8.json")) as f:
        hf = json.load(f)
    share = hf["experts_held"]
    cfg = KeyeVL2Config.from_hf(hf, num_experts=hf["num_experts_published"],
                                experts_held=(share["first"], share["count"]))
    assert (cfg.num_hidden_layers, cfg.num_experts, cfg.experts_in_tree, cfg.hidden_size,
            cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.indexer_num_heads, cfg.indexer_head_dim, cfg.index_topk,
            cfg.moe_intermediate_size, cfg.num_experts_per_tok, cfg.vocab_size,
            cfg.rope_theta, cfg.mrope_section) \
        == (6, 128, 16, 2048, 32, 4, 128, 16, 64, 2048, 768, 8, 151936, 1e7, (16, 24, 24))
    with pytest.raises(ValueError, match="decoder_sparse_step"):
        KeyeVL2Config.from_hf({**hf, "decoder_sparse_step": 2})
    with pytest.raises(ValueError, match="sliding_window"):
        KeyeVL2Config.from_hf({**hf, "sliding_window": 4096})
    with pytest.raises(ValueError, match="indexer's key"):
        KeyeVL2Config.from_hf({**hf, "sa_config": {**hf["sa_config"],
                                                   "indexer_num_kv_heads": 2}})
    with pytest.raises(ValueError, match="experts_held"):
        KeyeVL2Config.tiny(experts_held=(6, 4))
    with pytest.raises(ValueError, match="mrope_section"):
        KeyeVL2Config.tiny(mrope_section=(16, 24, 8))


def test_the_reference_lists_the_tree_the_program_holds(served):
    cfg, _, params, ref_cfg = served[:4]
    for c, r in ((cfg, ref_cfg), (dataclasses.replace(cfg, experts_held=(2, 4)), None)):
        r = r or reference_config(c)
        ours = [(p, s, f, jnp.dtype(d).name, st)
                for p, s, f, d, st in model_file.param_spec(c, jnp.bfloat16)]
        theirs = [(p, s, f, jnp.dtype(d).name, st) for p, s, f, d, st in reference.param_spec(r)]
        assert ours == theirs
    flat = {"/".join(k.key for k in path) for path, _ in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    assert flat == {"/".join(p) for p, *_ in model_file.param_spec(cfg)}


@pytest.mark.parametrize("chunks", [
    (16,),                              # a prompt in one chunk: nothing sparse yet
    (16, 16, 9),                        # in several: past 24 tokens a query selects
    (16, 16, 5) + (1,) * 20,            # then decode through the pages, sparsely
    (3, 1, 7, 2, 16, 1, 1, 8, 1),       # ragged lengths
])
def test_chunked_prefill_then_decode_agrees_with_the_full_forward(served, chunks):
    ids, want = served[4], served[5]
    engine = _engine(served)
    assert _worst(_feed(engine, 0, ids[0], chunks), want[0]) < TOLERANCE


def test_chunks_and_decode_rows_through_the_pallas_kernels_agree_too(served, monkeypatch):
    """A block the kernels tile (8 tokens) and interpret mode on: a chunk's
    rows and decode rows are scored by ``paged_index_scores``, thresholded by
    ``topk_threshold`` and read by the masked walk itself, and agree with the
    reference as they do through the dense twins."""
    from deepspeed_tpu import telemetry
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("DS_TPU_DISABLE_PALLAS", raising=False)
    cfg, _, params, _, ids, want = served
    telemetry.reset()
    telemetry.configure(enabled=True)
    try:
        # another config, so that the layers are traced anew with the kernels on
        other = dataclasses.replace(cfg, max_position_embeddings=513)
        engine = build_engine(KeyeVL2ForCausalLM(other), params, {
            **ENGINE, "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}})
        assert _worst(_feed(engine, 0, ids[0], (16, 16, 8) + (1,) * 6), want[0]) < TOLERANCE
        taken = {k[:2] for k in telemetry.get_telemetry().dispatch_stats}
    finally:
        telemetry.configure(enabled=False)
        telemetry.reset()
    assert ("paged_mha", "tuning") in taken, taken
    for kernel in ("paged_mha", "paged_index_scores", "topk_threshold"):
        assert (kernel, "fallback") in taken      # "no_mesh": the kernel itself ran
    assert not any(k[2] in ("no_tpu", "unsupported_shape")
                   for k in telemetry.get_telemetry().dispatch_stats)


def test_rows_of_unequal_length_in_one_dispatch_agree_and_free_their_pages(served):
    ids, want = served[4], served[5]
    engine = _engine(served)
    for uid in range(4):                           # contexts 8, 16, 24, 32
        assert _worst(_feed(engine, uid, ids[uid], (8,) * (uid + 1)), want[uid]) < TOLERANCE
    at = {u: 8 * (u + 1) for u in range(4)}
    for step in range(10):                         # a [4, 1] dispatch a step:
        rows = engine.put(list(range(4)),          # short rows beside sparse ones
                          [ids[u][at[u] + step:at[u] + step + 1] for u in range(4)])
        for u in range(4):
            assert float(np.max(np.abs(rows[u] - want[u][at[u] + step]))) < TOLERANCE
    groups = engine.kv_stats()["groups"]
    assert set(groups) == {"kv"} and groups["kv"]["leaves"] == 3
    assert groups["kv"]["total"] - groups["kv"]["free"] == sum(
        -(-(at[u] + 10) // 4) for u in range(4))
    for uid in range(4):
        engine.flush(uid)
    assert engine.kv_stats()["groups"]["kv"]["free"] == 64


def test_what_the_tolerance_refuses(served):
    """The selection left out, a window in its place, int8 matmuls, bfloat16
    pages: each moves the logits by far more than ``TOLERANCE``."""
    cfg, model, params, ref_cfg, ids, want = served
    engine = build_engine(model, params, {**ENGINE, "kv_cache": {
        "block_size": 4, "cache_dtype": "bf16"}})
    assert _worst(_feed(engine, 0, ids[0], (16, 16, 8)), want[0]) > 20 * TOLERANCE
    for term in reference.TERMS:
        got = np.asarray(reference.full_logits(ref_cfg, params, jnp.asarray(ids[0]),
                                               leave_out=(term,)))
        # the first 24 tokens read all they see either way
        assert float(np.max(np.abs(got[:24] - want[0][:24]))) < TOLERANCE, term
        assert float(np.max(np.abs(got - want[0]))) > 100 * TOLERANCE, term
    low = np.asarray(reference.full_logits(ref_cfg, params, jnp.asarray(ids[0]), "int8"))
    assert float(np.max(np.abs(low - want[0]))) > 100 * TOLERANCE


def test_mrope_by_sections_against_the_reference_and_plain_rope():
    """At UNEQUAL position rows the sectioned tables rotate as the reference
    does; at equal rows they are ``rotary_tables``'s, bit for bit."""
    rng = np.random.default_rng(3)
    T, H, Dh, theta, sections = 12, 3, 128, 1e7, (16, 24, 24)
    x = jnp.asarray(rng.normal(size=(1, T, H, Dh)), jnp.float32)
    pos3 = jnp.asarray(rng.integers(0, 5000, (3, 1, T)), jnp.int32)
    got = rotary_apply(x, *mrope_tables(pos3, Dh, theta, sections))[0]
    want = reference._rotate(x[0], reference.mrope_angles(pos3[:, 0], Dh, theta, sections))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # pair 0 turns by the temporal row, pair 16 by the height row, 40 by width
    for pair, row in ((0, 0), (15, 0), (16, 1), (39, 1), (40, 2), (63, 2)):
        moved = pos3.at[row].add(7)
        other = rotary_apply(x, *mrope_tables(moved, Dh, theta, sections))[0]
        changed = np.abs(np.asarray(other - got)).max(axis=(0, 1)).reshape(Dh // 2, 2).max(1)
        assert changed[pair] > 0
    same = jnp.broadcast_to(pos3[0], (3, 1, T))
    plain = rotary_tables(same[0], *rope_frequencies(Dh, theta))
    for a, b in zip(mrope_tables(same, Dh, theta, sections), plain):
        assert (np.asarray(a) == np.asarray(b)).all()
    # and the reference's forward takes unequal rows too
    cfg = KeyeVL2Config.tiny()
    tree = KeyeVL2ForCausalLM(cfg).init_params(jax.random.PRNGKey(2))
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, T))
    equal = reference.full_logits(reference_config(cfg), tree, ids)
    moved = reference.full_logits(reference_config(cfg), tree, ids,
                                  positions=jnp.arange(T)[None] * jnp.asarray([[1], [2], [3]]))
    assert float(jnp.max(jnp.abs(equal - moved))) > 1e-3


# -- the experts ----------------------------------------------------------------

def _layer_case(E=16, k=3, D=128, F=128, T=24, seed=1):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]), jnp.float32)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    return dict(x=x, wg=n(D, E), w1=n(E, D, F), w2=n(E, F, D), w3=n(E, D, F), k=k)


def _moe(case, held=None, **kw):
    cut = (lambda w: w) if held is None else (lambda w: w[held[0]:held[0] + held[1]])
    return moe_layer.moe_ffn(
        case["x"], case["wg"], cut(case["w1"]), cut(case["w2"]), cut(case["w3"]),
        k=case["k"], dtype=jnp.float32, experts_held=held, **kw)


@pytest.mark.parametrize("backend", ["einsum", "gmm"])
def test_the_eight_shares_add_up_to_the_uncut_layer(monkeypatch, backend):
    """The expert parts of all eight shares of a softmax-routed layer sum to
    the whole layer's, which is the plain reference's uncut layer; in the
    einsum oracle and in the grouped GEMM (interpret mode)."""
    if backend == "gmm":
        monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    case = _layer_case()
    force = backend == "einsum"
    whole = np.asarray(_moe(case, force_einsum=force))
    parts = sum(np.asarray(_moe(case, held=(first, 2), force_einsum=force))
                for first in range(0, 16, 2))
    np.testing.assert_allclose(parts, whole, atol=2e-5)
    c = {"num_experts_per_tok": case["k"], "rms_norm_eps": 0.0, "held": (0, 16)}
    p = {"post_attention_layernorm": {"scale": 1.0},
         "moe": {"router": {"kernel": case["wg"]}}}
    x = case["x"] / jnp.sqrt(jnp.mean(case["x"] ** 2, -1, keepdims=True))
    with jax.default_matmul_precision("highest"):
        ref, _ = reference._moe(c, "f32", p,
                                lambda j: (case["w1"][j], case["w3"][j], case["w2"][j]), x)
    mine = np.asarray(_moe(dict(case, x=x), force_einsum=force))
    np.testing.assert_allclose(mine, np.asarray(ref - x), atol=2e-5)


def test_a_share_of_the_model_agrees_with_the_reference_given_the_same_share(served):
    cfg, _, params, _, ids, whole = served
    held = dataclasses.replace(cfg, experts_held=(3, 4))
    tree = _share(params, cfg, 3, 4)
    want = np.asarray(reference.full_logits(reference_config(held), tree, jnp.asarray(ids[0])))
    assert float(np.max(np.abs(want - whole[0]))) > 1e-2          # a share is not the whole
    engine = build_engine(KeyeVL2ForCausalLM(held), tree, ENGINE)
    got = _feed(engine, 0, ids[0], (16, 16, 5) + (1,) * 8)
    assert _worst(got, want) < TOLERANCE


# -- the pages ------------------------------------------------------------------

def test_the_index_keys_are_a_third_pool_under_the_same_pages(served):
    cfg = served[0]
    engine = _engine(served)
    kv = engine._state.kv_cache
    assert kv.leaves == 2 and not kv.kv_pair and len(kv.fwd) == 3
    pages, bs = 64 + 1, 4                                         # a trash page
    assert kv.k_pool.shape == kv.v_pool.shape == (cfg.num_hidden_layers, pages, 2, bs, 128)
    assert kv.i_pool.shape == (cfg.num_hidden_layers, pages, 1, bs, cfg.index_row_width)
    want = cfg.num_hidden_layers * pages * bs * (2 * 2 * 128 + 128) * 4   # float32 here
    assert kv.pool_bytes == want == engine.kv_stats()["groups"]["kv"]["bytes"]
    assert sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(engine._state.cache_view())) == want
    # an index key is written where its token's K and V are: the token's page
    engine.put([7], [served[4][0][:6]])
    blocks = engine._state.get_sequence(7).kv_blocks
    held = np.asarray(engine._state.kv_cache.i_pool)[0, blocks[0], 0]
    assert np.abs(held[:, :cfg.indexer_head_dim]).min() > 0
    assert not held[:, cfg.indexer_head_dim:].any()
    assert not np.asarray(engine._state.kv_cache.i_pool)[0, blocks[1], 0, 2:].any()


def test_what_an_index_leaf_cannot_do_yet_is_refused_by_its_declaration(served):
    """The refusals follow from the group the model declares (``index_dim``),
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
    with pytest.raises(ValueError, match="index leaf"):
        engine._state.kv_cache.export_blocks([0])
    from deepspeed_tpu.inference.v2.ragged.cache_groups import PagedGroup
    from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
    with pytest.raises(ValueError, match="kv_dtype int8.*index leaf"):
        BlockedKVCache(1, 4, 4, 1, 128, kv_dtype="int8", index_dim=128)
    with pytest.raises(ValueError, match="index leaf"):
        PagedGroup("kv", 1, 1, 128, leaves=1, value_dim=128, index_dim=128)
    with pytest.raises(ValueError, match="index leaf"):
        PagedGroup("w", 1, 1, 128, window=64, index_dim=128)


def test_a_preempted_sequence_takes_its_index_keys_to_the_host_and_back(served):
    """Index pages are allocated, freed, preempted and resumed with their K
    and V pages: a sequence swapped out and back in (into OTHER pages, after
    another sequence used the pool) decodes as if nothing had happened."""
    ids, want = served[4], served[5]
    engine = _engine(served)
    got = _feed(engine, 0, ids[0], (16, 16, 3))
    before = list(engine._state.get_sequence(0).kv_blocks)
    engine.preempt(0)
    assert engine.kv_stats()["groups"]["kv"]["free"] == 64
    assert _worst(_feed(engine, 1, ids[1], (16, 16, 9)), want[1]) < TOLERANCE
    engine.resume(0)
    assert list(engine._state.get_sequence(0).kv_blocks) != before
    got.update(_feed(engine, 0, ids[0], (1,) * 10, start=35))
    assert _worst(got, want[0]) < TOLERANCE


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


@pytest.mark.parametrize("seen,new,topk", [(0, 5, 3), (10, 4, 3), (1, 4, 3), (0, 1, 8),
                                           (2, 3, 8), (6, 5, 8), (0, 16, 24), (20, 16, 24)])
def test_selected_tokens_counts_what_a_row_reads_from_its_lengths(seen, new, topk):
    assert selected_tokens(seen, new, topk) == sum(
        min(seen + i + 1, topk) for i in range(new))


def test_scheduler_serves_and_its_counters_equal_the_spans_sums(served, tmp_path):
    """Through ``SplitFuseScheduler`` under a share of the experts: greedy
    streams start at the reference's argmax, and the plain counters equal the
    sums of the ``serving/build`` spans' attributes: ``index_pages``,
    ``sparse_rows`` (rows past ``index_topk`` tokens), ``selected_tokens``
    (what the read touches, from the lengths) and ``expert_rows``."""
    cfg, _, params, _, ids, _ = served
    held = dataclasses.replace(cfg, experts_held=(4, 2))
    tree = _share(params, cfg, 4, 2)
    engine = build_engine(KeyeVL2ForCausalLM(held), tree, ENGINE)
    sched = SplitFuseScheduler(engine)
    prompts = {u: ids[u][:14 + 7 * u] for u in range(4)}       # 14 .. 35 tokens
    lengths = []
    program = engine_v2.packed_forward

    def spy(forward_fn, cfg_, layout, params_, cache, packed, kept, verify_k):
        fields = engine_v2.unpack(layout, jnp.asarray(packed))
        lengths.append((np.asarray(fields["seen"]), np.asarray(fields["q_len"])))
        return program(forward_fn, cfg_, layout, params_, cache, packed, kept, verify_k)

    def run():
        for u, p in prompts.items():
            sched.submit(u, p, max_new_tokens=12)
        engine_v2.packed_forward = spy
        try:
            sched.run_to_completion()
        finally:
            engine_v2.packed_forward = program

    spans = _captured(tmp_path, run)
    builds = [a for name, _, a in spans if name == "serving/build"]
    total = lambda key: sum(int(a[key]) for a in builds)
    assert builds and sched.dispatches == len(builds) == len(lengths)
    assert all(int(a["experts_held"]) == 2 and int(a["experts_routed_over"]) == 8
               and int(a["index_row_bytes"]) == 128 * 4 for a in builds)
    assert sched.real_tokens == total("real_tokens")
    assert sched.expert_rows == total("expert_rows") == sched.real_tokens * 2 * 2
    assert sched.index_pages == total("index_pages") > 0
    assert sched.sparse_rows == total("sparse_rows") > 0
    assert sched.selected_tokens == total("selected_tokens") > 0
    assert all(int(a["index_pages"]) >= int(a["live_pages"]) for a in builds)
    # each dispatch's attributes are the count from the lengths it was given
    for a, (seen, q_len) in zip(builds, lengths):
        real = q_len > 0
        assert int(a["sparse_rows"]) == int(np.sum((seen + q_len > 24) & real))
        assert int(a["selected_tokens"]) == 2 * sum(
            min(int(s) + i + 1, 24) for s, n in zip(seen, q_len) for i in range(int(n)))
    want = {u: np.asarray(reference.full_logits(reference_config(held), tree,
                                                jnp.asarray(p))) for u, p in prompts.items()}
    for u, p in prompts.items():
        if u in sched._requests:
            assert sched._requests[u].generated[0] == int(np.argmax(want[u][len(p) - 1]))


def test_a_program_lowers_one_function_for_its_layers_and_compiles_once_a_shape(
        served, monkeypatch):
    """The layers of a dispatch call ONE lowered function
    (``keye_vl2._layer`` is a jit of its own), not one inlined copy a layer;
    and a run compiles as many programs as it has distinct ``(seq_bucket,
    chunk_bucket)``."""
    import re
    cfg, _, params, _, ids, _ = served
    deep = dataclasses.replace(cfg, num_hidden_layers=4, max_position_embeddings=514)
    engine = build_engine(KeyeVL2ForCausalLM(deep),
                          KeyeVL2ForCausalLM(deep).init_params(jax.random.PRNGKey(1)), ENGINE)
    sched = SplitFuseScheduler(engine)
    before = engine_v2.packed_forward._cache_size()
    for u in range(3):
        sched.submit(u, ids[u][:20 + 9 * u], max_new_tokens=6)
    shapes = set()
    while sched.has_work:
        sched.step()
        shapes.update(engine.last_batch_shapes)
    assert len(shapes) >= 2
    assert engine_v2.packed_forward._cache_size() - before == len(shapes)

    program, got = engine_v2.packed_forward, []

    class Captured(Exception):
        pass

    def spy(*args):
        got.extend(args)
        raise Captured

    monkeypatch.setattr(engine_v2, "packed_forward", spy)
    with pytest.raises(Captured):
        engine.put([8, 9], [np.zeros(1, np.int32)] * 2)
    text = program.lower(*got).as_text()
    assert len(set(re.findall(r"func\.func private @(_layer\w*)", text))) == 1
    assert len(re.findall(r"call @_layer", text)) == 4
