"""Mellum2 on the normal serving path at a tiny size: ``InferenceEngineV2``
built by ``engine_factory.build_engine`` over the one ``DSStateManager`` with
two paged groups and no slot group (the full layers' pages, the sliding
layers' pages that are freed behind the window), a sparse-expert layer in
every layer, against the plain reference's full forward
(``benchmark/references/mellum2.py``) in LOGITS, on seeded weights.

Float32 throughout (``Mellum2Config.tiny``): hidden 64, 4 q / 2 kv heads of
16, 8 experts of width 32, 2 a token, window 8, 8 layers (two periods), YaRN
factor 4 over an original length of 16; block 4. The contexts are 60 tokens:
longer than the window plus a block and than YaRN's original length.
"""

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references import mellum2 as reference
from deepspeed_tpu.inference.v2.engine_factory import (
    build_engine, resolve_cache_groups, resolve_forward_fn, resolve_verify_fn)
from deepspeed_tpu.inference.v2 import engine_v2
from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler
from deepspeed_tpu.models import mellum2 as model_file
from deepspeed_tpu.models.mellum2 import (
    FULL, SLIDING, Mellum2Config, Mellum2ForCausalLM)

#: |logit - reference logit|. Both sides are float32 and differ in the order
#: of sums only (cache, chunks and the dispatch-combine einsum against one
#: full pass with a plain sum over experts): the program reads 7e-7 at logits
#: of ~0.7. A term left out moves the reference itself by 0.15 (the
#: ``attention_factor``), 0.42 (the renormalisation) and 0.45 (the q/k norm),
#: and int8 matmuls by 0.22, all of which this limit has to refuse.
TOLERANCE = 2e-5

ENGINE = {"state_manager": {"max_ragged_sequence_count": 4, "max_ragged_batch_size": 16,
                            "max_context": 128, "num_kv_blocks": 64},
          "kv_cache": {"block_size": 4, "cache_dtype": "fp32"}}


def reference_config(cfg):
    ref = {k: getattr(cfg, k) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_experts", "num_experts_per_tok",
        "moe_intermediate_size", "sliding_window", "rms_norm_eps")}
    ref["layer_types"] = list(cfg.layer_types)
    ref["rope_parameters"] = {SLIDING: dict(cfg.rope_sliding), FULL: dict(cfg.rope_full)}
    return ref


@pytest.fixture(scope="module")
def served():
    cfg = Mellum2Config.tiny()
    model = Mellum2ForCausalLM(cfg)
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
    assert resolve_forward_fn(model).__module__.endswith("model_implementations.mellum2")
    assert resolve_verify_fn(model) is None
    groups = resolve_cache_groups(model)
    assert [(type(g).__name__, g.name, g.layers, g.kv_heads, g.head_dim, g.window)
            for g in groups] == [("PagedGroup", "kv", 2, 2, 16, None),
                                 ("PagedGroup", "window", 6, 2, 16, 8)]
    engine = _engine(served)
    assert isinstance(engine, InferenceEngineV2) and not engine.verify_supported
    assert engine._state.slot_group is None and engine.state_slot(0) is None
    assert set(engine.kv_stats()["groups"]) == {"kv", "window"}
    # the published pattern and sizes are the defaults
    full = Mellum2Config()
    assert full.layer_types[:4] == model_file.PERIOD and len(full.layer_types) == 28
    assert [g.layers for g in Mellum2ForCausalLM.cache_groups(full)] == [7, 21]
    assert Mellum2ForCausalLM.cache_groups(dataclasses.replace(
        full, num_hidden_layers=12, layer_types=None))[1].layers == 9


def test_the_reference_lists_the_tree_the_program_holds(served):
    cfg, _, params, ref_cfg = served[:4]
    ours = [(p, s, f, jnp.dtype(d).name, st)
            for p, s, f, d, st in model_file.param_spec(cfg, jnp.bfloat16)]
    theirs = [(p, s, f, jnp.dtype(d).name, st) for p, s, f, d, st in reference.param_spec(ref_cfg)]
    assert ours == theirs
    flat = {"/".join(k.key for k in path) for path, _ in
            jax.tree_util.tree_flatten_with_path(params)[0]}
    assert flat == {"/".join(p) for p, *_ in ours}


def test_yarn_table_agrees_with_the_reference_and_differs_from_default(served):
    from deepspeed_tpu.models.llama import rope_frequencies
    cfg, ref_cfg = served[0], served[3]
    for kind in (SLIDING, FULL):
        inv, scale = rope_frequencies(cfg.head_dim, *cfg.rope(kind))
        want_inv, want_scale = reference.rope_table(ref_cfg, kind)
        np.testing.assert_allclose(np.asarray(inv), want_inv, rtol=1e-6)
        assert scale == pytest.approx(want_scale)
    plain, _ = rope_frequencies(cfg.head_dim, cfg.rope(FULL)[0])
    yarn, scale = rope_frequencies(cfg.head_dim, *cfg.rope(FULL))
    assert scale == pytest.approx(0.1 * np.log(4) + 1) and not np.allclose(plain, yarn)
    # the published sizes: dimensions below 18 keep theta's frequency, from 35 on a 16th
    inv, scale = rope_frequencies(128, *Mellum2Config().rope(FULL))
    base = 500000.0 ** (-np.arange(64) / 64)
    np.testing.assert_allclose(np.asarray(inv[:19]), base[:19], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inv[35:]), base[35:] / 16, rtol=1e-6)
    assert scale == pytest.approx(1.2772588722239782)


@pytest.mark.parametrize("chunks", [
    (16,),                              # a prompt in one chunk
    (16, 16, 9),                        # in several: pages of both groups carried over
    (16, 16, 5) + (1,) * 20,            # then decode through both groups
    (3, 1, 7, 2, 16, 1, 1, 8, 1),       # ragged lengths
], ids=["one-chunk", "chunks", "chunks-then-decode", "ragged"])
def test_logits_agree_with_the_reference(served, chunks):
    ids, want = served[4][0], served[5][0]
    got = _feed(_engine(served), 0, ids, chunks)
    assert _worst(got, want) < TOLERANCE


@pytest.mark.parametrize("control", ["attention_factor", "renormalise", "qk_norm", "int8"])
def test_a_term_left_out_or_a_lower_precision_fails_the_tolerance(served, control):
    _, _, params, ref_cfg, ids, want = served
    kw = {"precision": "int8"} if control == "int8" else {"leave_out": (control,)}
    low = np.asarray(reference.full_logits(ref_cfg, params, jnp.asarray(ids[0]), **kw))
    assert np.max(np.abs(low - want[0])) > 1000 * TOLERANCE


def test_mixed_rounds_of_decode_rows_beside_a_chunk(served):
    """Rounds as the scheduler composes them: the rows of one token together
    as [4, 1], every other row alone as [1, 16]."""
    ids, want = served[4], served[5]
    engine = _engine(served, state_manager=dict(ENGINE["state_manager"],
                                                max_ragged_batch_size=32))
    pos = {u: 0 for u in range(4)}
    worst = 0.0
    for lengths in [(8, 3, 1, 5), (1, 8, 2, 7), (4, 1, 8, 1), (2, 6, 1, 3), (1, 1, 1, 1),
                    (1, 1, 12, 1), (1, 1, 1, 1)]:
        out = engine.put(list(range(4)), [ids[u][pos[u]:pos[u] + n]
                                          for u, n in enumerate(lengths)])
        ones = lengths.count(1)
        assert engine.last_batch_shapes == [(4, 1)] + [(1, 16)] * (4 - ones)
        for u, n in enumerate(lengths):
            pos[u] += n
            worst = max(worst, float(np.max(np.abs(out[u] - want[u][pos[u] - 1]))))
    assert worst < TOLERANCE


@pytest.mark.parametrize("rows", [4, 3], ids=["full", "one-padded-row"])
def test_a_decode_round_advances_both_groups_and_a_padded_row_changes_nothing(served, rows):
    """``rows`` sequences decode together as [4, 1], 30 rounds: the window
    ring frees pages, the full layers keep every page; with three rows the
    fourth is padding, routed to no expert, written to the trash pages."""
    ids, want = served[4], served[5]
    engine = _engine(served)
    uids = list(range(rows))
    pos = {}
    for u in uids:
        pos[u] = 7 + 3 * u                       # prompts of 7, 10, 13, 16 tokens
        engine.put([u], [ids[u][:pos[u]]])
    freed = engine._state.window_pages_freed
    worst = 0.0
    for _ in range(30):
        out = engine.put(uids, [ids[u][pos[u]:pos[u] + 1] for u in uids])
        assert engine.last_batch_shapes == [(4, 1)]
        assert engine.last_counts["expert_rows"] == rows * 2 * 8
        assert engine.last_counts["expert_rows_padded"] == 0
        for u in uids:
            pos[u] += 1
            worst = max(worst, float(np.max(np.abs(out[u] - want[u][pos[u] - 1]))))
    assert worst < TOLERANCE
    assert engine._state.window_pages_freed - freed >= 6 * rows
    for u in uids:
        seq = engine._state.get_sequence(u)
        assert seq.seen_tokens == pos[u] and seq.slot is None
        assert len(seq.kv_blocks) == -(-pos[u] // 4), "the full layers keep every page"
        assert len(seq.group_blocks["window"]) <= 3, "the ring holds the window's pages"


def test_padded_slots_take_no_expert_rows_and_change_no_real_row(served):
    """The expert layer alone: a batch of 5 real token slots among 16. The
    real rows' outputs are those of the 5 tokens run alone, bit for bit in
    the einsum and within the kernel's rounding in the grouped GEMM, whatever
    the padded slots hold; the padded slots' outputs are zero."""
    from deepspeed_tpu.inference.v2.model_implementations.moe_layer import moe_ffn
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    rng = np.random.default_rng(3)
    T, D, F, E, k = 16, 128, 128, 8, 2
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(D, E)) * 0.3, jnp.float32)
    w1, w3 = (jnp.asarray(rng.normal(size=(E, D, F)) / 8, jnp.float32) for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(E, F, D)) / 8, jnp.float32)
    valid = np.zeros(T, bool)
    valid[[0, 3, 4, 9, 15]] = True
    alone = moe_ffn(x[valid], gate, w1, w2, w3, k=k, dtype=jnp.float32, force_einsum=True)
    junk = jnp.where(valid[:, None], x, 1e4 * x + jnp.nan)   # padded slots hold anything
    for xs in (x, junk):
        got = moe_ffn(xs, gate, w1, w2, w3, k=k, dtype=jnp.float32, valid=jnp.asarray(valid),
                      force_einsum=True)
        np.testing.assert_array_equal(np.asarray(got)[valid], np.asarray(alone))
        assert not np.asarray(got)[~valid].any()
        tv, ti = gg.topk_router(jnp.where(valid[:, None], xs, 0), gate, k)
        tv = jnp.where(valid[:, None], tv, 0.0)
        kernel = gg.moe_ffn_gmm(xs, tv, ti, w1, w2, w3, n_experts=E, dtype=jnp.float32,
                                valid=jnp.asarray(valid), interpret=True)
        np.testing.assert_allclose(np.asarray(kernel)[valid], np.asarray(alone),
                                   atol=2e-3, rtol=2e-3)
        assert not np.asarray(kernel)[~valid].any()


def test_the_grouped_gemm_visits_the_valid_rows_only():
    """What ``expert_rows_padded == 0`` rests on: the group sizes handed to
    the grouped GEMM sum to the valid tokens' rows."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    seen = {}
    import jax.experimental.pallas.ops.tpu.megablox as megablox
    real = megablox.gmm

    def spy(lhs, rhs, group_sizes, **kw):
        seen.setdefault("sizes", []).append(np.asarray(group_sizes))
        return real(lhs, rhs, group_sizes, **kw)

    megablox.gmm = spy
    try:
        rng = np.random.default_rng(0)
        T, D, F, E, k = 24, 128, 128, 4, 2
        x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
        w1, w3 = (jnp.asarray(rng.normal(size=(E, D, F)) / 8, jnp.float32) for _ in range(2))
        w2 = jnp.asarray(rng.normal(size=(E, F, D)) / 8, jnp.float32)
        tv, ti = gg.topk_router(x, jnp.asarray(rng.normal(size=(D, E)), jnp.float32), k)
        valid = jnp.arange(T) < 7
        gg._moe_ffn_gmm_local(x, tv, ti, valid, w1, w2, w3, n_experts=E, dtype=jnp.float32,
                              interpret=True)
    finally:
        megablox.gmm = real
    assert len(seen["sizes"]) == 3 and all(int(s.sum()) == 7 * k for s in seen["sizes"])


def test_the_ring_frees_pages_and_changes_no_logit(served):
    """Contexts of many windows: the window group holds a bounded number of
    pages and gives the logits that an engine keeping every page gives; the
    primary group keeps every page."""
    _, model, params, _, ids, want = served
    chunks = (16, 16, 7) + (1,) * 21
    ring = _engine(served)
    got_ring = _feed(ring, 0, ids[0], chunks)
    groups = resolve_cache_groups(model)
    kept_groups = (groups[0], dataclasses.replace(groups[1], window=None))
    kept = InferenceEngineV2(model, params, ENGINE, cache_groups=kept_groups)
    got_kept = _feed(kept, 0, ids[0], chunks)
    for p in got_ring:
        np.testing.assert_allclose(got_ring[p], got_kept[p], atol=1e-6, rtol=0)
    assert _worst(got_ring, want[0]) < TOLERANCE
    seq_ring, seq_kept = ring._state.get_sequence(0), kept._state.get_sequence(0)
    assert len(seq_kept.group_blocks["window"]) == 15 == len(seq_kept.kv_blocks)
    # window 8 of block 4 at 60 tokens: position 60 sees 53..59, pages 13, 14
    assert len(seq_ring.group_blocks["window"]) == 2 and seq_ring.group_base["window"] == 13
    assert ring._state.window_pages_freed == 13
    assert ring._state.table_width["window"] == 8 // 4 + 16 // 4 + 1
    assert len(seq_ring.kv_blocks) == 15, "the full layers' pages grow with the context"
    ring.flush(0)
    stats = ring.kv_stats()["groups"]
    assert all(g["free"] == g["total"] for g in stats.values())


def test_preempt_then_resume_reproduces_the_uninterrupted_logits(served):
    ids, want = served[4], served[5]
    engine = _engine(served)
    got = _feed(engine, 0, ids[0], (16, 16, 3))
    engine.preempt(0)
    seq = engine._state.get_sequence(0)
    assert seq.is_swapped and not seq.group_blocks["window"]
    assert not engine.can_schedule([0], [1]).success
    assert all(g["free"] == g["total"] for g in engine.kv_stats()["groups"].values())
    assert _worst(_feed(engine, 1, ids[1], (16, 9)), want[1]) < TOLERANCE
    assert engine.further_groups_fit_resume(0)
    engine.resume(0)
    got.update(_feed(engine, 0, ids[0], (1,) * 10, start=35))
    assert _worst(got, want[0]) < TOLERANCE


def test_admission_needs_window_pages_and_no_slot(served):
    engine = _engine(served, state_manager=dict(ENGINE["state_manager"],
                                                max_tracked_sequences=16))
    ids = served[4]
    for uid in range(4):
        engine.put([uid], [ids[uid][:5]])
    assert engine.can_schedule([9], [4]).success, "no slot group: a fifth sequence is admitted"
    window = engine._state.paged_groups["window"][1]
    held = window.reserve(window.free_blocks)
    verdict = engine.can_schedule([9], [4])
    assert not verdict.success and verdict.reason == "not enough window blocks"
    window.free(held)


def test_what_this_model_cannot_do_yet_is_refused_by_its_groups(served):
    """The refusals follow from the groups the model declares (a further
    group beside "kv"), not from a family's name."""
    _, model, params = served[:3]
    with pytest.raises(ValueError, match="prefix_caching is not supported"):
        build_engine(model, params, {**ENGINE, "prefix_caching": True})
    with pytest.raises(ValueError, match="speculative.enabled"):
        build_engine(model, params, {**ENGINE, "speculative": {"enabled": True}})
    with pytest.raises(ValueError, match="kv_dtype int8"):
        build_engine(model, params, {**ENGINE, "state_manager": dict(
            ENGINE["state_manager"], kv_dtype="int8")})
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
    """Through ``SplitFuseScheduler``: greedy streams equal the reference's
    argmax continuation, and the plain counters equal the sums of the
    ``serving/build`` spans' attributes, the expert rows among them."""
    cfg, _, _, _, ids, want = served
    engine = _engine(served)
    sched = SplitFuseScheduler(engine)
    prompts = {u: ids[u][:20 + 5 * u] for u in range(4)}

    def run():
        for u, p in prompts.items():
            sched.submit(u, p, max_new_tokens=12)
        sched.run_to_completion()

    spans = _captured(tmp_path, run)
    builds = [a for name, _, a in spans if name == "serving/build"]
    assert builds and all("expert_rows" in a and "window_pages" in a for a in builds)
    total = lambda key: sum(int(a[key]) for a in builds)
    assert sched.dispatches == len(builds)
    assert sched.real_tokens == total("real_tokens")
    assert sched.expert_rows == total("expert_rows") \
        == sched.real_tokens * cfg.num_experts_per_tok * cfg.num_hidden_layers
    assert sched.expert_rows_padded == total("expert_rows_padded") == 0
    assert sched.window_pages_freed == total("window_pages_freed") > 0
    assert sched.state_slots == total("state_slots") == 0
    assert all(int(a["global_pages"]) >= int(a["window_live_pages"]) > 0 for a in builds[4:])
    # the first generated token is the reference's argmax after the prompt
    for u, p in prompts.items():
        first = sched._requests[u].generated[0] if u in sched._requests else None
        if first is not None:
            assert first == int(np.argmax(want[u][len(p) - 1]))


def test_a_program_lowers_one_function_a_layer_type(served, monkeypatch):
    """The eight layers of a dispatch call TWO lowered functions, one a layer
    type (``mellum2._layer`` is a jit of its own and the layer's place in its
    pool a traced value), not eight inlined copies: what a program costs to
    trace and lower does not grow with the depth. At the benchmark's 12
    layers of 4 kernel calls each, eleven unrolled programs took the cell's
    set-up past the time its run is given."""
    import re
    cfg = served[0]
    engine = _engine(served)
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
    layers = re.findall(r"func\.func private @(_layer\w*)", text)
    assert len(layers) == len(set(cfg.layer_types)) == 2
    assert len(re.findall(r"call @_layer", text)) == cfg.num_hidden_layers == 8


def test_mixtral_reads_a_stated_head_dim():
    """``mixtral.ragged_forward`` takes the head size the config states, as
    ``cache_groups.homogeneous`` does, and ``hidden / heads`` only without."""
    from deepspeed_tpu.inference.v2.model_implementations import mixtral
    from deepspeed_tpu.inference.v2.ragged.cache_groups import homogeneous
    from deepspeed_tpu.models.mixtral import MixtralConfig

    @dataclasses.dataclass(frozen=True)
    class Stated(MixtralConfig):
        head_dim: int = 0

    base = MixtralConfig.tiny(dtype=jnp.float32)
    cfg = Stated(**{**dataclasses.asdict(base), "head_dim": 32})   # hidden / heads is 16
    H, KV, Dh, d = cfg.num_attention_heads, cfg.num_key_value_heads, 32, cfg.hidden_size
    E, F = cfg.num_local_experts, cfg.intermediate_size
    assert homogeneous(cfg)[0].head_dim == Dh
    rng = np.random.default_rng(0)
    w = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2]), jnp.float32)
    layer = lambda: {
        "input_layernorm": {"scale": jnp.ones(d)}, "post_attention_layernorm": {"scale": jnp.ones(d)},
        "self_attn": {"q_proj": {"kernel": w(d, H * Dh)}, "k_proj": {"kernel": w(d, KV * Dh)},
                      "v_proj": {"kernel": w(d, KV * Dh)}, "o_proj": {"kernel": w(H * Dh, d)}},
        "block_sparse_moe": {"gate": {"wg": w(d, E)}, "experts": {"MixtralExpertMLP_0": {
            "w1": {"kernel": w(E, d, F)}, "w2": {"kernel": w(E, F, d)},
            "w3": {"kernel": w(E, d, F)}}}}}
    params = {"embed_tokens": w(cfg.vocab_size, d), "lm_head": w(cfg.vocab_size, d),
              "norm": {"scale": jnp.ones(d)},
              **{f"layers_{i}": layer() for i in range(cfg.num_hidden_layers)}}
    L, nb, bs = cfg.num_hidden_layers, 5, 4
    pool = lambda: jnp.zeros((L, nb, KV, bs, Dh), jnp.float32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 4)), jnp.int32)
    logits, cache = mixtral.ragged_forward(
        cfg, params, {"kv": (pool(), pool())}, tokens, jnp.asarray([4, 3], jnp.int32),
        jnp.zeros(2, jnp.int32), {"kv": jnp.asarray([[0, 1], [2, 3]], jnp.int32)})
    assert logits.shape == (2, cfg.vocab_size) and bool(jnp.all(jnp.isfinite(logits)))
    assert cache["kv"][0].shape == (L, nb, KV, bs, Dh)
    assert float(jnp.abs(cache["kv"][0][:, 0]).max()) > 0
