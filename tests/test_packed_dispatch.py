"""A dispatch's host arrays cross to the device as ONE int32 buffer
(``ragged_wrapper.pack``) and the program of a dispatch
(``engine_v2.packed_forward``) slices them back out by the layout before it
calls the family's forward. Every family goes through it: one paged group
(llama: 5 arrays), a ring and a slot list beside it (phi4flash: 8), two paged
groups (mellum2: 7), one group of one leaf (kanana2: 5), the verify forward.
A row's source is one of the arrays: where it is not -1 the program takes
the row's token from the ids the round before left on the device (a round
dispatched ahead of the fetch, ``SplitFuseScheduler.step``).

What is pinned: pack -> unpack gives every array back exactly; a served run
emits, bit for bit, what it emits when each dispatch hands the family's
forward the separate arrays (the path before the buffer); a run compiles one
program a ``(seq_bucket, chunk_bucket, verify_k)`` and none a row count or a
table fill; a round's dispatches each read a buffer of their own.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import InferenceEngineV2, engine_v2
from deepspeed_tpu.inference.v2.engine_factory import build_engine
from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import pack, unpack
from deepspeed_tpu.inference.v2.scheduler import SplitFuseScheduler

LIMITS = {"state_manager": {"max_ragged_sequence_count": 4, "max_ragged_batch_size": 16,
                            "max_context": 128, "num_kv_blocks": 64},
          "kv_cache": {"block_size": 4, "cache_dtype": "fp32"}}

#: family -> (host arrays a dispatch packs, the tables' names in order)
FAMILIES = {"llama": (5, ["kv"]),
            "phi4flash": (8, ["kv", "window", "window_base", "state"]),
            "mellum2": (7, ["kv", "window", "window_base"]),
            "kanana2": (5, ["kv"]),
            "llama-verify": (5, ["kv"])}


def _build(family):
    """(engine factory, vocabulary) of a tiny model of ``family``."""
    if family.startswith("llama"):
        from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig.tiny(scan_layers=True, remat=False)
        model = LlamaForCausalLM(cfg)
        params = model.init(jax.random.PRNGKey(0),
                            {"input_ids": np.zeros((1, 8), np.int32)})["params"]
        config = dict(LIMITS, **({"speculative": {"enabled": True, "max_draft_tokens": 4}}
                                 if family == "llama-verify" else {}))
        return lambda: InferenceEngineV2(model, params, config=config), cfg.vocab_size
    if family == "phi4flash":
        from deepspeed_tpu.models.phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM
        cfg = Phi4FlashConfig.tiny()
        model = Phi4FlashForCausalLM(cfg)
    elif family == "mellum2":
        from deepspeed_tpu.models.mellum2 import Mellum2Config, Mellum2ForCausalLM
        cfg = Mellum2Config.tiny()
        model = Mellum2ForCausalLM(cfg)
    else:
        from deepspeed_tpu.models.kanana2 import Kanana2Config, Kanana2ForCausalLM
        cfg = Kanana2Config.tiny()
        model = Kanana2ForCausalLM(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return lambda: build_engine(model, params, LIMITS), cfg.vocab_size


def _prompts(family, vocab):
    """Prompts on both sides of the 16-token budget; a verify run's are
    periodic, so that the n-gram drafter has drafts to verify."""
    rng = np.random.default_rng(40)
    if family == "llama-verify":
        return [np.tile(rng.integers(0, vocab, n).astype(np.int32), 8)[:m]
                for n, m in ((3, 30), (2, 9), (4, 21))]
    return [rng.integers(0, vocab, n).astype(np.int32) for n in (5, 21, 9)]


def _serve(engine, prompts, new_tokens=6):
    """Serve ``prompts`` together, the second and third arriving while the
    first decodes. Returns ({uid: ids}, the dispatched (seq_bucket,
    chunk_bucket) shapes)."""
    sched = SplitFuseScheduler(engine)
    shapes, rnd = set(), 0
    while rnd == 0 or sched.has_work:
        for uid, p in enumerate(prompts):
            if uid == min(rnd, 2) and uid not in sched._requests:
                sched.submit(uid, p, max_new_tokens=new_tokens)
        sched.step()
        shapes.update(engine.last_batch_shapes)
        rnd += 1
    return {u: np.asarray(ids).tolist() for u, ids in sched.results().items()}, shapes


def _separate_arrays(forward_fn, cfg, layout, params, cache, packed, kept, verify_k):
    """The dispatch as it went before the buffer: every array a device array
    of its own, handed to the family's forward; a token the round before
    left on the device is taken from there on the host."""
    tables = {name: np.array(a)
              for name, a in unpack(layout, np.asarray(packed)).items()}
    src = tables.pop("src")
    tables["tokens"][:, 0] = np.where(src < 0, tables["tokens"][:, 0],
                                      np.asarray(kept)[np.maximum(src, 0)])
    tables = {name: jnp.asarray(a) for name, a in tables.items()}
    tokens, q_len, seen = (tables.pop(n) for n in ("tokens", "q_len", "seen"))
    extra = () if verify_k is None else (verify_k,)
    return forward_fn(cfg, params, cache, tokens, q_len, seen, tables, *extra)


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_served_run_through_the_packed_buffer(family, monkeypatch):
    arrays, table_names = FAMILIES[family]
    make_engine, vocab = _build(family)
    prompts = _prompts(family, vocab)

    # pack -> unpack gives back every array of every dispatch exactly, the
    # layout names them in the order the program takes them, and the buffer
    # is what crosses
    packed_fields, dispatched = [], []

    def recording_pack(fields):
        layout, packed = pack(fields)
        packed_fields.append((fields, layout, packed))
        return layout, packed

    program = engine_v2.packed_forward

    def recording_program(forward_fn, cfg, layout, params, cache, packed, kept, verify_k):
        dispatched.append((layout, np.asarray(packed), verify_k))
        return program(forward_fn, cfg, layout, params, cache, packed, kept, verify_k)

    monkeypatch.setattr(engine_v2, "pack", recording_pack)
    monkeypatch.setattr(engine_v2, "packed_forward", recording_program)
    jax.clear_caches()
    engine = make_engine()
    ids, shapes = _serve(engine, prompts)
    assert len(ids) == 3 and all(len(v) == 6 for v in ids.values())
    assert len(packed_fields) == len(dispatched) == engine.dispatch > 3
    verify_ks = set()
    for (fields, layout, packed), (got_layout, got_packed, verify_k) in zip(
            packed_fields, dispatched):
        assert list(fields) == ["tokens", "q_len", "seen", "src"] + table_names
        assert len(fields) == arrays
        assert layout == got_layout == tuple((n, a.shape) for n, a in fields.items())
        assert packed.dtype == np.int32 and packed.ndim == 1
        assert packed.nbytes == sum(a.nbytes for a in fields.values())
        np.testing.assert_array_equal(packed, got_packed)
        back = unpack(layout, packed)
        assert list(back) == list(fields)
        for name, a in fields.items():
            assert back[name].shape == a.shape and back[name].dtype == np.int32
            np.testing.assert_array_equal(np.asarray(back[name]), a)
        verify_ks.add(verify_k)
    # a speculating engine verifies every round, [last] + 4 drafts in the
    # power-of-two bucket of 8 positions a row
    assert verify_ks == ({8} if family == "llama-verify" else {None})

    # one program a (seq_bucket, chunk_bucket, verify_k): the layout is a
    # function of the two buckets, never of a row count or a table's fill
    keys = {(dict(layout)["tokens"], k) for layout, _, k in dispatched}
    assert {shape for shape, _ in keys} == shapes
    assert len({layout for layout, _, _ in dispatched}) == len(shapes)
    assert program._cache_size() == len(keys)

    # the same run with every dispatch handed to the family's forward as
    # separate arrays emits the same ids, bit for bit
    monkeypatch.setattr(engine_v2, "packed_forward", _separate_arrays)
    assert _serve(make_engine(), prompts)[0] == ids


def test_a_table_of_another_dtype_is_refused_by_name():
    fields = {"tokens": np.zeros((4, 1), np.int32), "q_len": np.zeros(4, np.int32),
              "seen": np.zeros(4, np.int32), "kv": np.zeros((4, 8), np.int64)}
    with pytest.raises(TypeError, match="'kv' is int64"):
        pack(fields)
    layout, packed = pack({**fields, "kv": np.zeros((4, 8), np.int32)})
    with pytest.raises(ValueError, match="layout holds 44 values, the buffer 43"):
        unpack(layout, packed[:-1])


def test_each_dispatch_of_a_round_reads_a_buffer_of_its_own(monkeypatch):
    """A round of TWO dispatches built back to back (two decode rows as
    ``[4, 1]``, a 12-token chunk as ``[1, 16]``): the second is packed while
    the first's transfer may still read its host memory (the CPU backend
    aliases it), so no buffer is reused; each program sees its own values."""
    make_engine, vocab = _build("llama")
    engine = make_engine()
    rng = np.random.default_rng(41)
    first = [rng.integers(0, vocab, 7).astype(np.int32) for _ in range(2)]
    engine.put([0, 1], first)
    host, seen = [], []

    def recording_pack(fields):
        layout, packed = pack(fields)
        host.append(packed)
        return layout, packed

    program = engine_v2.packed_forward

    def recording_program(forward_fn, cfg, layout, params, cache, packed, kept, verify_k):
        seen.append((layout, packed))
        return program(forward_fn, cfg, layout, params, cache, packed, kept, verify_k)

    monkeypatch.setattr(engine_v2, "pack", recording_pack)
    monkeypatch.setattr(engine_v2, "packed_forward", recording_program)
    rows = [np.array([3], np.int32), np.array([5], np.int32),
            rng.integers(0, vocab, 12).astype(np.int32)]
    engine.put([0, 1, 2], rows)
    assert engine.last_batch_shapes == [(4, 1), (1, 16)]
    (a, b) = host
    assert not np.shares_memory(a, b) and a.base is None and b.base is None
    # after the whole round, each dispatch's device buffer still holds what
    # was packed for it, and its slices are that dispatch's rows
    for (layout, packed), kept in zip(seen, host):
        np.testing.assert_array_equal(np.asarray(packed), kept)
    decode, chunk = (unpack(layout, np.asarray(packed)) for layout, packed in seen)
    assert np.asarray(decode["tokens"])[:2, 0].tolist() == [3, 5]
    assert np.asarray(decode["q_len"]).tolist() == [1, 1, 0, 0]
    assert np.asarray(decode["seen"])[:2].tolist() == [7, 7]
    np.testing.assert_array_equal(np.asarray(chunk["tokens"])[0, :12], rows[2])
    assert np.asarray(chunk["q_len"]).tolist() == [12]
    assert np.asarray(chunk["seen"]).tolist() == [0]
