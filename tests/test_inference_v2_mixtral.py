"""Mixtral ragged (MoE) serving + engine factory tests.

Gold oracle: transformers' torch Mixtral — build_hf_engine must reproduce its
next-token logits through the paged/ragged path (prefill + decode), which
exercises the grouped-expert GEMM dispatch (moe_gather/scatter analog) and the
paged KV cache end to end.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")

from deepspeed_tpu.inference.v2.engine_factory import build_hf_engine


def tiny_mixtral(tmp_path, seed=0):
    cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=1,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=128, tie_word_embeddings=False)
    torch.manual_seed(seed)
    hf = transformers.MixtralForCausalLM(cfg).eval()
    d = str(tmp_path / "mixtral")
    hf.save_pretrained(d, safe_serialization=True)
    return hf, d


def hf_next_logits(hf, ids):
    with torch.no_grad():
        return hf(torch.from_numpy(np.asarray(ids))).logits[:, -1].float().numpy()


def test_build_hf_engine_mixtral_prefill_parity(tmp_path):
    hf, d = tiny_mixtral(tmp_path)
    eng = build_hf_engine(d, {"state_manager": {"max_ragged_sequence_count": 4,
                                                "max_ragged_batch_size": 64,
                                                "max_context": 128}},
                          dtype=np.float32)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 128, size=16).astype(np.int32)
    logits = eng.put([7], [prompt])
    ref = hf_next_logits(hf, prompt[None])
    np.testing.assert_allclose(logits[0], ref[0], atol=2e-2, rtol=2e-2)


def test_mixtral_decode_matches_hf_generation(tmp_path):
    """Greedy decode through the ragged engine == HF greedy continuation."""
    hf, d = tiny_mixtral(tmp_path, seed=1)
    eng = build_hf_engine(d, {"state_manager": {"max_ragged_sequence_count": 2,
                                                "max_ragged_batch_size": 64,
                                                "max_context": 128}},
                          dtype=np.float32)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 128, size=8).astype(np.int32)

    ids = list(prompt)
    ours = []
    logits = eng.put([1], [np.asarray(ids, np.int32)])
    for _ in range(6):
        nxt = int(np.argmax(logits[0]))
        ours.append(nxt)
        logits = eng.put([1], [np.asarray([nxt], np.int32)])

    theirs = []
    t_ids = list(prompt)
    for _ in range(6):
        nxt = int(np.argmax(hf_next_logits(hf, np.asarray(t_ids, np.int64)[None])[0]))
        theirs.append(nxt)
        t_ids.append(nxt)
    assert ours == theirs, (ours, theirs)


def test_mixtral_multi_sequence_ragged_batch(tmp_path):
    hf, d = tiny_mixtral(tmp_path, seed=2)
    eng = build_hf_engine(d, {"state_manager": {"max_ragged_sequence_count": 4,
                                                "max_ragged_batch_size": 64,
                                                "max_context": 128}},
                          dtype=np.float32)
    rng = np.random.default_rng(2)
    p1 = rng.integers(0, 128, size=12).astype(np.int32)
    p2 = rng.integers(0, 128, size=5).astype(np.int32)
    logits = eng.put([11, 22], [p1, p2])
    r1 = hf_next_logits(hf, p1[None])[0]
    r2 = hf_next_logits(hf, p2[None])[0]
    np.testing.assert_allclose(logits[0], r1, atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(logits[1], r2, atol=2e-2, rtol=2e-2)
    eng.flush(11)
    eng.flush(22)


def test_build_hf_engine_rejects_unknown_family(tmp_path):
    cfg = transformers.GPT2Config(vocab_size=64, n_positions=16, n_embd=16,
                                  n_layer=1, n_head=1)
    torch.manual_seed(3)
    m = transformers.GPT2LMHeadModel(cfg)
    d = str(tmp_path / "gpt2")
    m.save_pretrained(d, safe_serialization=True)
    with pytest.raises(ValueError, match="ragged engine supports"):
        build_hf_engine(d)


def test_paged_attention_dense_on_cpu(monkeypatch):
    """Kernel-eligible shapes, but no TPU and no interpret mode: the read is
    the dense twin's program, and no Pallas call is traced."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.model_implementations import paged_layer
    monkeypatch.delenv("DS_TPU_PALLAS_INTERPRET", raising=False)
    q = jnp.zeros((2, 1, 4, 64))
    pool = jnp.zeros((8, 2, 16, 64))
    tables, seen = jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32)
    q_len = jnp.ones((2,), jnp.int32)
    took = jax.make_jaxpr(lambda *a: paged_layer._paged_attention(
        *a, 16, q_len))(q, pool, pool, tables, seen)
    dense = jax.make_jaxpr(lambda *a: paged_layer._paged_attention_dense(
        *a, 16))(q, pool, pool, tables, seen)
    assert "pallas_call" not in str(took)
    assert str(took) == str(dense)


def test_qwen2_bias_through_v2_engine(tmp_path):
    cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=1,
        max_position_embeddings=128, tie_word_embeddings=False)
    torch.manual_seed(4)
    hf = transformers.Qwen2ForCausalLM(cfg).eval()
    d = str(tmp_path / "qwen2")
    hf.save_pretrained(d, safe_serialization=True)
    eng = build_hf_engine(d, {"state_manager": {"max_ragged_sequence_count": 2,
                                                "max_ragged_batch_size": 64,
                                                "max_context": 128}},
                          dtype=np.float32)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 128, size=10).astype(np.int32)
    logits = eng.put([1], [prompt])
    ref = hf_next_logits(hf, prompt[None])
    np.testing.assert_allclose(logits[0], ref[0], atol=2e-2, rtol=2e-2)


def test_mistral_sliding_window_through_v2_engine(tmp_path):
    cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=1,
        max_position_embeddings=128, sliding_window=8,
        tie_word_embeddings=False)
    torch.manual_seed(5)
    hf = transformers.MistralForCausalLM(cfg).eval()
    d = str(tmp_path / "mistral")
    hf.save_pretrained(d, safe_serialization=True)
    eng = build_hf_engine(d, {"state_manager": {"max_ragged_sequence_count": 2,
                                                "max_ragged_batch_size": 64,
                                                "max_context": 128}},
                          dtype=np.float32)
    # prompt longer than the window so windowing actually matters
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 128, size=24).astype(np.int32)
    logits = eng.put([1], [prompt])
    ref = hf_next_logits(hf, prompt[None])
    np.testing.assert_allclose(logits[0], ref[0], atol=2e-2, rtol=2e-2)


def test_falcon_through_v2_engine(tmp_path):
    cfg = transformers.FalconConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=True,
        new_decoder_architecture=False, parallel_attn=True, bias=False,
        alibi=False, max_position_embeddings=128, tie_word_embeddings=False)
    torch.manual_seed(6)
    hf = transformers.FalconForCausalLM(cfg).eval()
    d = str(tmp_path / "falcon")
    hf.save_pretrained(d, safe_serialization=True)
    eng = build_hf_engine(d, {"state_manager": {"max_ragged_sequence_count": 2,
                                                "max_ragged_batch_size": 64,
                                                "max_context": 128}},
                          dtype=np.float32)
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, 128, size=11).astype(np.int32)
    logits = eng.put([1], [prompt])
    ref = hf_next_logits(hf, prompt[None])
    np.testing.assert_allclose(logits[0], ref[0], atol=2e-2, rtol=2e-2)
    # decode continues greedily in agreement
    nxt = int(np.argmax(logits[0]))
    logits2 = eng.put([1], [np.asarray([nxt], np.int32)])
    ref2 = hf_next_logits(hf, np.asarray(list(prompt) + [nxt], np.int64)[None])
    np.testing.assert_allclose(logits2[0], ref2[0], atol=2e-2, rtol=2e-2)


def test_phi_through_v2_engine(tmp_path):
    cfg = transformers.PhiConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        partial_rotary_factor=0.5, max_position_embeddings=128,
        tie_word_embeddings=False)
    torch.manual_seed(7)
    hf = transformers.PhiForCausalLM(cfg).eval()
    d = str(tmp_path / "phi")
    hf.save_pretrained(d, safe_serialization=True)
    eng = build_hf_engine(d, {"state_manager": {"max_ragged_sequence_count": 2,
                                                "max_ragged_batch_size": 64,
                                                "max_context": 128}},
                          dtype=np.float32)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 128, size=9).astype(np.int32)
    logits = eng.put([1], [prompt])
    ref = hf_next_logits(hf, prompt[None])
    np.testing.assert_allclose(logits[0], ref[0], atol=2e-2, rtol=2e-2)


def test_opt_through_v2_engine(tmp_path):
    """OPT completes the reference's v2 family set (engine_factory.py:99)."""
    cfg = transformers.OPTConfig(
        vocab_size=128, hidden_size=64, ffn_dim=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=128,
        do_layer_norm_before=True, word_embed_proj_dim=64)
    torch.manual_seed(8)
    hf = transformers.OPTForCausalLM(cfg).eval()
    d = str(tmp_path / "opt")
    hf.save_pretrained(d, safe_serialization=True)
    eng = build_hf_engine(d, {"state_manager": {"max_ragged_sequence_count": 2,
                                                "max_ragged_batch_size": 64,
                                                "max_context": 128}},
                          dtype=np.float32)
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, 128, size=12).astype(np.int32)
    logits = eng.put([1], [prompt])
    ref = hf_next_logits(hf, prompt[None])
    np.testing.assert_allclose(logits[0], ref[0], atol=2e-2, rtol=2e-2)
    # decode leg (positions must keep the +2 OPT offset through the cache)
    nxt = int(np.argmax(logits[0]))
    logits2 = eng.put([1], [np.asarray([nxt], np.int32)])
    ref2 = hf_next_logits(hf, np.asarray(list(prompt) + [nxt], np.int64)[None])
    np.testing.assert_allclose(logits2[0], ref2[0], atol=2e-2, rtol=2e-2)
