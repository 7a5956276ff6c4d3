"""Numerics tests for the Pallas flash-attention kernel vs the XLA reference.

Runs in Pallas interpret mode on the CPU mesh (the kernel itself is exercised
compiled on real TPU by bench.py); mirrors the reference's per-kernel numerics
tests under ``tests/unit/ops/``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.flash_attention import mha_reference
from deepspeed_tpu.ops.pallas.flash_attention import flash_mha, is_supported


def make_qkv(B=2, T=256, H=4, KV=None, Dh=64, dtype=jnp.float32, seed=0):
    KV = KV or H
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, T, KV, Dh), dtype)
    v = jax.random.normal(ks[2], (B, T, KV, Dh), dtype)
    return q, k, v


def assert_close(a, b, atol=2e-3):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=atol, rtol=2e-2)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = make_qkv()
    out = flash_mha(q, k, v, causal=causal, interpret=True)
    ref = mha_reference(q, k, v, causal=causal)
    assert_close(out, ref)


def test_forward_gqa():
    q, k, v = make_qkv(H=8, KV=2)
    out = flash_mha(q, k, v, causal=True, interpret=True)
    ref = mha_reference(q, k, v, causal=True)
    assert_close(out, ref)


def test_forward_bias_broadcast():
    B, T, H = 2, 256, 4
    q, k, v = make_qkv(B=B, T=T, H=H)
    # [1, 1, T, T] sliding-window-style mask bias (the llama/mistral shape)
    pos = jnp.arange(T)
    near = (pos[:, None] - pos[None, :]) < 64
    bias = jnp.where(near, 0.0, -1e9)[None, None]
    out = flash_mha(q, k, v, bias=bias, causal=True, interpret=True)
    ref = mha_reference(q, k, v, bias=bias, causal=True)
    assert_close(out, ref)


def test_forward_bias_full_batch_head():
    B, T, H = 2, 128, 4
    q, k, v = make_qkv(B=B, T=T, H=H)
    bias = jax.random.normal(jax.random.PRNGKey(7), (B, H, T, T)) * 0.5
    out = flash_mha(q, k, v, bias=bias, causal=False, interpret=True)
    ref = mha_reference(q, k, v, bias=bias, causal=False)
    assert_close(out, ref)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_rectangular(causal):
    # Tq != Tk: causal must be bottom-right aligned (tril offset Tk-Tq),
    # matching mha_reference — the chunked-prefill / cross-attention shape
    B, H, Dh = 2, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, 128, H, Dh))
    k = jax.random.normal(ks[1], (B, 384, H, Dh))
    v = jax.random.normal(ks[2], (B, 384, H, Dh))
    out = flash_mha(q, k, v, causal=causal, interpret=True)
    ref = mha_reference(q, k, v, causal=causal)
    assert_close(out, ref)


def test_gradients_rectangular_causal():
    B, H, Dh = 1, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, 128, H, Dh))
    k = jax.random.normal(ks[1], (B, 256, H, Dh))
    v = jax.random.normal(ks[2], (B, 256, H, Dh))

    gf = jax.grad(lambda q, k, v: jnp.sum(
        flash_mha(q, k, v, causal=True, interpret=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        mha_reference(q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert_close(a, b, atol=5e-3)


def test_softmax_scale():
    q, k, v = make_qkv(T=128)
    out = flash_mha(q, k, v, causal=True, softmax_scale=0.25, interpret=True)
    ref = mha_reference(q, k, v, causal=True, softmax_scale=0.25)
    assert_close(out, ref)


@pytest.mark.parametrize("kv_heads", [4, 2])
def test_gradients_match_reference(kv_heads):
    q, k, v = make_qkv(B=1, T=128, H=4, KV=kv_heads)

    def loss_flash(q, k, v):
        return jnp.sum(flash_mha(q, k, v, causal=True, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert_close(a, b, atol=5e-3)


def test_gradients_with_bias():
    q, k, v = make_qkv(B=1, T=128, H=2)
    pos = jnp.arange(128)
    bias = jnp.where((pos[:, None] - pos[None, :]) < 32, 0.0, -1e9)[None, None]

    def loss_flash(q, k, v):
        return jnp.sum(flash_mha(q, k, v, bias=bias, causal=True, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, bias=bias, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert_close(a, b, atol=5e-3)


def test_bf16_tolerances():
    q, k, v = make_qkv(T=256, dtype=jnp.bfloat16)
    out = flash_mha(q, k, v, causal=True, interpret=True)
    ref = mha_reference(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    assert_close(out, ref, atol=2e-2)


def test_is_supported_gating():
    assert is_supported((2, 256, 4, 64), (2, 256, 4, 64))
    assert is_supported((2, 256, 8, 64), (2, 256, 2, 64))        # GQA
    assert not is_supported((2, 100, 4, 64), (2, 100, 4, 64))    # not tileable
    assert not is_supported((2, 256, 3, 64), (2, 256, 2, 64))    # H % KV != 0
    assert not is_supported((2, 256, 4, 512), (2, 256, 4, 512))  # Dh too big
    assert is_supported((2, 256, 4, 64), (2, 256, 4, 64), (1, 1, 256, 256))
    assert not is_supported((2, 256, 4, 64), (2, 256, 4, 64), (3, 1, 256, 256))


def test_mha_entry_point_falls_back_on_cpu():
    # on the CPU test mesh the builder is incompatible -> reference path
    from deepspeed_tpu.ops.flash_attention import mha
    q, k, v = make_qkv(T=64)
    out = mha(q, k, v, causal=True)
    assert_close(out, mha_reference(q, k, v, causal=True))

# ---------------------------------------------------------------------------
# sliding window + segment ids (in-kernel; VERDICT r2 #6)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [32, 64, 200])
def test_forward_sliding_window(window):
    q, k, v = make_qkv(T=256)
    out = flash_mha(q, k, v, causal=True, window=window, interpret=True)
    ref = mha_reference(q, k, v, causal=True, window=window)
    assert_close(out, ref)


def test_forward_sliding_window_rectangular():
    # chunked-prefill shape: Tq < Tk with bottom-right-aligned window
    B, H, Dh = 2, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, 128, H, Dh))
    k = jax.random.normal(ks[1], (B, 384, H, Dh))
    v = jax.random.normal(ks[2], (B, 384, H, Dh))
    out = flash_mha(q, k, v, causal=True, window=96, interpret=True)
    ref = mha_reference(q, k, v, causal=True, window=96)
    assert_close(out, ref)


def test_gradients_sliding_window():
    q, k, v = make_qkv(B=1, T=256, H=2)

    gf = jax.grad(lambda q, k, v: jnp.sum(
        flash_mha(q, k, v, causal=True, window=48, interpret=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        mha_reference(q, k, v, causal=True, window=48) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert_close(a, b, atol=5e-3)


def _packed_segments(B, T, n_seg, seed=0):
    """Random contiguous segment partition of each row (packed sequences)."""
    rng = np.random.default_rng(seed)
    ids = np.zeros((B, T), np.int32)
    for b in range(B):
        cuts = np.sort(rng.choice(np.arange(1, T), size=n_seg - 1, replace=False))
        ids[b] = np.searchsorted(cuts, np.arange(T), side="right")
    return jnp.asarray(ids)


@pytest.mark.parametrize("n_seg", [2, 5])
def test_forward_segment_ids(n_seg):
    B, T = 2, 256
    q, k, v = make_qkv(B=B, T=T)
    seg = _packed_segments(B, T, n_seg)
    out = flash_mha(q, k, v, causal=True, segment_ids=seg, interpret=True)
    ref = mha_reference(q, k, v, causal=True, segment_ids=seg)
    assert_close(out, ref)


def test_forward_segment_ids_gqa_bf16():
    B, T = 2, 256
    q, k, v = make_qkv(B=B, T=T, H=8, KV=2, dtype=jnp.bfloat16)
    seg = _packed_segments(B, T, 3, seed=4)
    out = flash_mha(q, k, v, causal=True, segment_ids=seg, interpret=True)
    ref = mha_reference(q, k, v, causal=True, segment_ids=seg)
    assert_close(out, ref, atol=2e-2)


def test_gradients_segment_ids():
    B, T = 1, 128
    q, k, v = make_qkv(B=B, T=T, H=2)
    seg = _packed_segments(B, T, 3, seed=2)

    gf = jax.grad(lambda q, k, v: jnp.sum(
        flash_mha(q, k, v, causal=True, segment_ids=seg, interpret=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        mha_reference(q, k, v, causal=True, segment_ids=seg) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert_close(a, b, atol=5e-3)


def test_window_with_segment_ids_combined():
    B, T = 2, 256
    q, k, v = make_qkv(B=B, T=T)
    seg = _packed_segments(B, T, 2, seed=9)
    out = flash_mha(q, k, v, causal=True, window=64, segment_ids=seg,
                    interpret=True)
    ref = mha_reference(q, k, v, causal=True, window=64, segment_ids=seg)
    assert_close(out, ref)


def test_is_supported_window_segments():
    assert is_supported((2, 256, 4, 64), (2, 256, 4, 64), window=128)
    assert not is_supported((2, 256, 4, 64), (2, 256, 4, 64), window=0)
    assert is_supported((2, 256, 4, 64), (2, 256, 4, 64),
                        segment_ids_shape=((2, 256), (2, 256)))
    assert not is_supported((2, 256, 4, 64), (2, 256, 4, 64),
                            segment_ids_shape=((2, 128), (2, 256)))


def test_llama_sliding_window_off_bias_path():
    """models/llama.py must pass the window through mha (no [T,T] bias)."""
    import inspect
    from deepspeed_tpu.models import llama
    src = inspect.getsource(llama.LlamaAttention)
    # the non-cache branch must not materialize a [T, T] window mask
    assert "window=cfg.sliding_window" in src


def test_window_zero_disabled_or_rejected():
    """sliding_window=0 means 'disabled' at the model layer; mha raises on it
    rather than silently masking everything (code-review r3 finding)."""
    from deepspeed_tpu.ops.flash_attention import mha
    q, k, v = make_qkv(T=64)
    with pytest.raises(ValueError):
        mha(q, k, v, causal=True, window=0)
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=32,
                      sliding_window=0)
    model = LlamaForCausalLM(cfg)
    ids = np.arange(16, dtype=np.int32)[None, :] % 64
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    logits = model.apply({"params": params}, {"input_ids": ids})
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    # window=0 must equal no-window (disabled), not fully-masked attention
    cfg_nw = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                         num_hidden_layers=1, num_attention_heads=2,
                         num_key_value_heads=2, max_position_embeddings=32,
                         sliding_window=None)
    logits_nw = LlamaForCausalLM(cfg_nw).apply({"params": params}, {"input_ids": ids})
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(logits_nw, np.float32), atol=1e-5)


# ---------------------------------------------------------------------------
# seq-length auto-padding (non-128-multiple inputs stay on the kernel path)
# ---------------------------------------------------------------------------

def _pad_and_run(q, k, v, bias=None, causal=True, window=None,
                 segment_ids=None):
    from deepspeed_tpu.ops.flash_attention import _pad_seq_to_lanes
    if segment_ids is not None and not isinstance(segment_ids, (tuple, list)):
        segment_ids = (segment_ids, segment_ids)
    q2, k2, v2, b2, s2, T = _pad_seq_to_lanes(q, k, v, bias, segment_ids,
                                              causal)
    assert q2.shape[1] % 128 == 0
    out = flash_mha(q2, k2, v2, bias=b2, causal=causal, window=window,
                    segment_ids=s2, interpret=True)
    return out[:, :T]


@pytest.mark.parametrize("T", [200, 77])
def test_padded_causal_matches_reference(T):
    q, k, v = make_qkv(T=256)
    q, k, v = q[:, :T], k[:, :T], v[:, :T]
    got = _pad_and_run(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    assert_close(got, ref)


def test_padded_bidirectional_masks_padding():
    # non-causal: synthesized pad segments must keep pad keys invisible
    q, k, v = make_qkv(T=256)
    q, k, v = q[:, :150], k[:, :150], v[:, :150]
    got = _pad_and_run(q, k, v, causal=False)
    ref = mha_reference(q, k, v, causal=False)
    assert_close(got, ref)


def test_padded_with_segments_and_window():
    B, T = 2, 180
    q, k, v = make_qkv(B=B, T=256)
    q, k, v = q[:, :T], k[:, :T], v[:, :T]
    seg = _packed_segments(B, T, 3, seed=5)
    got = _pad_and_run(q, k, v, causal=True, window=64, segment_ids=seg)
    ref = mha_reference(q, k, v, causal=True, window=64, segment_ids=seg)
    assert_close(got, ref)


def test_padded_gradients_match():
    q, k, v = make_qkv(B=1, T=256, H=2)
    q, k, v = q[:, :200], k[:, :200], v[:, :200]
    gf = jax.grad(lambda q, k, v: jnp.sum(
        _pad_and_run(q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        mha_reference(q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert_close(a, b, atol=5e-3)


def test_mha_nonstandard_bias_falls_back_gracefully(monkeypatch):
    """Non-4D / broadcast-T bias with odd seq len must route to the XLA
    reference, not crash in the padding helper (review r3 finding). The
    flash branch is forced on (is_compatible monkeypatched) so the padding
    guard actually executes on the CPU test mesh; the kernel itself must
    never be reached for this shape."""
    import deepspeed_tpu.ops.flash_attention as mod
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    monkeypatch.setattr(mod.FlashAttnBuilder, "is_compatible",
                        lambda self: True)

    def boom(*a, **kw):
        raise AssertionError("flash kernel must not run for a 2D bias")
    monkeypatch.setattr(fa, "flash_mha", boom)
    q, k, v = make_qkv(T=256)
    q, k, v = q[:, :200], k[:, :200], v[:, :200]
    bias2d = jnp.zeros((200, 200))
    out = mod.mha(q, k, v, bias=bias2d, causal=True)
    assert_close(out, mha_reference(q, k, v, bias=bias2d, causal=True))


# ---------------------------------------------------------------------------
# the walk inside a block (sub-tiles up to the diagonal / the window's edge)
# ---------------------------------------------------------------------------

def _walk_case(tq=512, tk=512, blocks=(512, 512), tiles=(128, 128), H=2,
               KV=2, causal=True, window=None, seg=False, bias=False):
    return dict(tq=tq, tk=tk, blocks=blocks, tiles=tiles, H=H, KV=KV,
                causal=causal, window=window, seg=seg, bias=bias)


WALK_CASES = {
    # nq = nk = 1: every bound of the walk is static
    "causal-one-block": _walk_case(),
    "causal-one-block-tiles-256x128": _walk_case(tiles=(256, 128)),
    # several blocks: the bounds follow the block's place
    "causal-blocks": _walk_case(blocks=(256, 256)),
    "rect-tq-lt-tk": _walk_case(tq=256, blocks=(256, 256)),
    "rect-one-q-block": _walk_case(tq=256, blocks=(256, 512)),
    "window-narrower-than-a-tile": _walk_case(window=40),
    "window-wider-than-a-block": _walk_case(blocks=(256, 256), window=300),
    "window-non-causal": _walk_case(blocks=(256, 256), causal=False,
                                    window=200),
    "segment-ids": _walk_case(seg=True),
    "segment-ids-blocks": _walk_case(blocks=(256, 256), seg=True),
    "bias": _walk_case(blocks=(256, 256), bias=True),
    "gqa": _walk_case(blocks=(512, 256), H=4, KV=2),
    "non-causal": _walk_case(tq=256, blocks=(256, 256), causal=False),
}


def _tiles_touched(tq, tk, sq, sk, causal, window):
    """Share of the square in [sq, sk] tiles that hold a visible pair, by
    enumerating the position mask."""
    qpos = np.arange(tq)[:, None] + (tk - tq)
    kpos = np.arange(tk)[None, :]
    mask = np.ones((tq, tk), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= kpos > qpos - window
    tiles = mask.reshape(tq // sq, sq, tk // sk, sk).any(axis=(1, 3))
    return tiles.mean()


@pytest.mark.parametrize("case", list(WALK_CASES), ids=list(WALK_CASES))
def test_walk_matches_reference(case, monkeypatch):
    """Forward and all three gradients through the walk against the plain
    reference, and the share of the square the walk says it computes against
    the mask counted out."""
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    c = WALK_CASES[case]
    monkeypatch.setattr(fa, "_TILES", {k: c["tiles"] for k in fa._TILES})
    tq, tk, H = c["tq"], c["tk"], c["H"]
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    q = jax.random.normal(ks[0], (1, tq, H, 64))
    k = jax.random.normal(ks[1], (1, tk, c["KV"], 64))
    v = jax.random.normal(ks[2], (1, tk, c["KV"], 64))
    w = jax.random.normal(ks[3], (1, tq, H, 64))
    kw = dict(causal=c["causal"], window=c["window"])
    if c["seg"]:
        kw["segment_ids"] = (jnp.arange(tq)[None] // 100).astype(jnp.int32)
    if c["bias"]:
        kw["bias"] = jax.random.normal(ks[4], (1, H, tq, tk)) * 0.5
    blocks = dict(zip(("block_q", "block_k"), c["blocks"]))

    def flash(q, k, v):
        return fa.flash_mha(q, k, v, interpret=True, block_config=blocks, **kw)

    def ref(q, k, v):
        return mha_reference(q, k, v, **kw)

    assert_close(flash(q, k, v), ref(q, k, v))
    gf = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert_close(a, b, atol=5e-3)
    # sub-tiles where the block is the sequence and an edge crosses it, else
    # the block is the tile
    edge = c["causal"] or c["window"] is not None
    walked = edge and c["blocks"] == (tq, tk)
    share = registry.active_kernel_configs()["flash_mha"]["visible_share"]
    assert share == _tiles_touched(
        tq, tk, *(c["tiles"] if walked else c["blocks"]), c["causal"],
        c["window"])
    assert share == 1.0 if not edge else share <= 1.0


def test_visible_share_at_the_training_shape(monkeypatch):
    from deepspeed_tpu.ops.pallas import flash_attention as fa
    share = lambda *a: fa.visible_share(1024, 1024, *a)
    assert share(1024, 1024, True, None) == 0.625          # keys in tiles of 256
    assert {sk for _, sk in fa._TILES.values()} == {256}   # in all three kernels
    assert share(512, 512, True, None) == 0.75     # several blocks: by block
    assert share(1024, 1024, False, None) == 1.0   # no edge, nothing to walk
    assert share(1024, 1024, True, 256) == 0.4375    # both edges
    monkeypatch.setattr(fa, "_TILES", {k: (128, 128) for k in fa._TILES})
    assert share(1024, 1024, True, None) == 0.5625
    # a block no tile divides is walked whole
    assert fa._tiles("dkv", 192, 320, 192, 320, True, None) == (192, 320)


@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["scan_layers", "layer_loop"])
@pytest.mark.parametrize("policy,in_cpu,forwards_a_layer", [
    ("dots", False, 1), ("dots", True, 1), ("everything", False, 2),
    ("nothing", False, 1)])
def test_forward_kernels_in_a_gradient(policy, in_cpu, forwards_a_layer,
                                       scan_layers, monkeypatch):
    """A policy that saves the kernel's residuals by name holds ONE
    ``flash_mha_fwd`` a layer in the gradient's jaxpr; ``everything`` reruns
    it, by its meaning."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.runtime.activation_checkpointing import checkpointing
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setitem(checkpointing._CONFIG, "policy", policy)
    monkeypatch.setitem(checkpointing._CONFIG, "checkpoint_in_cpu", in_cpu)
    cfg = GPT2Config.tiny(scan_layers=scan_layers)
    model = GPT2LMHeadModel(cfg)
    ids = jnp.zeros((1, 128), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), ids))

    def loss(p):
        return jnp.sum(model.apply(p, ids).astype(jnp.float32))

    jaxpr = str(jax.make_jaxpr(jax.grad(loss))(params))
    layers = 1 if scan_layers else cfg.n_layer
    assert jaxpr.count("name=flash_mha_fwd") == forwards_a_layer * layers
    assert jaxpr.count("name=flash_mha_bwd_dq") == layers
