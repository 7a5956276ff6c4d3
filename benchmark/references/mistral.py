"""Plain reference for Mistral-7B-family serving: the full forward pass in
straightforward ``jax.numpy`` float32, no kernels, no cache, no batching of
requests of different lengths beyond padding.

Follows the published architecture: RMSNorm, rotary embeddings on q and k,
grouped-query causal attention with a sliding window, SwiGLU, untied LM head.
Departure: rotary pairs are interleaved (x[0::2], x[1::2]), the original RoPE
layout, where the published code splits halves; with seeded random weights the
two are one model under a fixed permutation of each head's columns.
Weights are regenerated from the seed one layer at a time (float32 copies of
the bfloat16 values the configuration serves), so the whole model is never
held and nothing the program made is read.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.references.common import HIGHEST, matmul


def param_spec(cfg):
    """The parameter tree as the program's LlamaForCausalLM(scan_layers)
    holds it; matrices bfloat16, norm scales float32."""
    d, f, L = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // H
    bf, one = jnp.bfloat16, ("const", 1.0)
    blk = lambda *p: ("layers", "block") + p
    lin = lambda name, i, o: (blk(*name, "kernel"), (L, i, o), 1 / math.sqrt(i), bf, True)
    return [
        (("embed_tokens",), (cfg["vocab_size"], d), 0.02, bf, False),
        (("lm_head",), (cfg["vocab_size"], d), 0.02, bf, False),
        (("norm", "scale"), (d,), one, jnp.float32, False),
        (blk("input_layernorm", "scale"), (L, d), one, jnp.float32, True),
        (blk("post_attention_layernorm", "scale"), (L, d), one, jnp.float32, True),
        lin(("self_attn", "q_proj"), d, H * dh),
        lin(("self_attn", "k_proj"), d, KV * dh),
        lin(("self_attn", "v_proj"), d, KV * dh),
        lin(("self_attn", "o_proj"), H * dh, d),
        lin(("mlp", "gate_proj"), d, f),
        lin(("mlp", "up_proj"), d, f),
        lin(("mlp", "down_proj"), f, d),
    ]


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotary(x, theta):
    """x [T, heads, dh], positions 0..T-1, interleaved pairs."""
    T, _, dh = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def _layer_one(cfg, precision, p, x):
    """One decoder layer over one sequence x [T, d]."""
    T, d = x.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // H
    f32 = lambda t: t.astype(jnp.float32)
    a = p["self_attn"]
    h = _rms(x, p["input_layernorm"]["scale"], cfg["rms_norm_eps"])
    q = _rotary(matmul(h, f32(a["q_proj"]["kernel"]), precision).reshape(T, H, dh),
                cfg["rope_theta"])
    k = _rotary(matmul(h, f32(a["k_proj"]["kernel"]), precision).reshape(T, KV, dh),
                cfg["rope_theta"])
    v = matmul(h, f32(a["v_proj"]["kernel"]), precision).reshape(T, KV, dh)
    qg = q.reshape(T, KV, H // KV, dh)
    s = jnp.einsum("tkrd,skd->krts", qg, k, precision=HIGHEST) / math.sqrt(dh)
    pos = jnp.arange(T)
    seen = (pos[None, :] <= pos[:, None]) & (pos[None, :] > pos[:, None] - cfg["sliding_window"])
    s = jnp.where(seen, s, -jnp.inf)
    o = jnp.einsum("krts,skd->tkrd", jax.nn.softmax(s, -1), v, precision=HIGHEST)
    x = x + matmul(o.reshape(T, H * dh), f32(a["o_proj"]["kernel"]), precision)
    h = _rms(x, p["post_attention_layernorm"]["scale"], cfg["rms_norm_eps"])
    m = p["mlp"]
    g = jax.nn.silu(matmul(h, f32(m["gate_proj"]["kernel"]), precision))
    u = matmul(h, f32(m["up_proj"]["kernel"]), precision)
    return x + matmul(g * u, f32(m["down_proj"]["kernel"]), precision)


@functools.partial(jax.jit, static_argnums=(0, 1, 2), donate_argnums=(5,))
def _layer(cfg_items, spec, precision, key, i, x):
    cfg = dict(cfg_items)
    p = weights.layer_tree(key, spec, i)["layers"]["block"]
    return jax.lax.map(lambda row: _layer_one(cfg, precision, p, row), x)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _head(cfg_items, spec, precision, key, x, rows):
    """Final norm and LM head on the gathered rows: x [B, T, d], rows [B, N]
    -> logits [B, N, V]."""
    cfg = dict(cfg_items)
    res = weights.resident_tree(key, spec)
    x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    x = _rms(x, res["norm"]["scale"], cfg["rms_norm_eps"])
    return matmul(x, res["lm_head"].astype(jnp.float32).T, precision)


def _logits(cfg, seed, ids, rows, precision):
    spec = tuple(param_spec(cfg))
    cfg_items = tuple(sorted((k, cfg[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "rms_norm_eps", "rope_theta",
        "sliding_window")))
    key = weights.base_key(seed)
    embed = jax.jit(lambda k: weights.one_leaf(k, spec, ("embed_tokens",)))(key)
    x = embed[ids].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(cfg_items, spec, precision, key, jnp.int32(i), x)
    return _head(cfg_items, spec, precision, key, x, rows)


def served_token_gaps(cfg, seed, prompts, outputs, pad_to, max_new, low_precision=None):
    """For each request (prompt, served output tokens): how far each served
    token's float32-reference logit lies below the reference's best at that
    position. With ``low_precision`` the token that this precision puts first
    takes the served token's place (the control). Returns a flat list."""
    import numpy as np
    B = len(prompts)
    ids = np.zeros((B, pad_to), np.int32)
    rows = np.zeros((B, max_new), np.int32)
    toks = np.zeros((B, max_new), np.int32)
    valid = np.zeros((B, max_new), bool)
    for b, (p, o) in enumerate(zip(prompts, outputs)):
        seq = np.concatenate([p, o[:-1]])
        ids[b, :len(seq)] = seq
        rows[b, :len(o)] = len(p) - 1 + np.arange(len(o))
        toks[b, :len(o)] = o
        valid[b, :len(o)] = True
    ids, rows = jnp.asarray(ids), jnp.asarray(rows)
    ref = _logits(cfg, seed, ids, rows, "f32")
    if low_precision is not None:
        toks = jnp.argmax(_logits(cfg, seed, ids, rows, low_precision), -1)
    at = jnp.take_along_axis(ref, jnp.asarray(toks)[:, :, None], -1)[..., 0]
    gaps = np.asarray(jnp.max(ref, -1) - at)
    return gaps[valid].tolist()
