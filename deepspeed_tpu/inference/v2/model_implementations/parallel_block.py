"""Ragged (paged-KV) forward for the parallel-residual families (Falcon/Phi).

Reference v2 implementations ``inference/v2/model_implementations/{falcon,phi}``
(two of the eight ``engine_factory.py:68-129`` families). Shares the paged
attention pieces with the llama implementation; the block math follows
``models/parallel_block.py`` (shared input layernorm, parallel attn+mlp
residual, fused-MQA or split qkv, partial rotary).
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.parallel_block import partial_rotary
from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
    _paged_attention, _pool_block_size, _scatter_kv, last_token, layer_rows,
    layer_trash, merge_layers, pool_pages_per_layer, split_layers)


def _layernorm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def ragged_forward(cfg, params, cache, tokens, q_len, seen, tables):
    """One ragged Falcon/Phi forward step -> (last-token logits, new cache);
    the contract is ``llama.ragged_forward``'s."""
    (k_pool, v_pool), block_tables = cache["kv"], tables["kv"]
    S, Q = tokens.shape
    H, KV, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    L = cfg.num_hidden_layers
    bs = _pool_block_size(k_pool)  # [L, NB, KV, bs, Dh] (pair when int8)
    nb = pool_pages_per_layer(k_pool)
    positions = seen[:, None] + jnp.arange(Q)[None, :]

    embed = params["embed_tokens"].astype(cfg.dtype)
    x = embed[tokens]

    def lin(p, h):
        y = h @ p["kernel"].astype(cfg.dtype)
        if "bias" in p:
            y = y + p["bias"].astype(cfg.dtype)
        return y

    # the unrolled loop runs over the one merged pool (paged_layer.py, "The
    # layout")
    k_pool, v_pool = merge_layers((k_pool, v_pool))
    for i in range(L):
        lp = params[f"layers_{i}"]
        layer_tables = layer_rows(block_tables, i, nb)
        ln = lp["input_layernorm"]
        h = _layernorm(x, ln["scale"], ln["bias"], cfg.layer_norm_eps)
        if cfg.fused_qkv:
            qkv = lin(lp["query_key_value"], h)
            q = qkv[..., : H * Dh].reshape(S, Q, H, Dh)
            k = qkv[..., H * Dh: (H + KV) * Dh].reshape(S, Q, KV, Dh)
            v = qkv[..., (H + KV) * Dh:].reshape(S, Q, KV, Dh)
        else:
            q = lin(lp["q_proj"], h).reshape(S, Q, H, Dh)
            k = lin(lp["k_proj"], h).reshape(S, Q, KV, Dh)
            v = lin(lp["v_proj"], h).reshape(S, Q, KV, Dh)
        q = partial_rotary(q, positions, cfg.rope_theta, cfg.rotary_dim)
        k = partial_rotary(k, positions, cfg.rope_theta, cfg.rotary_dim)
        k_pool, v_pool = _scatter_kv(k_pool, v_pool, k, v, layer_tables, seen,
                                     q_len, bs, trash=layer_trash(i, nb))
        attn = _paged_attention(q, k_pool, v_pool, layer_tables, seen, bs,
                                q_len)
        attn_out = lin(lp["dense"], attn.reshape(S, Q, H * Dh))
        mlp_out = lin(lp["fc2"], jax.nn.gelu(lin(lp["fc1"], h),
                                             approximate=not cfg.gelu_exact))
        x = x + attn_out + mlp_out
    k_pool, v_pool = split_layers((k_pool, v_pool), L)

    fl = params["final_layernorm"]
    x = _layernorm(x, fl["scale"], fl["bias"], cfg.layer_norm_eps)
    head = embed if cfg.tie_lm_head else params["lm_head"].astype(cfg.dtype)
    logits = last_token(x, q_len) @ head.T
    if "lm_head_bias" in params:
        logits = logits + params["lm_head_bias"].astype(cfg.dtype)
    return logits.astype(jnp.float32), {"kv": (k_pool, v_pool)}
