"""Ragged (paged-KV) OPT forward — completes the reference's v2 family set
(``inference/v2/model_implementations/opt``, ``engine_factory.py:99``).

OPT particulars: learned positional embeddings with the +2 offset (positions
derive from each sequence's ``seen`` count — no rotary), biased projections,
pre-LN sequential residuals, ReLU FFN, lm_head tied to the token embedding.
Shares the paged-attention pieces with the llama implementation.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
    _paged_attention, _pool_block_size, _scatter_kv, last_token, layer_rows,
    layer_trash, merge_layers, pool_pages_per_layer, split_layers)
from deepspeed_tpu.inference.v2.model_implementations.parallel_block import (
    _layernorm)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def ragged_forward(cfg, params, cache, tokens, q_len, seen, tables):
    """One ragged OPT forward step -> (last-token logits, new cache);
    the contract is ``llama.ragged_forward``'s."""
    (k_pool, v_pool), block_tables = cache["kv"], tables["kv"]
    S, Q = tokens.shape
    H = cfg.num_attention_heads
    Dh = cfg.hidden_size // H
    L = cfg.num_hidden_layers
    bs = _pool_block_size(k_pool)  # [L, NB, KV, bs, Dh] (pair when int8)
    nb = pool_pages_per_layer(k_pool)
    positions = seen[:, None] + jnp.arange(Q)[None, :]

    embed = params["embed_tokens"].astype(cfg.dtype)
    pos_emb = params["embed_positions"].astype(cfg.dtype)
    x = embed[tokens] + pos_emb[positions + cfg.POSITION_OFFSET]

    def lin(p, h):
        return h @ p["kernel"].astype(cfg.dtype) + p["bias"].astype(cfg.dtype)

    layers = params["layers"]["block"] if "layers" in params else None

    def layer_step(x, kp, vp, lp, i):
        layer_tables = layer_rows(block_tables, i, nb)
        at = lp["self_attn"]
        ln = lp["self_attn_layer_norm"]
        h = _layernorm(x, ln["scale"], ln["bias"], cfg.layer_norm_epsilon)
        q = lin(at["q_proj"], h).reshape(S, Q, H, Dh)
        k = lin(at["k_proj"], h).reshape(S, Q, H, Dh)
        v = lin(at["v_proj"], h).reshape(S, Q, H, Dh)
        kp, vp = _scatter_kv(kp, vp, k, v, layer_tables, seen, q_len, bs,
                             trash=layer_trash(i, nb))
        attn = _paged_attention(q, kp, vp, layer_tables, seen, bs, q_len)
        x = x + lin(at["out_proj"], attn.reshape(S, Q, H * Dh))
        ln2 = lp["final_layer_norm"]
        h = _layernorm(x, ln2["scale"], ln2["bias"], cfg.layer_norm_epsilon)
        x = x + lin(lp["fc2"], jax.nn.relu(lin(lp["fc1"], h)))
        return x, kp, vp

    # either layout loops over the one merged pool, the pools on the loop's
    # carry (paged_layer.py, "The layout")
    k_pool, v_pool = merge_layers((k_pool, v_pool))
    if layers is not None:  # scan-stacked training layout
        (x, k_pool, v_pool), _ = jax.lax.scan(
            lambda carry, xs: (layer_step(*carry, *xs), None),
            (x, k_pool, v_pool), (layers, jnp.arange(L)))
    else:
        for i in range(L):
            x, k_pool, v_pool = layer_step(x, k_pool, v_pool,
                                           params[f"layers_{i}"], i)
    k_pool, v_pool = split_layers((k_pool, v_pool), L)

    fl = params["final_layer_norm"]
    x = _layernorm(x, fl["scale"], fl["bias"], cfg.layer_norm_epsilon)
    logits = last_token(x, q_len) @ embed.T  # tied lm_head
    return logits.astype(jnp.float32), {"kv": (k_pool, v_pool)}
