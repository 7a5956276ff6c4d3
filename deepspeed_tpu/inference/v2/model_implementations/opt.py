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

from deepspeed_tpu.inference.v2.model_implementations.llama import (
    _paged_attention, _pool_block_size, _pool_layer, _pool_set_layer,
    _scatter_kv)
from deepspeed_tpu.inference.v2.model_implementations.parallel_block import (
    _layernorm)
from deepspeed_tpu.inference.v2.modules.module_registry import module_preference


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def ragged_forward(cfg, params, cache, tokens, q_len, seen, tables):
    """One ragged OPT forward step -> (last-token logits, new cache);
    the contract is ``llama.ragged_forward``'s."""
    (k_pool, v_pool), block_tables = cache["kv"], tables["kv"]
    S, Q = tokens.shape
    H = cfg.num_attention_heads
    Dh = cfg.hidden_size // H
    bs = _pool_block_size(k_pool)  # [L, NB, KV, bs, Dh] (pair when int8)
    positions = seen[:, None] + jnp.arange(Q)[None, :]

    embed = params["embed_tokens"].astype(cfg.dtype)
    pos_emb = params["embed_positions"].astype(cfg.dtype)
    x = embed[tokens] + pos_emb[positions + cfg.POSITION_OFFSET]

    def lin(p, h):
        return h @ p["kernel"].astype(cfg.dtype) + p["bias"].astype(cfg.dtype)

    layers = params["layers"]["block"] if "layers" in params else None

    def layer_step(x, lp, kp, vp):
        at = lp["self_attn"]
        ln = lp["self_attn_layer_norm"]
        h = _layernorm(x, ln["scale"], ln["bias"], cfg.layer_norm_epsilon)
        q = lin(at["q_proj"], h).reshape(S, Q, H, Dh)
        k = lin(at["k_proj"], h).reshape(S, Q, H, Dh)
        v = lin(at["v_proj"], h).reshape(S, Q, H, Dh)
        kp, vp = _scatter_kv(kp, vp, k, v, block_tables, seen, q_len, bs)
        attn = _paged_attention(q, kp, vp, block_tables, seen, bs, q_len=q_len,
                                prefer=module_preference(cfg, "attention"))
        x = x + lin(at["out_proj"], attn.reshape(S, Q, H * Dh))
        ln2 = lp["final_layer_norm"]
        h = _layernorm(x, ln2["scale"], ln2["bias"], cfg.layer_norm_epsilon)
        x = x + lin(lp["fc2"], jax.nn.relu(lin(lp["fc1"], h)))
        return x, kp, vp

    if layers is not None:  # scan-stacked training layout
        def body(x, xs):
            lp, kp, vp = xs
            x, kp, vp = layer_step(x, lp, kp, vp)
            return x, (kp, vp)
        x, (k_pool, v_pool) = jax.lax.scan(body, x, (layers, k_pool, v_pool))
    else:
        for i in range(cfg.num_hidden_layers):
            x, kpi, vpi = layer_step(x, params[f"layers_{i}"],
                                     _pool_layer(k_pool, i),
                                     _pool_layer(v_pool, i))
            k_pool = _pool_set_layer(k_pool, i, kpi)
            v_pool = _pool_set_layer(v_pool, i, vpi)

    fl = params["final_layer_norm"]
    x = _layernorm(x, fl["scale"], fl["bias"], cfg.layer_norm_epsilon)
    last = jnp.take_along_axis(
        x, jnp.maximum(q_len - 1, 0)[:, None, None], axis=1)[:, 0]
    logits = last @ embed.T  # tied lm_head
    return logits.astype(jnp.float32), {"kv": (k_pool, v_pool)}
