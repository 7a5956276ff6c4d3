"""Ragged (paged-KV) Mixtral forward — MoE continuous batching.

Capability analog of the reference's Mixtral v2 implementation
(``inference/v2/model_implementations/mixtral`` + the ragged MoE kernel set
``kernels/ragged_ops/{moe_gather,moe_scatter,top_k_gating}`` and the grouped
``cutlass_ops/moe_gemm``). The expert layer is ``moe_layer.moe_ffn``, shared
with every family that has one: the ragged grouped GEMM where it tiles, else
GShard dense dispatch-combine; a padded token slot takes no expert rows.

Operates on the training param tree of
``deepspeed_tpu.models.mixtral.MixtralForCausalLM`` (non-scanned
``layers_{i}`` naming; experts stacked [E, ...]).
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import rotary_embed
from deepspeed_tpu.inference.v2.model_implementations.llama import _rmsnorm
from deepspeed_tpu.inference.v2.model_implementations.moe_layer import (
    dispatch_report, moe_ffn)  # the first: this family's export
from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
    _paged_attention, _pool_block_size, _scatter_kv, last_token, layer_rows,
    layer_trash, merge_layers, pool_pages_per_layer, real_slots,
    split_layers)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def ragged_forward(cfg, params, cache, tokens, q_len, seen, tables):
    """One ragged Mixtral forward step -> (last-token logits, new cache);
    the contract is ``llama.ragged_forward``'s."""
    (k_pool, v_pool), block_tables = cache["kv"], tables["kv"]
    S, Q = tokens.shape
    H, KV = cfg.num_attention_heads, cfg.num_key_value_heads
    # a head_dim the config states wins, as in ``cache_groups.homogeneous``
    Dh = getattr(cfg, "head_dim", None) or cfg.hidden_size // H
    L = cfg.num_hidden_layers
    bs = _pool_block_size(k_pool)  # [L, NB, KV, bs, Dh] (pair when int8)
    nb = pool_pages_per_layer(k_pool)
    positions = seen[:, None] + jnp.arange(Q)[None, :]
    real = real_slots(q_len, Q).reshape(S * Q)

    x = params["embed_tokens"].astype(cfg.dtype)[tokens]

    def layer_step(x, kp, vp, lp, i):
        layer_tables = layer_rows(block_tables, i, nb)
        attn = lp["self_attn"]
        h = _rmsnorm(x, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
        q = (h @ attn["q_proj"]["kernel"].astype(cfg.dtype)).reshape(S, Q, H, Dh)
        k = (h @ attn["k_proj"]["kernel"].astype(cfg.dtype)).reshape(S, Q, KV, Dh)
        v = (h @ attn["v_proj"]["kernel"].astype(cfg.dtype)).reshape(S, Q, KV, Dh)
        q = rotary_embed(q, positions, cfg.rope_theta)
        k = rotary_embed(k, positions, cfg.rope_theta)
        kp, vp = _scatter_kv(kp, vp, k, v, layer_tables, seen, q_len, bs,
                             trash=layer_trash(i, nb))
        out = _paged_attention(q, kp, vp, layer_tables, seen, bs, q_len)
        x = x + out.reshape(S, Q, H * Dh) @ attn["o_proj"]["kernel"].astype(cfg.dtype)

        moe = lp["block_sparse_moe"]
        ex = moe["experts"]["MixtralExpertMLP_0"]
        h = _rmsnorm(x, lp["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
        y = moe_ffn(h.reshape(S * Q, -1),
                    moe["gate"]["wg"].astype(cfg.dtype),
                    ex["w1"]["kernel"].astype(cfg.dtype),
                    ex["w2"]["kernel"].astype(cfg.dtype),
                    ex["w3"]["kernel"].astype(cfg.dtype),
                    k=cfg.num_experts_per_tok, dtype=cfg.dtype, valid=real)
        return x + y.reshape(S, Q, -1), kp, vp

    # non-scanned stack: the loop is unrolled (the layer count is static and
    # the weights differ per layer) over the one merged pool (paged_layer.py,
    # "The layout")
    k_pool, v_pool = merge_layers((k_pool, v_pool))
    for i in range(L):
        x, k_pool, v_pool = layer_step(x, k_pool, v_pool,
                                       params[f"layers_{i}"], i)
    k_pool, v_pool = split_layers((k_pool, v_pool), L)

    x = _rmsnorm(x, params["norm"]["scale"], cfg.rms_norm_eps)
    logits = last_token(x, q_len) @ params["lm_head"].astype(cfg.dtype).T
    return logits.astype(jnp.float32), {"kv": (k_pool, v_pool)}
