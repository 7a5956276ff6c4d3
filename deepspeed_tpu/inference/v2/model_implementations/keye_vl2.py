"""Ragged forward for the language model of Keye-VL-2.0
(``models/keye_vl2.py`` has the architecture): grouped-query attention that
reads only the cached tokens a learned indexer picks, over ONE paged group
whose page keeps the indexer's key beside K and V, and a sparse-expert
feed-forward part in every layer.

``cache["kv"]`` is ``(K, V, index)``: K and V ``[layers, NB+1, KV, bs, Dh]``
and the index keys ``[layers, NB+1, 1, bs, W]`` (a key's ``indexer_head_dim``
columns, zeros up to a whole lane tile), all three under ``tables["kv"]``. A
layer writes a token's three rows (``_scatter_kv``, ``_scatter_index``) and
reads through ``paged_layer.dsa_attention``: a dispatch none of whose rows
passes ``index_topk`` tokens takes the plain paged read; any other scores each
query against every cached index key of its row, finds the score of its
``index_topk``-th largest without sorting, and walks the row's pages under
the mask that threshold gives.

Positions. RoPE's tables come through M-RoPE's sections from a ``[3, S, Q]``
position array, three equal rows made from ``seen`` (text tokens; no image
tokens are served), which is plain RoPE; the indexer's q and key are rotated
by plain RoPE over all their columns.

The expert layer is ``moe_layer.moe_ffn`` (shared with Mixtral, Mellum2 and
Kanana-2) with the softmax router, told which experts this tree holds. The
layer is a jit of its own, so a program of any depth traces one function.

Scopes for the device trace: ``dsa_attn`` > ``dsa_qkv``, ``dsa_write``,
``dsa_index``, ``dsa_select``, ``dsa_read``, ``dsa_out``, beside ``moe_ffn``'s.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.llama import _rmsnorm
from deepspeed_tpu.inference.v2.model_implementations.moe_layer import (
    dispatch_report, moe_ffn)  # the first: this family's export
from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
    _pool_block_size, _scatter_index, _scatter_kv, dsa_attention, last_token,
    layer_rows, layer_trash, merge_layers, pool_pages_per_layer, real_slots,
    split_layers)
from deepspeed_tpu.models.keye_vl2 import mrope_tables
from deepspeed_tpu.models.llama import (
    rope_frequencies, rotary_apply, rotary_tables)


def _layernorm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), -1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


@functools.partial(jax.jit, static_argnums=(0,))
def _layer(cfg, lp, x, k_pool, v_pool, i_pool, tables, seen, q_len, real,
           rope, rope_idx, trash):
    """One decoder layer over x [S, Q, d] against the merged pools;
    ``tables`` and ``trash`` are this layer's. ``trash`` is a traced scalar
    so that every layer shares ONE traced and lowered function
    (``mellum2._layer``)."""
    S, Q, _ = x.shape
    H, KV, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    Hi, Di = cfg.indexer_num_heads, cfg.indexer_head_dim
    eps, dt = cfg.rms_norm_eps, cfg.dtype
    attn, idx = lp["self_attn"], lp["self_attn"]["indexer"]
    bs = k_pool.shape[2]
    h = _rmsnorm(x, lp["input_layernorm"]["scale"], eps)
    with jax.named_scope("dsa_attn"):
        with jax.named_scope("dsa_qkv"):
            proj = lambda name, heads: (
                h @ attn[name]["kernel"].astype(dt)).reshape(S, Q, heads, Dh)
            q = _rmsnorm(proj("q_proj", H), attn["q_norm"]["scale"], eps)
            k = _rmsnorm(proj("k_proj", KV), attn["k_norm"]["scale"], eps)
            v = proj("v_proj", KV)
            q, k = rotary_apply(q, *rope), rotary_apply(k, *rope)
            q_idx = (h @ idx["wq"]["kernel"].astype(dt)).reshape(S, Q, Hi, Di)
            k_idx = _layernorm(h @ idx["wk"]["kernel"].astype(dt),
                               idx["k_norm"]["scale"], idx["k_norm"]["bias"],
                               eps)
            q_idx = rotary_apply(q_idx, *rope_idx)
            k_idx = rotary_apply(k_idx[:, :, None, :], *rope_idx)[:, :, 0]
            w_idx = (h @ idx["weights_proj"]["kernel"].astype(dt)).astype(
                jnp.float32) * cfg.index_weight_scale
        with jax.named_scope("dsa_write"):
            k_pool, v_pool = _scatter_kv(k_pool, v_pool, k, v, tables, seen,
                                         q_len, bs, trash=trash)
            i_pool = _scatter_index(i_pool, k_idx, tables, seen, q_len, bs,
                                    trash)
        out = dsa_attention(q, q_idx, w_idx, k_pool, v_pool, i_pool, tables,
                            seen, bs, q_len, cfg.index_topk)
        with jax.named_scope("dsa_out"):
            x = x + out.reshape(S, Q, H * Dh) @ attn["o_proj"]["kernel"].astype(dt)

    moe = lp["moe"]
    h = _rmsnorm(x, lp["post_attention_layernorm"]["scale"], eps)
    y = moe_ffn(h.reshape(S * Q, -1), moe["router"]["kernel"].astype(dt),
                moe["w1"].astype(dt), moe["w2"].astype(dt), moe["w3"].astype(dt),
                k=cfg.num_experts_per_tok, dtype=dt, valid=real,
                experts_held=cfg.experts_held)
    return x + y.reshape(S, Q, -1), k_pool, v_pool, i_pool


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def ragged_forward(cfg, params, cache, tokens, q_len, seen, tables):
    """One ragged forward step -> (last-token logits [S, V], new cache); the
    contract is ``llama.ragged_forward``'s."""
    S, Q = tokens.shape
    pools = cache["kv"]                               # (K, V, index)
    layers, nb = pools[0].shape[0], pool_pages_per_layer(pools[0])
    assert _pool_block_size(pools[0]) == pools[2].shape[3]
    positions = seen[:, None] + jnp.arange(Q)[None, :]
    real = real_slots(q_len, Q).reshape(S * Q)
    # text tokens: the temporal, height and width rows are one position
    rope = mrope_tables(jnp.broadcast_to(positions, (3, S, Q)), cfg.head_dim,
                        cfg.rope_theta, cfg.mrope_section)
    rope_idx = rotary_tables(positions, *rope_frequencies(
        cfg.indexer_head_dim, cfg.rope_theta))

    # the stacked pools are merged pools on the loop's carry
    # (paged_layer.py, "The layout")
    pools = merge_layers(pools)
    x = params["embed_tokens"].astype(cfg.dtype)[tokens]
    for l in range(cfg.num_hidden_layers):
        x, *pools = _layer(cfg, params[f"layers_{l}"], x, *pools,
                           layer_rows(tables["kv"], l, nb), seen, q_len, real,
                           rope, rope_idx, jnp.int32(layer_trash(l, nb)))

    x = _rmsnorm(x, params["norm"]["scale"], cfg.rms_norm_eps)
    logits = last_token(x, q_len) @ params["lm_head"].astype(cfg.dtype).T
    return logits.astype(jnp.float32), {"kv": split_layers(tuple(pools), layers)}
