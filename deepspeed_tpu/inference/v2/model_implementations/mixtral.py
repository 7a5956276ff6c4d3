"""Ragged (paged-KV) Mixtral forward — MoE continuous batching.

Capability analog of the reference's Mixtral v2 implementation
(``inference/v2/model_implementations/mixtral`` + the ragged MoE kernel set
``kernels/ragged_ops/{moe_gather,moe_scatter,top_k_gating}`` and the grouped
``cutlass_ops/moe_gemm``). TPU design: GShard dense dispatch-combine —
top-k gating builds a [tokens, experts, capacity] dispatch tensor, one einsum
gathers tokens per expert (moe_scatter), a batched einsum over stacked expert
weights runs all expert FFNs as grouped MXU GEMMs (cutlass moe_gemm), and the
transpose einsum scatters weighted results back (moe_gather).

Operates on the training param tree of
``deepspeed_tpu.models.mixtral.MixtralForCausalLM`` (non-scanned
``layers_{i}`` naming; experts stacked [E, ...]).
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import rotary_embed
from deepspeed_tpu.inference.v2.model_implementations.llama import _rmsnorm
from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
    _paged_attention, _pool_block_size, _scatter_kv, last_token, layer_rows,
    layer_trash, merge_layers, pool_pages_per_layer, split_layers)
from deepspeed_tpu.ops.registry import pallas_interpret, takes_kernel


def _moe_ffn(x, gate_wg, w1, w2, w3, *, k, dtype, force_einsum=False):
    """Grouped-expert FFN over a flat token batch: the ragged grouped GEMM
    (ops/pallas/grouped_gemm.py: tokens sorted by expert, no capacity
    dimension) when Pallas is on and the dims tile, else the GShard dense
    dispatch-combine einsum below, which ``force_einsum`` pins as the tests'
    oracle.

    x: [T, D]; gate_wg: [D, E]; w1/w3: [E, D, F]; w2: [E, F, D].
    Returns [T, D].

    Inference uses LOSSLESS capacity C = T: HF Mixtral never drops tokens, and
    ragged batches carry identical padding rows that would otherwise route to
    one expert and steal bucket slots from real tokens. The training-side
    capacity_factor machinery (moe/sharded_moe.py) does not apply here.
    """
    T, D = x.shape
    E = gate_wg.shape[1]
    C = T

    # single routing implementation for both dispatch backends
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    top_vals, top_idx = gg.topk_router(x, gate_wg, k)    # [T, k]

    F = w1.shape[-1]
    if not force_einsum and takes_kernel(
            "moe_ffn_gmm", gg.is_supported(D, F),
            f"dims ({D}, {F}) not 128-tileable for gmm"):
        return gg.moe_ffn_gmm(x, top_vals, top_idx, w1, w2, w3, n_experts=E,
                              dtype=dtype, interpret=pallas_interpret())

    # top_k_gating: position of each (token, slot) inside its expert's bucket
    onehot = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)       # [T, k, E]
    flat = onehot.reshape(T * k, E)
    pos = jnp.cumsum(flat, axis=0) * flat - flat                 # [T*k, E]
    keep = (pos < C).astype(jnp.float32) * flat
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)
    # dispatch [T, k, E, C] -> moe_scatter matrix [T, E, C]
    disp = (keep[..., None] * pos_oh).reshape(T, k, E, C)
    dispatch = disp.sum(axis=1)
    combine = (disp * top_vals[..., None, None]).sum(axis=1)     # [T, E, C]

    xe = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32)).astype(dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w1)) * \
        jnp.einsum("ecd,edf->ecf", xe, w3)                        # grouped GEMMs
    out_e = jnp.einsum("ecf,efd->ecd", h, w2)                    # [E, C, D]
    return jnp.einsum("tec,ecd->td", combine,
                      out_e.astype(jnp.float32)).astype(dtype)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def ragged_forward(cfg, params, cache, tokens, q_len, seen, tables):
    """One ragged Mixtral forward step -> (last-token logits, new cache);
    the contract is ``llama.ragged_forward``'s."""
    (k_pool, v_pool), block_tables = cache["kv"], tables["kv"]
    S, Q = tokens.shape
    H, KV = cfg.num_attention_heads, cfg.num_key_value_heads
    Dh = cfg.hidden_size // H
    L = cfg.num_hidden_layers
    bs = _pool_block_size(k_pool)  # [L, NB, KV, bs, Dh] (pair when int8)
    nb = pool_pages_per_layer(k_pool)
    positions = seen[:, None] + jnp.arange(Q)[None, :]

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
        y = _moe_ffn(h.reshape(S * Q, -1),
                     moe["gate"]["wg"].astype(cfg.dtype),
                     ex["w1"]["kernel"].astype(cfg.dtype),
                     ex["w2"]["kernel"].astype(cfg.dtype),
                     ex["w3"]["kernel"].astype(cfg.dtype),
                     k=cfg.num_experts_per_tok,
                     dtype=cfg.dtype)
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
