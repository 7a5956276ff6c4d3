"""Ragged forward for LongCat-Flash-Chat (``models/longcat_flash.py`` has the
architecture): shortcut-connected double layers, each two latent attentions
(MLA with a low-rank query) and two dense FFNs in series and one expert layer
whose result joins the stream a sub-block later, over ONE paged group of one
leaf with TWO planes a layer, beside a counter group.

``cache["kv"]`` is ``(pages,)``: ``[2 * layers, NB+1, 1, bs, W]``; plane ``2 l
+ j`` keeps sub-block ``j`` of layer ``l``'s latent rows, written and read as
Kanana-2's are (``kanana2.latent_mla``: the read through ``paged_mla``,
absorbed or up-projected in the walk by the chunk's length, shared with that
family, so that family's cell guards it).
``cache["counters"]`` is the int32 accumulator of
``models.longcat_flash.COUNTER_FIELDS``: a dispatch adds its expert layers'
``moe_layer.COUNTS`` (what only the device knows: the rows that took a zero
expert, the rows that landed on an expert held here, the held experts hit) and
1 to ``dispatches``; nothing of a round fetches it (``engine.device_counters``).

The two scales. ``prepare_params`` folds ``s_q`` into ``q_a_layernorm``'s
float32 scale and ``s_kv`` into ``kv_a_layernorm``'s: ``q_b`` is linear, so
``q_b(norm(.) s_q) = s_q q_b(norm(.))``, and the published ``c = norm(latent)
s_kv`` is what the row keeps and ``W_UK`` / ``W_UV`` read; the product is taken
in float32 before the one rounding to the serving dtype, where the published
code rounds twice. The plain reference applies both as published.

The shortcut. ``m = MoE(h0)`` is computed where its input exists (after the
first attention) and added where the model adds it (after the second FFN);
between the two nothing reads it, so the order of the expert layer's
operations against the first dense FFN, the second attention and the second
FFN is the compiler's to choose (PERF.md section 5 says what the trace shows).

One jitted ``_layer`` is traced once for all layers (a traced plane index).
Device scopes: ``scmoe_layer/{mla_attn_0, mla_attn_1, dense_ffn_0,
dense_ffn_1, moe_ffn}``, inside an attention ``mla_q / mla_latent_write /
mla_read / mla_out``, inside the expert layer ``moe_layer``'s own.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations import moe_layer
from deepspeed_tpu.inference.v2.model_implementations.kanana2 import (
    latent_mla, latent_read_report)
from deepspeed_tpu.inference.v2.model_implementations.llama import _rmsnorm
from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
    _pool_block_size, last_token, layer_rows, layer_trash, merge_layers,
    pool_pages_per_layer, real_slots, split_layers)
from deepspeed_tpu.models.llama import rope_frequencies, rotary_tables
from deepspeed_tpu.models.longcat_flash import COUNTER_FIELDS

assert COUNTER_FIELDS == moe_layer.COUNTS + ("dispatches",)


def dispatch_report(cfg, real_tokens, chunk):
    """``moe_layer.dispatch_report``'s two mappings (``expert_rows`` counts the
    rows ROUTED, zero experts' among them; which were which is the counter
    group's to say); on the span also the zero experts, the router's whole
    width and the planes a latent page index spans (``latent_pages`` and the
    engine's ``live_pages`` count page indices: x ``kv_planes`` in pages);
    added besides, the latent read's form (``kanana2.latent_read_report``)."""
    adds, rides = moe_layer.dispatch_report(cfg, real_tokens, chunk)
    adds = dict(adds, **latent_read_report(cfg, real_tokens, chunk))
    return adds, dict(rides, zero_experts=cfg.zero_expert_num,
                      experts_routed_over=cfg.router_width,
                      kv_planes=2 * cfg.num_layers)


def prepare_params(cfg, params):
    """The tree as the forward reads it: each attention's ``kv_b_proj`` cut
    once into ``w_uk`` [r, H, nope] and ``w_uv`` [r, H, v] (as
    ``kanana2.prepare_params`` cuts it), ``s_q`` folded into
    ``q_a_layernorm``'s scale and ``s_kv`` into ``kv_a_layernorm``'s (module
    docstring). A tree of shapes gives a tree of shapes."""
    H, dn = cfg.num_attention_heads, cfg.qk_nope_head_dim

    def cut(kv_b, q_norm, kv_norm):
        kv_b = kv_b.reshape(kv_b.shape[0], H, -1)
        return (kv_b[..., :dn] + 0, kv_b[..., dn:] + 0,
                q_norm * jnp.float32(cfg.q_scale),
                kv_norm * jnp.float32(cfg.kv_scale))

    out = dict(params)
    for l in range(cfg.num_layers):
        layer = dict(params[f"layers_{l}"])
        for j in (0, 1):
            attn = dict(layer[f"self_attn_{j}"])
            args = (attn.pop("kv_b_proj")["kernel"],
                    attn["q_a_layernorm"]["scale"], attn["kv_a_layernorm"]["scale"])
            shapes = isinstance(args[0], jax.ShapeDtypeStruct)
            attn["w_uk"], attn["w_uv"], q_norm, kv_norm = \
                jax.eval_shape(cut, *args) if shapes else cut(*args)
            attn["q_a_layernorm"] = {"scale": q_norm}
            attn["kv_a_layernorm"] = {"scale": kv_norm}
            layer[f"self_attn_{j}"] = attn
        out[f"layers_{l}"] = layer
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _layer(cfg, lp, x, pool, tables, seen, q_len, real, rope, trash):
    """One double layer over x [S, Q, d] against the merged pool; ``tables``
    and ``trash`` are [2, ...]: sub-block ``j``'s plane. Traced scalars, so
    that every layer shares ONE traced and lowered function. Returns the
    stream, the pool and the expert layer's ``moe_layer.COUNTS``."""
    S, Q, d = x.shape
    H = cfg.num_attention_heads
    eps, dt = cfg.rms_norm_eps, cfg.dtype

    def attention(j, x, pool):
        attn = lp[f"self_attn_{j}"]

        def project_q(h):
            q_a = _rmsnorm(h @ attn["q_a_proj"]["kernel"].astype(dt),
                           attn["q_a_layernorm"]["scale"], eps)
            return (q_a @ attn["q_b_proj"]["kernel"].astype(dt)).reshape(
                S, Q, H, cfg.qk_head_dim)

        h = _rmsnorm(x, lp[f"input_layernorm_{j}"]["scale"], eps)
        return latent_mla(cfg, f"mla_attn_{j}", attn, project_q, h, x, pool,
                          tables[j], seen, q_len, rope, trash[j])

    def dense_ffn(j, h):
        with jax.named_scope(f"dense_ffn_{j}"):
            w = lambda name: lp[f"mlps_{j}"][name]["kernel"].astype(dt)
            return (jax.nn.silu(h @ w("gate_proj")) * (h @ w("up_proj"))) \
                @ w("down_proj")

    with jax.named_scope("scmoe_layer"):
        a0, pool = attention(0, x, pool)
        h0 = _rmsnorm(a0, lp["post_attention_layernorm_0"]["scale"], eps)
        moe = lp["moe"]
        m, counts = moe_layer.moe_ffn(
            h0.reshape(S * Q, d), moe["router"]["kernel"].astype(dt),
            moe["w1"].astype(dt), moe["w2"].astype(dt), moe["w3"].astype(dt),
            k=cfg.moe_topk, dtype=dt, valid=real, scoring="softmax_bias",
            score_bias=moe["router"]["bias"],
            routed_scale=cfg.routed_scaling_factor,
            experts_held=cfg.experts_held, zero_experts=cfg.zero_expert_num,
            counts=True)
        b0 = a0 + dense_ffn(0, h0)
        a1, pool = attention(1, b0, pool)
        h1 = _rmsnorm(a1, lp["post_attention_layernorm_1"]["scale"], eps)
        b1 = a1 + dense_ffn(1, h1) + m.reshape(S, Q, d)
    return b1, pool, counts


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def ragged_forward(cfg, params, cache, tokens, q_len, seen, tables):
    """One ragged forward step -> (last-token logits [S, V], new cache); the
    contract is ``llama.ragged_forward``'s. ``params``: ``prepare_params``'s."""
    S, Q = tokens.shape
    (pool,) = cache["kv"]
    planes, nb = pool.shape[0], pool_pages_per_layer(pool)
    assert planes == 2 * cfg.num_layers
    assert _pool_block_size(pool) == pool.shape[3]
    positions = seen[:, None] + jnp.arange(Q)[None, :]
    real = real_slots(q_len, Q).reshape(S * Q)
    rope = rotary_tables(positions, *rope_frequencies(
        cfg.qk_rope_head_dim, cfg.rope_theta))

    pool = merge_layers(pool)
    x = params["embed_tokens"].astype(cfg.dtype)[tokens]
    counts = jnp.zeros((len(moe_layer.COUNTS),), jnp.int32)
    for l in range(cfg.num_layers):
        both = (2 * l, 2 * l + 1)
        x, pool, n = _layer(
            cfg, params[f"layers_{l}"], x, pool,
            jnp.stack([layer_rows(tables["kv"], p, nb) for p in both]),
            seen, q_len, real, rope,
            jnp.asarray([layer_trash(p, nb) for p in both], jnp.int32))
        counts = counts + n

    x = _rmsnorm(x, params["norm"]["scale"], cfg.rms_norm_eps)
    logits = last_token(x, q_len) @ params["lm_head"].astype(cfg.dtype).T
    counters = cache["counters"] + jnp.concatenate([counts, jnp.ones((1,), jnp.int32)])
    return logits.astype(jnp.float32), {"kv": (split_layers(pool, planes),),
                                         "counters": counters}
