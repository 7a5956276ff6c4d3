"""Ragged forward for Kanana-2 (``models/kanana2.py`` has the architecture, a
DeepSeek-V3 tree): latent attention (MLA) over ONE paged group of one leaf,
one leading dense layer, then sparse-expert layers with a shared expert.

``cache["kv"]`` is ``(pages,)``: ``[layers, NB+1, 1, bs, W]``, a row a token
and layer holding the normalised latent ``c`` (``kv_lora_rank`` columns), the
rotated shared ``k_pe`` and zeros up to a whole lane tile
(``Kanana2Config.latent_row_width``). The row is written whole
(``paged_layer._scatter_latent``) and read whole, once, for the scores and for
the values.

The read has two forms, and ``paged_layer.up_projects_in_walk`` is the one
rule between them, by a dispatch's static shapes. ABSORBED, for a decode row,
a verify round and a short chunk: ``q_lat_i = q_nope_i W_UK_i^T`` puts a head's
query into the row's own columns, ``paged_mla`` (or its dense twin) scores it
against the row and sums the row's latent part, ``o_i = o_lat_i W_UV_i`` takes
that to the head's values, so a page is read once and never up-projected.
UP-PROJECTED IN THE WALK, for a chunk long enough that a head's queries share
a trip's up-projection (from 256 tokens: the rule's docstring has the count and
the chip's timings): q goes in as projected, a grid step of ``paged_mla`` holds
ONE head's queries with that head's ``W_UK_i`` and ``W_UV_i``, a trip's page
buffer becomes ``k_i = c W_UK_i`` and ``v_i = c W_UV_i`` in VMEM (bfloat16,
where the published forward rounds ``kv_b_proj``'s output) and the head's
values come out: a page still crosses HBM once and nothing up-projected ever
does. (The form that up-projects every live row to every head's ``k_nope`` and
``v`` in HBM FIRST was timed on the chip and lost at every chunk but 128
tokens: PERF.md, PR 37; it lost to its bytes, not to its count.) ``W_UK`` and
``W_UV`` are cut out of ``kv_b_proj`` once, when the engine is built
(``prepare_params``).

The expert layer is ``moe_layer.moe_ffn`` (shared with Mixtral and Mellum2)
told the scoring, the shared expert and which experts this tree holds. The
dense layer and the expert layer are each a jit of their own (``_layer``'s
static ``dense``), so that a program of any depth traces two functions.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.llama import _rmsnorm
from deepspeed_tpu.inference.v2.model_implementations import moe_layer
from deepspeed_tpu.inference.v2.model_implementations.moe_layer import moe_ffn
from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
    _latent_attention, _latent_attention_up, _pool_block_size, _scatter_latent,
    last_token, layer_rows, layer_trash, merge_layers, pool_pages_per_layer,
    real_slots, split_layers, up_projects_in_walk)
from deepspeed_tpu.models.llama import (
    rope_frequencies, rotary_apply, rotary_tables)


def cut_kv_b(cfg, kv_b):
    """``kv_b_proj``'s kernel [r, H * (nope + v)] as ``(w_uk [r, H, nope],
    w_uv [r, H, v])``, whole buffers of their own; shapes give shapes."""
    H, dn = cfg.num_attention_heads, cfg.qk_nope_head_dim

    def cut(kv_b):
        kv_b = kv_b.reshape(kv_b.shape[0], H, -1)
        return kv_b[..., :dn] + 0, kv_b[..., dn:] + 0

    return jax.eval_shape(cut, kv_b) if isinstance(kv_b, jax.ShapeDtypeStruct) \
        else cut(kv_b)


def prepare_params(cfg, params):
    """The tree as the forward reads it: each layer's ``kv_b_proj`` [r, H *
    (nope + v)] cut once into ``w_uk`` [r, H, nope] and ``w_uv`` [r, H, v],
    whole buffers of their own, instead of two strided slices a dispatch. A
    tree of shapes (a compile for a described chip) gives a tree of shapes."""
    out = dict(params)
    for l in range(cfg.num_hidden_layers):
        layer = dict(params[f"layers_{l}"])
        attn = dict(layer["self_attn"])
        attn["w_uk"], attn["w_uv"] = cut_kv_b(cfg, attn.pop("kv_b_proj")["kernel"])
        layer["self_attn"] = attn
        out[f"layers_{l}"] = layer
    return out


def up_projects(cfg, chunk):
    """``paged_layer.up_projects_in_walk`` at ``cfg``'s widths for a dispatch
    of ``chunk`` token slots a row."""
    return up_projects_in_walk(chunk, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                               cfg.v_head_dim)


def latent_read_report(cfg, real_tokens, chunk):
    """What a dispatch reports of the latent read's form, added to the
    round's counts: its real tokens under ``latent_up_tokens`` where the
    walk up-projects (``up_projects``), else under
    ``latent_absorbed_tokens``."""
    up = up_projects(cfg, chunk)
    return {"latent_up_tokens": real_tokens if up else 0,
            "latent_absorbed_tokens": 0 if up else real_tokens}


def dispatch_report(cfg, real_tokens, chunk):
    """``moe_layer.dispatch_report``'s two mappings, and added to the first
    the latent read's (``latent_read_report``)."""
    adds, rides = moe_layer.dispatch_report(cfg, real_tokens, chunk)
    return dict(adds, **latent_read_report(cfg, real_tokens, chunk)), rides


def latent_mla(cfg, scope, attn, project_q, h, x, pool, tables, seen, q_len,
               rope, trash):
    """``x + Attn(h)`` of one latent attention over the merged pool, in the
    form ``up_projects`` picks for the dispatch's chunk (module docstring),
    and the pool with the new rows written: what this family, LongCat-Flash
    (``longcat_flash.py``: a low-rank q, two of them a layer) and Kimi-Linear
    share. ``attn``: ``kv_a_proj``, ``kv_a_layernorm``, ``w_uk``, ``w_uv``,
    ``o_proj``; ``project_q(h)`` -> [S, Q, H, nope + rope], traced under
    ``mla_q``. ``rope``: the rotary tables of the dispatch's positions, or
    None for a model whose latent attention has no positions
    (``kimi_linear.py``: the "rope" columns are then 64 more shared key
    columns, as projected). The device scopes are ``<scope>/{mla_q,
    mla_latent_write, mla_read, mla_out}``."""
    S, Q, _ = x.shape
    H, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    W, bs = pool.shape[-1], pool.shape[2]
    eps, dt = cfg.rms_norm_eps, cfg.dtype
    w_uk, w_uv = attn["w_uk"].astype(dt), attn["w_uv"].astype(dt)
    rotate = (lambda t: t) if rope is None else (lambda t: rotary_apply(t, *rope))
    up = up_projects(cfg, Q)
    with jax.named_scope(scope):
        with jax.named_scope("mla_q"):
            q = project_q(h)
            if up:
                q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:])], -1)
            else:
                q_lat = jnp.einsum("sqhd,chd->sqhc", q[..., :dn], w_uk)
                q_row = jnp.concatenate(
                    [q_lat, rotate(q[..., dn:]),
                     jnp.zeros((S, Q, H, W - r - dr), dt)], -1)
        with jax.named_scope("mla_latent_write"):
            ckv = h @ attn["kv_a_proj"]["kernel"].astype(dt)      # [S, Q, r + dr]
            c = _rmsnorm(ckv[..., :r], attn["kv_a_layernorm"]["scale"], eps)
            k_pe = rotate(ckv[..., None, r:])[..., 0, :]
            row = jnp.concatenate(
                [c, k_pe, jnp.zeros((S, Q, W - r - dr), dt)], -1)
            pool = _scatter_latent(pool, row, tables, seen, q_len, bs, trash)
        with jax.named_scope("mla_read"):
            if up:
                o = _latent_attention_up(q, w_uk, w_uv, pool, tables, seen,
                                         bs, q_len, cfg.softmax_scale)
            else:
                o_lat = _latent_attention(q_row, pool, tables, seen, bs,
                                          q_len, r, cfg.softmax_scale)
        with jax.named_scope("mla_out"):
            if not up:
                o = jnp.einsum("sqhc,chd->sqhd", o_lat, w_uv)
            x = x + o.reshape(S, Q, H * dv) @ attn["o_proj"]["kernel"].astype(dt)
    return x, pool


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(cfg, dense, lp, x, pool, tables, seen, q_len, real, rope, trash):
    """One decoder layer over x [S, Q, d] against the merged pool of latent
    pages; ``tables`` and ``trash`` are this layer's. ``trash`` is a traced
    scalar so that the layers of one kind share ONE traced and lowered
    function (``mellum2._layer``)."""
    S, Q, _ = x.shape
    H = cfg.num_attention_heads
    eps, dt = cfg.rms_norm_eps, cfg.dtype
    attn = lp["self_attn"]
    h = _rmsnorm(x, lp["input_layernorm"]["scale"], eps)
    x, pool = latent_mla(
        cfg, "mla_attn", attn,
        lambda h: (h @ attn["q_proj"]["kernel"].astype(dt)).reshape(
            S, Q, H, cfg.qk_head_dim),
        h, x, pool, tables, seen, q_len, rope, trash)

    h = _rmsnorm(x, lp["post_attention_layernorm"]["scale"], eps)
    if dense:
        mlp = lp["mlp"]
        w = lambda name: mlp[name]["kernel"].astype(dt)
        return x + (jax.nn.silu(h @ w("gate_proj")) * (h @ w("up_proj"))) \
            @ w("down_proj"), pool
    moe = lp["moe"]
    y = moe_ffn(h.reshape(S * Q, -1), moe["router"]["kernel"].astype(dt),
                moe["w1"].astype(dt), moe["w2"].astype(dt), moe["w3"].astype(dt),
                k=cfg.num_experts_per_tok, dtype=dt, valid=real,
                scoring="sigmoid", score_bias=moe["router"]["bias"],
                routed_scale=cfg.routed_scaling_factor,
                shared=tuple(moe["shared"][n].astype(dt) for n in ("w1", "w2", "w3")),
                experts_held=cfg.experts_held)
    return x + y.reshape(S, Q, -1), pool


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def ragged_forward(cfg, params, cache, tokens, q_len, seen, tables):
    """One ragged forward step -> (last-token logits [S, V], new cache); the
    contract is ``llama.ragged_forward``'s. ``params``: ``prepare_params``'s."""
    S, Q = tokens.shape
    (pool,) = cache["kv"]
    layers, nb = pool.shape[0], pool_pages_per_layer(pool)
    assert _pool_block_size(pool) == pool.shape[3]
    positions = seen[:, None] + jnp.arange(Q)[None, :]
    real = real_slots(q_len, Q).reshape(S * Q)
    rope = rotary_tables(positions, *rope_frequencies(
        cfg.qk_rope_head_dim, cfg.rope_theta))

    # the stacked pool is one merged pool on the loop's carry
    # (paged_layer.py, "The layout")
    pool = merge_layers(pool)
    x = params["embed_tokens"].astype(cfg.dtype)[tokens]
    for l in range(cfg.num_hidden_layers):
        x, pool = _layer(cfg, cfg.is_dense(l), params[f"layers_{l}"], x, pool,
                         layer_rows(tables["kv"], l, nb), seen, q_len, real,
                         rope, jnp.int32(layer_trash(l, nb)))

    x = _rmsnorm(x, params["norm"]["scale"], cfg.rms_norm_eps)
    logits = last_token(x, q_len) @ params["lm_head"].astype(cfg.dtype).T
    return logits.astype(jnp.float32), {"kv": (split_layers(pool, layers),)}
