"""Ragged (paged-KV) Llama forward (mirrors reference
``inference/v2/model_implementations/llama_v2`` + the ragged kernel set
``inference/v2/kernels/ragged_ops``: linear_blocked_kv_rotary -> scatter into
paged cache, blocked_flash -> paged attention, logits_gather -> last-token
logits).

Operates directly on the training param pytree of
``deepspeed_tpu.models.llama.LlamaForCausalLM`` with ``scan_layers=True`` (the
stacked-layer layout is exactly what ``lax.scan`` wants), so a trained
checkpoint serves with zero conversion. All shapes are static: S sequence
slots x Q new-token budget, MB-wide block tables, masked padding, and a trash
block absorbing padded-slot KV writes.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import rotary_embed
from deepspeed_tpu.ops.flash_attention import NEG_INF
from deepspeed_tpu.inference.v2.modules.module_registry import module_preference


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (norm * scale).astype(x.dtype)


def _pool_parts(pool):
    """A per-layer KV pool is either an array (fp) or an ``(int8, scale)``
    pair (``state_manager.kv_dtype="int8"``) — split without probing."""
    return pool if isinstance(pool, tuple) else (pool, None)


def _pool_block_size(pool):
    """Block size from a possibly-quantized STACKED pool [L, NB, KV, bs, Dh]."""
    return _pool_parts(pool)[0].shape[3]


def _pool_layer(pool, i):
    """Index layer ``i`` out of a stacked pool (pairs index leaf-wise)."""
    d, s = _pool_parts(pool)
    return d[i] if s is None else (d[i], s[i])


def _pool_set_layer(pool, i, new):
    """Write layer ``i`` back into a stacked pool (pairs update leaf-wise)."""
    d, s = _pool_parts(pool)
    nd, ns = _pool_parts(new)
    if s is None:
        return d.at[i].set(nd)
    return (d.at[i].set(nd), s.at[i].set(ns))


def _quantize_kv_rows(x):
    """[..., Dh] fp -> (int8 [..., Dh], fp32 scale [...]) — the per-row
    symmetric wire format of ``quant_collective`` applied per token row.
    Uses the module's jnp twin (the Pallas producer kernel needs
    group_size >= 256; KV rows are Dh wide), fused into the jitted forward."""
    from deepspeed_tpu.ops.pallas.quant_collective import _quantize_rows_ref
    q, scale = _quantize_rows_ref(
        x.astype(jnp.float32).reshape(-1, x.shape[-1]), 8)
    return q.reshape(x.shape), scale.reshape(x.shape[:-1])


def _scatter_kv(k_pool, v_pool, k, v, block_tables, seen, q_len, block_size,
                trash=None):
    """Write [S, Q, KV, Dh] new KVs into the [NB, KV, bs, Dh] pool via block
    tables.

    Padded token slots are routed to the ``trash`` block (default: the last
    block of the pool).
    Analog of the reference's linear_blocked_kv_copy kernel. Quantized pools
    (``(int8, scale)`` pairs) quantize on-write: each token's row quantizes
    per (token, kv head) over Dh, and the fp32 scale scatters into the side
    pool [NB, KV, 1, bs] under the same block/slot indices.
    """
    k_pool, k_scale = _pool_parts(k_pool)
    v_pool, v_scale = _pool_parts(v_pool)
    S, Q = k.shape[:2]
    if trash is None:
        trash = k_pool.shape[0] - 1
    pos = seen[:, None] + jnp.arange(Q)[None, :]              # [S, Q]
    valid = jnp.arange(Q)[None, :] < q_len[:, None]
    blk = jnp.take_along_axis(block_tables, pos // block_size, axis=1,
                              mode="clip")
    # every leading dim is indexed — (block, head, slot) per [Dh] row, values
    # [S*Q, KV, Dh] — so the scatter writes whole rows in the pool's own
    # layout. Leaving the head dim a slice between two indexed dims made the
    # chip's compiler re-lay the WHOLE pool out around the scatter.
    bi = jnp.where(valid, blk, trash).reshape(-1, 1)          # [S*Q, 1]
    si = jnp.where(valid, pos % block_size, 0).reshape(-1, 1)
    hi = jnp.arange(k.shape[2])[None, :]                      # [1, KV]
    if k_scale is not None:
        k, ks = _quantize_kv_rows(k)          # int8 [S,Q,KV,Dh], f32 [S,Q,KV]
        v, vs = _quantize_kv_rows(v)
        k_scale = k_scale.at[bi, hi, 0, si].set(ks.reshape(S * Q, -1))
        v_scale = v_scale.at[bi, hi, 0, si].set(vs.reshape(S * Q, -1))
    k_pool = k_pool.at[bi, hi, si].set(
        k.reshape(S * Q, *k.shape[2:]).astype(k_pool.dtype))
    v_pool = v_pool.at[bi, hi, si].set(
        v.reshape(S * Q, *v.shape[2:]).astype(v_pool.dtype))
    if k_scale is not None:
        return (k_pool, k_scale), (v_pool, v_scale)
    return k_pool, v_pool


def _paged_attention(q, k_pool, v_pool, block_tables, seen, block_size,
                     q_len=None, window=None, prefer=None, softmax_scale=None):
    """Grouped-query attention over per-sequence paged KV: the Pallas
    blocked-flash kernel (ops/pallas/paged_attention.py — O(seen) HBM reads)
    when the heuristics layer selects it, dense gather fallback elsewhere.
    ``window``: Mistral-style sliding window. ``prefer``: config pin from
    the modules registry. ``softmax_scale``: None is ``1/sqrt(Dh)``.
    q: [S,Q,H,Dh] -> [S,Q,H,Dh]."""
    kp, ks = _pool_parts(k_pool)
    if q_len is not None:
        from deepspeed_tpu.inference.v2.modules.heuristics import (
            instantiate_attention)
        impl, fn = instantiate_attention(q.shape, kp.shape,
                                         preference=prefer)
        if impl == "pallas_paged":
            vp, vs = _pool_parts(v_pool)
            if softmax_scale is None:
                return fn(q, kp, vp, block_tables, seen, q_len,
                          k_scale=ks, v_scale=vs, window=window)
            return fn(q, kp, vp, block_tables, seen, q_len, k_scale=ks,
                      v_scale=vs, window=window, softmax_scale=softmax_scale)
    return _paged_attention_dense(q, k_pool, v_pool, block_tables, seen,
                                  block_size, window=window,
                                  softmax_scale=softmax_scale)


def _paged_attention_dense(q, k_pool, v_pool, block_tables, seen, block_size,
                           window=None, softmax_scale=None):
    """Pure-XLA reference path (gathers the full table; numerics twin of the
    Pallas kernel — including the fused-dequant int8 path, which it
    reproduces as gather-then-dequantize with broadcast scales)."""
    k_pool, k_scale = _pool_parts(k_pool)
    v_pool, v_scale = _pool_parts(v_pool)
    S, Q, H, Dh = q.shape
    KV = k_pool.shape[1]
    rep = H // KV
    scale = 1.0 / (Dh ** 0.5) if softmax_scale is None else softmax_scale
    MB = block_tables.shape[1]

    def one_seq(q_s, bt_s, seen_s):
        keys, vals = k_pool[bt_s], v_pool[bt_s]       # [MB, KV, bs, Dh]
        if k_scale is not None:
            # scale rows [MB, KV, 1, bs] -> per-token column [MB, KV, bs, 1]
            keys = keys.astype(jnp.float32) * \
                jnp.swapaxes(k_scale[bt_s], -1, -2)
            vals = vals.astype(jnp.float32) * \
                jnp.swapaxes(v_scale[bt_s], -1, -2)
        # [MB, KV, bs, Dh] -> token-major [MB*bs, KV, Dh]
        keys = (keys.transpose(0, 2, 1, 3)
                .reshape(MB * block_size, KV, Dh).astype(q_s.dtype))
        vals = (vals.transpose(0, 2, 1, 3)
                .reshape(MB * block_size, KV, Dh).astype(q_s.dtype))
        qg = q_s.reshape(Q, KV, rep, Dh)
        logits = jnp.einsum("qkrd,skd->krqs", qg, keys).astype(jnp.float32) * scale
        key_pos = jnp.arange(MB * block_size)[None, :]
        qry_pos = (seen_s + jnp.arange(Q))[:, None]
        visible = key_pos <= qry_pos
        if window:
            visible = visible & (key_pos > qry_pos - window)
        logits = jnp.where(visible, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(q_s.dtype)
        return jnp.einsum("krqs,skd->qkrd", probs, vals).reshape(Q, H, Dh)

    return jax.vmap(one_seq)(q, block_tables, seen)


def _ragged_trunk(cfg, params, k_pool, v_pool, tokens, q_len, seen,
                  block_tables):
    """Shared embedding -> scanned-layers -> final-norm trunk.

    Both ``ragged_forward`` (plain: last-token logits) and
    ``ragged_forward_verify`` (speculative: last-``k_max``-token logits)
    close over this SAME function, so both lower through the identical
    layer ``scan`` — and in particular the identical paged-attention kernel
    call. Lint rule JX005 pins that property on the jaxprs; do not fork the
    trunk per caller. Returns (normed hidden [S, Q, D], k_pool, v_pool).
    """
    S, Q = tokens.shape
    H, KV, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    bs = _pool_block_size(k_pool)  # [L, NB, KV, bs, Dh] (pair when int8)
    positions = seen[:, None] + jnp.arange(Q)[None, :]

    x = params["embed_tokens"].astype(cfg.dtype)[tokens]
    layers = params["layers"]["block"]

    # The stacked pools [L, NB, ...] are ONE pool of L*NB pages to the layer
    # loop (a free reshape): layer ``i`` owns pages [i*NB, (i+1)*NB), reached
    # by offsetting the block tables. The pools ride the scan CARRY, the
    # scatter updates them in place and the paged kernel reads pages through
    # the tables — no layer's pool is ever sliced out or written back. As
    # scan inputs and outputs the pools were two buffers each (the whole KV
    # pool again as scratch) and every round moved them through HBM ~13x.
    L = cfg.num_hidden_layers
    nb = _pool_parts(k_pool)[0].shape[1]          # per layer, trash included
    merge = lambda a: a.reshape((L * nb,) + a.shape[2:])
    k_pool, v_pool = jax.tree.map(merge, (k_pool, v_pool))

    def layer_step(carry, xs):
        x, kp, vp = carry
        lp, i = xs
        layer_tables = block_tables + i * nb
        attn = lp["self_attn"]
        h = _rmsnorm(x, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)

        def proj(p):
            y = h @ p["kernel"].astype(cfg.dtype)
            if "bias" in p:  # qwen2-family qkv bias
                y = y + p["bias"].astype(cfg.dtype)
            return y

        q = proj(attn["q_proj"]).reshape(S, Q, H, Dh)
        k = proj(attn["k_proj"]).reshape(S, Q, KV, Dh)
        v = proj(attn["v_proj"]).reshape(S, Q, KV, Dh)
        q = rotary_embed(q, positions, cfg.rope_theta)
        k = rotary_embed(k, positions, cfg.rope_theta)
        kp, vp = _scatter_kv(kp, vp, k, v, layer_tables, seen, q_len, bs,
                             trash=i * nb + nb - 1)
        out = _paged_attention(q, kp, vp, layer_tables, seen, bs, q_len=q_len,
                               window=cfg.sliding_window,
                               prefer=module_preference(cfg, "attention"))
        o = out.reshape(S, Q, H * Dh) @ attn["o_proj"]["kernel"].astype(cfg.dtype)
        if "bias" in attn["o_proj"]:   # InternLM-family o bias
            o = o + attn["o_proj"]["bias"].astype(cfg.dtype)
        x = x + o
        mlp = lp["mlp"]
        h = _rmsnorm(x, lp["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
        gate = jax.nn.silu(h @ mlp["gate_proj"]["kernel"].astype(cfg.dtype))
        up = h @ mlp["up_proj"]["kernel"].astype(cfg.dtype)
        x = x + (gate * up) @ mlp["down_proj"]["kernel"].astype(cfg.dtype)
        return (x, kp, vp), None

    (x, k_pool, v_pool), _ = jax.lax.scan(
        layer_step, (x, k_pool, v_pool), (layers, jnp.arange(L)))
    split = lambda a: a.reshape((L, nb) + a.shape[1:])
    k_pool, v_pool = jax.tree.map(split, (k_pool, v_pool))

    x = _rmsnorm(x, params["norm"]["scale"], cfg.rms_norm_eps)
    return x, k_pool, v_pool


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def ragged_forward(cfg, params, cache, tokens, q_len, seen, tables):
    """One ragged forward step. ``cache`` and ``tables`` are the state
    manager's pytrees (``ragged/cache_groups.py``); this family has the one
    paged group: ``cache["kv"]`` the (K, V) pools, ``tables["kv"]`` the block
    tables.

    Returns (last-token logits [S, V], new cache).
    """
    k_pool, v_pool = cache["kv"]
    x, k_pool, v_pool = _ragged_trunk(cfg, params, k_pool, v_pool, tokens,
                                      q_len, seen, tables["kv"])
    # logits_gather analog: only the last real token of each sequence
    last = jnp.take_along_axis(
        x, jnp.maximum(q_len - 1, 0)[:, None, None], axis=1)[:, 0]
    logits = last @ params["lm_head"].astype(cfg.dtype).T
    return logits.astype(jnp.float32), {"kv": (k_pool, v_pool)}


@functools.partial(jax.jit, static_argnums=(0, 7), donate_argnums=(2,))
def ragged_forward_verify(cfg, params, cache, tokens, q_len, seen, tables,
                          k_max):
    """One ragged forward returning per-row logits for the last ``k_max``
    chunk positions instead of just the last token — the verify half of
    draft-then-verify decode. The trunk (embed -> layer scan -> norm) is
    byte-identical to ``ragged_forward``'s, so a verify round runs the same
    ragged paged-attention kernel as plain prefill (JX005-pinned); only the
    logits gather widens.

    Columns are LAST-aligned: for row ``s`` with chunk length ``q_len[s]``,
    output column ``c`` holds the logits after chunk position
    ``q_len[s] - k_max + c`` (clamped into the chunk) — column ``k_max-1``
    is always the row's ordinary last-token logits. A speculating row's
    chunk (length ``m <= k_max``) therefore occupies the last ``m``
    columns, while prefill/plain rows sharing the batch (chunks of any
    length) read their last-token logits at column ``k_max-1`` exactly as
    they would read ``ragged_forward``'s output.

    Returns (logits [S, k_max, V] fp32, new cache).
    """
    k_pool, v_pool = cache["kv"]
    x, k_pool, v_pool = _ragged_trunk(cfg, params, k_pool, v_pool, tokens,
                                      q_len, seen, tables["kv"])
    # per-column gather + matmul, each fenced to the exact [S, D] @ [D, V]
    # shape the plain forward lowers: XLA would otherwise merge the columns
    # into one batched dot whose different tiling perturbs low-order bits —
    # and the bit-exactness oracle (greedy speculative == plain stream,
    # test-pinned) tolerates zero drift. k_max is small (drafts + 1), so the
    # unrolled columns cost less than one extra layer.
    W = params["lm_head"].astype(cfg.dtype).T
    cap = jnp.maximum(q_len - 1, 0)
    cols = []
    for c in range(k_max):
        idx = jnp.clip(q_len - k_max + c, 0, cap)                 # [S]
        g = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        g = jax.lax.optimization_barrier(g)
        cols.append((g @ W).astype(jnp.float32))
    return jnp.stack(cols, axis=1), {"kv": (k_pool, v_pool)}
