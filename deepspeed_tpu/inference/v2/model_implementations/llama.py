"""Ragged (paged-KV) Llama forward (mirrors reference
``inference/v2/model_implementations/llama_v2`` + the ragged kernel set
``inference/v2/kernels/ragged_ops``: linear_blocked_kv_rotary -> scatter into
paged cache, blocked_flash -> paged attention, logits_gather -> last-token
logits).

Operates on the param pytree of
``deepspeed_tpu.models.llama.LlamaForCausalLM`` with ``scan_layers=True`` (the
stacked-layer layout is exactly what ``lax.scan`` wants). The forward takes
the training tree as it is, and the tree ``prepare_params`` makes of it once,
when the engine is built: the same values with q, k and v's kernels stored
``[L, heads, head_dim, hidden]``, as their product reads them. ``proj`` tells
the two apart by the kernel's rank. Why a stored layout: from ``[L, hidden,
heads * head_dim]`` the chip's compiler cuts each layer's kernel out of the
stack into VMEM, transposes it there and only then multiplies, three
operations where ``o_proj`` and the MLP are one fused product that reads its
kernel from HBM once (``tests/test_chip_compile.py`` holds the compiled
programs to that; ``rotary_embed`` below is written without a strided pair
split for the same reason). All shapes are static: S sequence slots x Q
new-token budget, MB-wide block tables, masked padding, and a trash block
absorbing padded-slot KV writes.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
    _paged_attention, _pool_block_size, _scatter_kv, last_token, layer_rows,
    layer_trash, merge_layers, pool_pages_per_layer, split_layers, token_at)


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (norm * scale).astype(x.dtype)


def rotary_embed(x, positions, theta):
    """``models.llama.rotary_embed`` (adjacent column pairs) value for value,
    without its strided pair split: ``x cos + partner sin``, a column's
    partner its pair's other column, signed, by two lane rolls and a select.
    x: [S, Q, heads, Dh]. Why a form of its own: ``x[..., ::2]`` makes the
    chip's compiler lay a prompt chunk's q and k tokens-minor, and their
    products then cut the kernel into VMEM first and read it through a
    transposing copy (45 + 147 us a layer for q at ``[1, 512]`` where one
    fused product does); from this form every dispatch program reads q, k
    and v's kernels where they lie (``tests/test_chip_compile.py``)."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    angles = positions[..., None].astype(jnp.float32) * freqs      # [S, Q, dh/2]
    cos = jnp.repeat(jnp.cos(angles), 2, axis=-1)[:, :, None, :]
    sin = jnp.repeat(jnp.sin(angles), 2, axis=-1)[:, :, None, :]
    partner = jnp.where(jnp.arange(dh) % 2 == 0,
                        -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1))
    return (x * cos + partner * sin).astype(x.dtype)


def prepare_params(cfg, params):
    """The tree as the forward reads it: ``self_attn/{q,k,v}_proj/kernel``
    re-laid from ``[L, hidden, heads * head_dim]`` to ``[L, heads, head_dim,
    hidden]`` (module docstring); a permutation of the stored values, biases
    and every other leaf as they are. The caller's tree is left whole. A
    kernel that already has four axes stays, so a prepared tree comes back
    as it is; a tree of shapes gives a tree of shapes."""
    @jax.jit                      # k's and v's kernels share one program
    def relay(kernel):
        L, D, out = kernel.shape
        return kernel.reshape(L, D, out // cfg.head_dim,
                              cfg.head_dim).transpose(0, 2, 3, 1)

    block = params["layers"]["block"]
    attn = dict(block["self_attn"])
    for name in ("q_proj", "k_proj", "v_proj"):
        kernel = attn[name]["kernel"]
        if len(kernel.shape) == 3:
            shapes = isinstance(kernel, jax.ShapeDtypeStruct)
            attn[name] = dict(attn[name], kernel=jax.eval_shape(relay, kernel)
                              if shapes else relay(kernel))
    return dict(params, layers=dict(params["layers"], block=dict(
        block, self_attn=attn)))


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
    H, Dh = cfg.num_attention_heads, cfg.head_dim
    bs = _pool_block_size(k_pool)  # [L, NB, KV, bs, Dh] (pair when int8)
    positions = seen[:, None] + jnp.arange(Q)[None, :]

    x = params["embed_tokens"].astype(cfg.dtype)[tokens]
    layers = params["layers"]["block"]

    # one merged pool on the scan carry (paged_layer.py, "The layout")
    L = cfg.num_hidden_layers
    nb = pool_pages_per_layer(k_pool)
    k_pool, v_pool = merge_layers((k_pool, v_pool))

    def layer_step(carry, xs):
        x, kp, vp = carry
        lp, i = xs
        layer_tables = layer_rows(block_tables, i, nb)
        attn = lp["self_attn"]
        h = _rmsnorm(x, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)

        def proj(p):              # -> [S, Q, heads, Dh]
            kernel = p["kernel"].astype(cfg.dtype)
            if kernel.ndim == 3:  # prepare_params' [heads, head_dim, hidden]
                y = jnp.einsum("sqd,hkd->sqhk", h, kernel)
            else:                 # the training tree's [hidden, heads * head_dim]
                y = (h @ kernel).reshape(S, Q, -1, Dh)
            if "bias" in p:  # qwen2-family qkv bias
                y = y + p["bias"].astype(cfg.dtype).reshape(-1, Dh)
            return y

        q, k, v = (proj(attn[n]) for n in ("q_proj", "k_proj", "v_proj"))
        q = rotary_embed(q, positions, cfg.rope_theta)
        k = rotary_embed(k, positions, cfg.rope_theta)
        kp, vp = _scatter_kv(kp, vp, k, v, layer_tables, seen, q_len, bs,
                             trash=layer_trash(i, nb))
        out = _paged_attention(q, kp, vp, layer_tables, seen, bs, q_len,
                               window=cfg.sliding_window)
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
    k_pool, v_pool = split_layers((k_pool, v_pool), L)

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
    logits = last_token(x, q_len) @ params["lm_head"].astype(cfg.dtype).T
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
        g = jax.lax.optimization_barrier(token_at(x, idx))
        cols.append((g @ W).astype(jnp.float32))
    return jnp.stack(cols, axis=1), {"kv": (k_pool, v_pool)}
