"""Ragged forward for Phi-4-mini-flash-reasoning (``models/phi4flash.py`` has
the architecture and the layouts).

Three kinds of state ride the ``cache`` pytree, each reached through its entry
of ``tables`` (``ragged/cache_groups.py``; the state manager builds both):

* ``cache["kv"]``: the full-attention layer's pages ``[1, NB+1, pairs, bs,
  2*Dh]``, written by that layer and read by every cross layer through
  ``tables["kv"]``.
* ``cache["window"]``: the window layers' pages ``[periods, NBw+1, ...]``.
  ``tables["window"]`` holds only a sequence's LIVE pages, the first of them
  starting at token ``tables["window_base"]``: the model has no positional
  encoding and both masks depend on differences of positions only, so these
  layers run on ``seen - base`` and never learn that earlier pages are gone.
* ``cache["state"]``: ``conv`` ``[M, slots+1, d_conv-1, Di]`` and ``ssm``
  ``[M, slots+1, N, Di]`` (float32), row ``tables["state"]`` of each a
  sequence's slot (the last row absorbs padded rows). A row whose ``seen`` is
  0 is a sequence's first chunk and starts from zero state whatever its slot
  held; positions ``>= q_len`` advance neither leaf.

Differential attention goes through the paged kernel that exists, in ONE call
a layer: a page row is a pair of K (or V) heads side by side, 128 wide; each q
head of 64 is zero-padded to 128 on its own half, so q1.K1 and q2.K2 are the
same dot products, every head of a pair reads the pair's [V1, V2], and the
kernel sees ``H`` query heads over ``KV/2`` page rows (rep 4) at scale
``1/sqrt(64)``. The extra multiplications are by zeros in VMEM; the bytes read
are those the layer needs.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
    _paged_attention, _scatter_kv, last_token, layer_rows, layer_trash,
    merge_layers, split_layers)
from deepspeed_tpu.inference.v2.model_implementations.parallel_block import (
    _layernorm)
from deepspeed_tpu.ops.registry import pallas_enabled, pallas_interpret


def _scan_fn(d_inner, d_state):
    from deepspeed_tpu.ops.pallas import selective_scan as ss
    if pallas_enabled() and ss.is_supported(d_inner, d_state):
        return functools.partial(ss.selective_scan,
                                 interpret=pallas_interpret())
    return ss.selective_scan_ref


def _ln(x, p, eps):
    return _layernorm(x, p["scale"], p["bias"], eps)


def _mlp(cfg, x, p):
    h = _ln(x, p["ln2"], cfg.layer_norm_eps)
    gp = h @ p["mlp"]["gate_up_proj"]["kernel"].astype(cfg.dtype)
    g, u = jnp.split(gp, 2, axis=-1)
    return x + (jax.nn.silu(g) * u) @ p["mlp"]["down_proj"]["kernel"].astype(cfg.dtype)


def _mamba(cfg, x, p, conv, ssm, slots, q_len, fresh):
    """One Mamba-1 layer over [S, Q, d]. ``conv``/``ssm`` are the merged slot
    pools ``[M * (slots+1), ...]``, ``slots`` the rows' indices into them.
    Returns (x, conv, ssm, y) with ``y`` the scan's output before the gate."""
    S, Q, _ = x.shape
    m = p["mixer"]
    N, R = cfg.mamba_d_state, cfg.mamba_dt_rank
    f32 = jnp.float32
    u = _ln(x, p["ln1"], cfg.layer_norm_eps)
    a, z = jnp.split(u @ m["in_proj"]["kernel"].astype(cfg.dtype), 2, axis=-1)
    keep = jnp.logical_not(fresh)[:, None, None]
    tail = jnp.where(keep, conv[slots], 0).astype(a.dtype)    # [S, K-1, Di]
    h0 = jnp.where(keep, ssm[slots], 0.0)                     # [S, N, Di]
    ext = jnp.concatenate([tail, a], axis=1)                  # [S, K-1+Q, Di]
    w = m["conv"]["kernel"].astype(f32)                       # [K, Di]
    K = w.shape[0]
    pre = sum(ext[:, i:i + Q].astype(f32) * w[i] for i in range(K)) \
        + m["conv"]["bias"].astype(f32)
    c = jax.nn.silu(pre).astype(cfg.dtype)
    # the K-1 columns before position q_len: a row of no real tokens keeps
    # its columns, a padded position never shifts them
    idx = q_len[:, None] + jnp.arange(K - 1)[None, :]
    conv = conv.at[slots].set(jnp.take_along_axis(
        ext, idx[:, :, None], axis=1).astype(conv.dtype))
    dbc = c @ m["x_proj"]["kernel"].astype(cfg.dtype)
    dt, B, C = dbc[..., :R], dbc[..., R:R + N], dbc[..., R + N:]
    delta = jax.nn.softplus(
        (dt @ m["dt_proj"]["kernel"].astype(cfg.dtype)).astype(f32)
        + m["dt_proj"]["bias"].astype(f32))
    y, h_t = _scan_fn(cfg.d_inner, N)(
        c, delta, -jnp.exp(m["A_log"].astype(f32)), B.astype(f32),
        C.astype(f32), m["D"].astype(f32), h0, q_len)
    ssm = ssm.at[slots].set(h_t)
    out = (y * jax.nn.silu(z)) @ m["out_proj"]["kernel"].astype(cfg.dtype)
    return _mlp(cfg, x + out, p), conv, ssm, y


def _gmu(cfg, x, p, memory):
    u = _ln(x, p["ln1"], cfg.layer_norm_eps)
    m = p["mixer"]
    g = jax.nn.silu(u @ m["in_proj"]["kernel"].astype(cfg.dtype))
    return _mlp(cfg, x + (memory * g) @ m["out_proj"]["kernel"].astype(cfg.dtype), p)


def _diff_attention(cfg, x, p, k_pool, v_pool, tables, seen, q_len, layer,
                    write, window, trash):
    """Differential attention of layer index ``layer`` (a traced scalar in the
    scanned runs) over the pages ``tables`` name; ``write``: project K and V
    and scatter them first (a self layer), else only read (a cross layer)."""
    S, Q, _ = x.shape
    H, KV, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    m = p["mixer"]
    f32 = jnp.float32
    u = _ln(x, p["ln1"], cfg.layer_norm_eps)
    qkv = u @ m["qkv_proj"]["kernel"].astype(cfg.dtype) \
        + m["qkv_proj"]["bias"].astype(cfg.dtype)
    bs = k_pool.shape[2]
    if write:
        k = qkv[..., H * Dh:(H + KV) * Dh].reshape(S, Q, KV // 2, 2 * Dh)
        v = qkv[..., (H + KV) * Dh:].reshape(S, Q, KV // 2, 2 * Dh)
        k_pool, v_pool = _scatter_kv(k_pool, v_pool, k, v, tables, seen,
                                     q_len, bs, trash=trash)
    q = qkv[..., :H * Dh].reshape(S, Q, H // 2, 2, 1, Dh)
    half = jnp.eye(2, dtype=q.dtype)[:, :, None]              # [2, 2, 1]
    qz = (q * half).reshape(S, Q, H, 2 * Dh)                  # own half, else 0
    o = _paged_attention(qz, k_pool, v_pool, tables, seen, bs, q_len,
                         window=window, softmax_scale=Dh ** -0.5)
    o = o.reshape(S, Q, H // 2, 2, 2 * Dh).astype(f32)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, f32))
    lam = jnp.exp(jnp.sum(m["lambda_q1"].astype(f32) * m["lambda_k1"].astype(f32))) \
        - jnp.exp(jnp.sum(m["lambda_q2"].astype(f32) * m["lambda_k2"].astype(f32))) \
        + lam0
    diff = o[..., 0, :] - lam * o[..., 1, :]                  # [S, Q, H/2, 2Dh]
    diff = diff * jax.lax.rsqrt(
        jnp.mean(diff * diff, axis=-1, keepdims=True) + cfg.subln_eps)
    diff = (diff * m["subln"]["scale"].astype(f32) * (1.0 - lam0)).astype(cfg.dtype)
    out = diff.reshape(S, Q, H * Dh) @ m["out_proj"]["kernel"].astype(cfg.dtype) \
        + m["out_proj"]["bias"].astype(cfg.dtype)
    return _mlp(cfg, x + out, p), k_pool, v_pool


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def ragged_forward(cfg, params, cache, tokens, q_len, seen, tables):
    """One ragged forward step -> (last-token logits [S, V], new cache)."""
    P = cfg.front_periods
    half = cfg.num_hidden_layers // 2
    k_full, v_full = cache["kv"]
    k_win, v_win = cache["window"]
    conv, ssm = cache["state"]["conv"], cache["state"]["ssm"]
    t_full, t_win = tables["kv"], tables["window"]
    slots = tables["state"]
    seen_win = seen - tables["window_base"]
    fresh = seen == 0

    # every stacked pool is one merged pool on its loop's carry
    # (paged_layer.py, "The layout"), the slots of state like the pages
    nbw, nbf, ns = k_win.shape[1], k_full.shape[1], conv.shape[1]
    kw, vw, cv, sm, kf, vf = merge_layers(
        (k_win, v_win, conv, ssm, k_full, v_full))

    x = params["embed_tokens"].astype(cfg.dtype)[tokens]

    def front(carry, xs):
        x, kw, vw, cv, sm = carry
        p, i = xs
        x, cv, sm, _ = _mamba(cfg, x, p["mamba"], cv, sm,
                              layer_rows(slots, i, ns), q_len, fresh)
        x, kw, vw = _diff_attention(
            cfg, x, p["window"], kw, vw, layer_rows(t_win, i, nbw), seen_win,
            q_len, 2 * i + 1, True, cfg.sliding_window, layer_trash(i, nbw))
        return (x, kw, vw, cv, sm), None

    (x, kw, vw, cv, sm), _ = jax.lax.scan(
        front, (x, kw, vw, cv, sm), (params["front"], jnp.arange(P)))
    x, cv, sm, memory = _mamba(cfg, x, params["middle_mamba"], cv, sm,
                               layer_rows(slots, P, ns), q_len, fresh)
    x, kf, vf = _diff_attention(cfg, x, params["full"], kf, vf, t_full, seen,
                                q_len, half + 1, True, None,
                                layer_trash(0, nbf))

    def back(x, xs):
        p, i = xs
        x = _gmu(cfg, x, p["gmu"], memory)
        x, _, _ = _diff_attention(cfg, x, p["cross"], kf, vf, t_full, seen,
                                  q_len, half + 3 + 2 * i, False, None, None)
        return x, None

    x, _ = jax.lax.scan(back, x, (params["back"], jnp.arange(cfg.back_periods)))

    x = _ln(x, params["final_layernorm"], cfg.layer_norm_eps)
    last = last_token(x, q_len)
    logits = last @ params["embed_tokens"].astype(cfg.dtype).T   # tied
    cache = {"kv": split_layers((kf, vf), 1),
             "window": split_layers((kw, vw), P),
             "state": split_layers({"conv": cv, "ssm": sm}, conv.shape[0])}
    return logits.astype(jnp.float32), cache
