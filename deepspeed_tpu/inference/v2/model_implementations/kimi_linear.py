"""Ragged forward for Kimi-Linear (``models/kimi_linear.py`` has the
architecture): three layers of gated delta-rule linear attention (KDA) to one
of latent attention without positions, one leading dense layer, then
sparse-expert layers with a shared expert.

Three kinds of state ride the ``cache`` pytree (``ragged/cache_groups.py``):

* ``cache["kv"]`` is ``(pages,)``: ``[MLA layers, NB+1, 1, bs, W]``, Kanana-2's
  latent row a token (``kanana2.latent_mla`` writes and reads it, told
  ``rope=None``: nothing is rotated and no table is built). Plane ``p`` is the
  ``p``-th MLA layer's: a KDA layer has no page, so the stack's layer index
  and the pool's differ and ``layer_rows`` takes the plane's.
* ``cache["state"]``: ``conv`` ``[KDA layers, slots+1, 4, 3 x H x dk]`` (the
  last ``taps-1`` inputs of the q, k and v convolutions side by side in rows
  ``0 .. taps-2``, the serving dtype; the rows up to a whole tile of four are
  zeros that nothing reads) and ``kda`` ``[KDA layers, slots+1, H, dk, dk]``
  float32, row ``tables["state"]`` of each a sequence's slot (the last
  absorbs padded rows). A row whose ``seen`` is 0 starts from zero state and
  zero tails whatever its slot held; positions ``>= q_len`` advance neither
  leaf (``phi4flash._mamba``'s rule). Why a fourth row: the chip's tiling
  pads three bfloat16 rows to four, and stored as three the pool was kept
  compact between uses and re-laid WHOLE around every layer's gather and
  scatter of a dispatch's rows (17 % of the serving cell's busy time). Do not
  restore the three rows, and do not flatten a slot to one row either: that
  program compiles and never ends its first dispatch on the chip (PERF.md,
  PR 56).
* ``cache["counters"]``: the expert layers' ``moe_layer.COUNTS``, summed over
  the layers of a dispatch and added on the device.

The KDA mixer: one matmul for q, k and v (``prepare_params`` lays the three
kernels side by side, and the two gates' down-projections and beta's), the
short convolution over the tails, then the state: a dispatch of ONE token a
row (``Q == 1``: a decode round) takes ``ops/pallas/kda.kda_step``, any other
``kda_chunk`` (the chunk form), each the kernel where Pallas is on and the
shapes tile, else its ``jax.numpy`` twin. Both work on the merged slot pool on
the loop's carry, in place.

Three jitted layer bodies: KDA + dense FFN, KDA + experts, MLA + experts
(``_kda_layer``'s static ``dense``; an MLA layer before
``first_k_dense_replace`` takes the same switch). Device scopes: everything of
the KDA mixer under ``kda``, inside it ``kda_proj``, ``kda_conv``, ``kda_step``
or ``kda_chunk`` (the kernel's own name is the same word), ``kda_out``; the MLA
under ``mla_attn`` as Kanana-2's; the expert layer ``moe_layer``'s own.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations import moe_layer
from deepspeed_tpu.inference.v2.model_implementations.kanana2 import (
    cut_kv_b, latent_mla, latent_read_report)
from deepspeed_tpu.inference.v2.model_implementations.llama import _rmsnorm
from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
    _pool_block_size, last_token, layer_rows, layer_trash, merge_layers,
    pool_pages_per_layer, real_slots, split_layers)
from deepspeed_tpu.ops.registry import pallas_interpret, takes_kernel


def dispatch_report(cfg, real_tokens, chunk):
    """``moe_layer.dispatch_report``'s two mappings and the KDA layers': added
    are ``kda_step_rows`` (rows that took the one-step update: a dispatch of
    one token slot a row) and ``kda_chunk_tokens`` (real tokens that took the
    chunk form), each a layer, and the MLA layers' read's form
    (``kanana2.latent_read_report``); ``kda_layers`` rides. The slots held are
    the state manager's ``state_slots``."""
    adds, rides = moe_layer.dispatch_report(cfg, real_tokens, chunk)
    step = chunk == 1
    adds = dict(adds, kda_step_rows=real_tokens if step else 0,
                kda_chunk_tokens=0 if step else real_tokens,
                **latent_read_report(cfg, real_tokens, chunk))
    return adds, dict(rides, kda_layers=len(cfg.kda_layers))


def prepare_params(cfg, params):
    """The tree as the forward reads it. An MLA layer's ``kv_b_proj`` cut once
    into ``w_uk`` and ``w_uv`` (``kanana2.cut_kv_b``). A KDA layer's q, k and v
    kernels side by side (``qkv_proj`` [d, 3 W]), the three convolutions
    likewise (``conv`` [taps, 3 W]), and the three narrow projections of the
    normed stream (the decay gate's down-projection, the output gate's,
    beta's) as one ``gates_proj`` [d, 2 dk + H]: one matmul each where the
    tree as trained has three. A tree of shapes gives a tree of shapes."""
    def beside(*kernels):
        fn = lambda *a: jnp.concatenate(a, axis=1)
        shapes = isinstance(kernels[0], jax.ShapeDtypeStruct)
        return jax.eval_shape(fn, *kernels) if shapes else fn(*kernels)

    out = dict(params)
    for l in range(cfg.num_hidden_layers):
        layer = dict(params[f"layers_{l}"])
        attn = dict(layer["self_attn"])
        if cfg.layer_kind(l) == "mla":
            attn["w_uk"], attn["w_uv"] = cut_kv_b(cfg, attn.pop("kv_b_proj")["kernel"])
        else:
            take = lambda *names: [attn.pop(n)["kernel"] for n in names]
            attn["qkv_proj"] = beside(*take("q_proj", "k_proj", "v_proj"))
            attn["conv"] = beside(*take("q_conv", "k_conv", "v_conv"))
            attn["gates_proj"] = beside(*take("f_a_proj", "g_a_proj", "b_proj"))
        layer["self_attn"] = attn
        out[f"layers_{l}"] = layer
    return out


def _state_fn(step, heads, dk):
    """The state's update and read for a dispatch: the one-step kernel or the
    chunk kernel, else its twin (a ``fallback`` dispatch record)."""
    from deepspeed_tpu.ops.pallas import kda
    name = "kda_step" if step else "kda_chunk"
    supported = (kda.step_is_supported if step else kda.chunk_is_supported)(
        heads, dk, dk)
    if takes_kernel(name, supported, f"heads of {dk} do not tile"):
        return functools.partial(getattr(kda, name), interpret=pallas_interpret())
    return kda.kda_step_ref if step else kda.kda_chunk_ref


def _kda(cfg, attn, h, x, conv, state, slots, q_len, keep):
    """``x + KDA(h)`` over [S, Q, d]; ``conv`` and ``state`` are the merged
    slot pools, ``slots`` the rows' indices into them, ``keep`` [S] False for
    a row that starts from zero. Returns (x, conv, state)."""
    S, Q, _ = x.shape
    H, dk, W = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_width
    f32, dt = jnp.float32, cfg.dtype
    valid = real_slots(q_len, Q)                               # [S, Q]
    with jax.named_scope("kda"):
        with jax.named_scope("kda_proj"):
            qkv = h @ attn["qkv_proj"].astype(dt)              # [S, Q, 3 W]
            low = h @ attn["gates_proj"].astype(dt)            # [S, Q, 2 dk + H]
            f = (low[..., :dk] @ attn["f_b_proj"]["kernel"].astype(dt)).astype(f32)
            g = -jnp.exp(attn["A_log"].astype(f32))[:, None] * jax.nn.softplus(
                f + attn["dt_bias"].astype(f32)).reshape(S, Q, H, dk)
            beta = jax.nn.sigmoid(low[..., 2 * dk:].astype(f32))     # [S, Q, H]
            gate = jax.nn.sigmoid(
                (low[..., dk:2 * dk] @ attn["g_b_proj"]["kernel"].astype(dt)).astype(f32)
                + attn["g_b_proj"]["bias"].astype(f32))
            # a position that holds no token leaves the state as it was
            g = jnp.where(valid[..., None, None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
        with jax.named_scope("kda_conv"):
            w = attn["conv"].astype(f32)                       # [K, 3 W]
            K = w.shape[0]
            tail = jnp.where(keep[:, None, None], conv[slots][:, :K - 1], 0).astype(dt)
            ext = jnp.concatenate([tail, qkv], axis=1)         # [S, K-1+Q, 3 W]
            c = jax.nn.silu(sum(ext[:, i:i + Q].astype(f32) * w[i] for i in range(K)))
            # the K-1 columns before position q_len: a row of no real tokens
            # keeps its columns, a padded position never shifts them
            idx = q_len[:, None] + jnp.arange(K - 1)[None, :]
            new = jnp.take_along_axis(ext, idx[:, :, None], axis=1).astype(conv.dtype)
            conv = conv.at[slots].set(jnp.pad(
                new, ((0, 0), (0, conv.shape[1] - (K - 1)), (0, 0))))
            heads = lambda a: a.reshape(S, Q, H, dk)
            unit = lambda a: a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
            q = unit(heads(c[..., :W])) * dk ** -0.5
            k = unit(heads(c[..., W:2 * W]))
            v = heads(c[..., 2 * W:])
        step = Q == 1
        with jax.named_scope("kda_step" if step else "kda_chunk"):
            fn = _state_fn(step, H, dk)
            if step:
                o, state = fn(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                              state, slots, keep)
                o = o[:, None]
            else:
                o, state = fn(q, k, v, g, beta, state, slots, keep, q_len)
        with jax.named_scope("kda_out"):
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + cfg.rms_norm_eps) \
                * attn["o_norm"]["scale"].astype(f32)
            o = (o.reshape(S, Q, W) * gate).astype(dt)
            x = x + o @ attn["o_proj"]["kernel"].astype(dt)
    return x, conv, state


def _ffn(cfg, dense, lp, x, real):
    """``x + FFN(RMSNorm(x))`` and the expert layer's ``moe_layer.COUNTS``
    (zeros for the dense layer)."""
    S, Q, _ = x.shape
    dt = cfg.dtype
    h = _rmsnorm(x, lp["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
    if dense:
        w = lambda name: lp["mlp"][name]["kernel"].astype(dt)
        y = (jax.nn.silu(h @ w("gate_proj")) * (h @ w("up_proj"))) @ w("down_proj")
        return x + y, jnp.zeros((len(moe_layer.COUNTS),), jnp.int32)
    moe = lp["moe"]
    y, counts = moe_layer.moe_ffn(
        h.reshape(S * Q, -1), moe["router"]["kernel"].astype(dt),
        moe["w1"].astype(dt), moe["w2"].astype(dt), moe["w3"].astype(dt),
        k=cfg.num_experts_per_token, dtype=dt, valid=real, scoring="sigmoid",
        score_bias=moe["router"]["bias"], routed_scale=cfg.routed_scaling_factor,
        shared=tuple(moe["shared"][n].astype(dt) for n in ("w1", "w2", "w3")),
        experts_held=cfg.experts_held, counts=True)
    return x + y.reshape(S, Q, -1), counts


@functools.partial(jax.jit, static_argnums=(0, 1))
def _kda_layer(cfg, dense, lp, x, conv, state, slots, q_len, keep, real):
    """One KDA layer over x [S, Q, d] against the merged slot pools;
    ``slots`` are this layer's. -> (x, conv, state, counts)."""
    h = _rmsnorm(x, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
    x, conv, state = _kda(cfg, lp["self_attn"], h, x, conv, state, slots,
                          q_len, keep)
    x, counts = _ffn(cfg, dense, lp, x, real)
    return x, conv, state, counts


@functools.partial(jax.jit, static_argnums=(0, 1))
def _mla_layer(cfg, dense, lp, x, pool, tables, seen, q_len, real, trash):
    """One MLA layer against the merged pool of latent pages; ``tables`` and
    ``trash`` (a traced scalar: the MLA layers share ONE traced function) are
    this layer's plane's. -> (x, pool, counts)."""
    S, Q, _ = x.shape
    H, dt = cfg.num_attention_heads, cfg.dtype
    attn = lp["self_attn"]
    h = _rmsnorm(x, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
    x, pool = latent_mla(
        cfg, "mla_attn", attn,
        lambda h: (h @ attn["q_proj"]["kernel"].astype(dt)).reshape(
            S, Q, H, cfg.qk_head_dim),
        h, x, pool, tables, seen, q_len, None, trash)
    x, counts = _ffn(cfg, dense, lp, x, real)
    return x, pool, counts


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def ragged_forward(cfg, params, cache, tokens, q_len, seen, tables):
    """One ragged forward step -> (last-token logits [S, V], new cache); the
    contract is ``llama.ragged_forward``'s. ``params``: ``prepare_params``'s."""
    S, Q = tokens.shape
    (pool,) = cache["kv"]
    conv, state = cache["state"]["conv"], cache["state"]["kda"]
    planes, nb, ns = pool.shape[0], pool_pages_per_layer(pool), conv.shape[1]
    assert planes == len(cfg.mla_layers) and conv.shape[0] == len(cfg.kda_layers)
    assert _pool_block_size(pool) == pool.shape[3]
    real = real_slots(q_len, Q).reshape(S * Q)
    keep = seen != 0

    # every stacked pool is one merged pool on the loop's carry
    # (paged_layer.py, "The layout"), the slots of state like the pages
    pool, conv, state = merge_layers((pool, conv, state))
    x = params["embed_tokens"].astype(cfg.dtype)[tokens]
    counts = jnp.zeros((len(moe_layer.COUNTS),), jnp.int32)
    plane = slot_layer = 0
    for l in range(cfg.num_hidden_layers):
        lp, dense = params[f"layers_{l}"], cfg.is_dense(l)
        if cfg.layer_kind(l) == "mla":
            x, pool, n = _mla_layer(
                cfg, dense, lp, x, pool, layer_rows(tables["kv"], plane, nb),
                seen, q_len, real, jnp.int32(layer_trash(plane, nb)))
            plane += 1
        else:
            x, conv, state, n = _kda_layer(
                cfg, dense, lp, x, conv, state,
                layer_rows(tables["state"], slot_layer, ns), q_len, keep, real)
            slot_layer += 1
        counts = counts + n

    x = _rmsnorm(x, params["norm"]["scale"], cfg.rms_norm_eps)
    logits = last_token(x, q_len) @ params["lm_head"].astype(cfg.dtype).T
    return logits.astype(jnp.float32), {
        "kv": (split_layers(pool, planes),),
        "state": split_layers({"conv": conv, "kda": state}, conv.shape[0] // ns),
        "counters": cache["counters"] + counts}
