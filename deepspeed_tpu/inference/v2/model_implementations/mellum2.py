"""Ragged forward for Mellum2 (``models/mellum2.py`` has the architecture):
sliding-window and full attention layers over two paged groups, a
sparse-expert feed-forward part in every layer.

Two kinds of pages ride the ``cache`` pytree, each reached through its entry
of ``tables`` (``ragged/cache_groups.py``; the state manager builds both):

* ``cache["kv"]``: the full layers' pages ``[full layers, NB+1, KV, bs, Dh]``,
  which live as long as the sequence; ``tables["kv"]`` names all of them.
* ``cache["window"]``: the sliding layers' pages ``[sliding layers, NBw+1,
  ...]``. ``tables["window"]`` holds only a sequence's LIVE pages, the first
  of them starting at token ``tables["window_base"]``. K is written to its
  page already rotated by its ABSOLUTE position and q is rotated by its own,
  so a score depends on the difference of positions as RoPE has it, and the
  write, the read and the window's mask, which need only differences, run on
  ``seen - base``: these layers never learn that earlier pages are gone.

RoPE differs by layer type (default for the sliding layers, YaRN for the full
ones): one ``(cos, sin)`` table a type, computed once before the layer loop.
The expert layer is ``moe_layer.moe_ffn``, shared with Mixtral; a padded
token slot takes no expert rows. The loop is unrolled, one subtree of weights
a layer, a layer of either type taking the next layer of its own pool; the
layer itself is a jit of its own, so the twelve calls of a program trace and
lower two functions (one a layer type), where twelve inlined copies of the
kernels' bodies took the benchmark cell's set-up past the limit of its run.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.llama import _rmsnorm
from deepspeed_tpu.inference.v2.model_implementations.moe_layer import (
    dispatch_report, moe_ffn)  # the first: this family's export
from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
    _paged_attention, _pool_block_size, _scatter_kv, last_token, layer_rows,
    layer_trash, merge_layers, pool_pages_per_layer, real_slots,
    split_layers)
from deepspeed_tpu.models.llama import (
    rope_frequencies, rotary_apply, rotary_tables)
from deepspeed_tpu.models.mellum2 import FULL, SLIDING


@functools.partial(jax.jit, static_argnums=(0, 10))
def _layer(cfg, lp, x, k_pool, v_pool, tables, seen, q_len, real, rope,
           window, trash):
    """One decoder layer over x [S, Q, d] against the merged pool of its
    type; ``tables``, ``seen`` and ``trash`` are that pool's. ``trash`` is
    a traced scalar so that the layers of one type share ONE traced and
    lowered function; XLA inlines the calls, and the compiled program has
    the operations it had."""
    S, Q, _ = x.shape
    H, KV, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    attn = lp["self_attn"]
    proj = lambda h, name, heads: (
        h @ attn[name]["kernel"].astype(cfg.dtype)).reshape(S, Q, heads, Dh)
    h = _rmsnorm(x, lp["input_layernorm"]["scale"], cfg.rms_norm_eps)
    q = _rmsnorm(proj(h, "q_proj", H), attn["q_norm"]["scale"], cfg.rms_norm_eps)
    k = _rmsnorm(proj(h, "k_proj", KV), attn["k_norm"]["scale"], cfg.rms_norm_eps)
    v = proj(h, "v_proj", KV)
    q, k = rotary_apply(q, *rope), rotary_apply(k, *rope)
    bs = k_pool.shape[2]
    k_pool, v_pool = _scatter_kv(k_pool, v_pool, k, v, tables, seen, q_len,
                                 bs, trash=trash)
    out = _paged_attention(q, k_pool, v_pool, tables, seen, bs, q_len,
                           window=window)
    x = x + out.reshape(S, Q, H * Dh) @ attn["o_proj"]["kernel"].astype(cfg.dtype)

    moe = lp["moe"]
    h = _rmsnorm(x, lp["post_attention_layernorm"]["scale"], cfg.rms_norm_eps)
    y = moe_ffn(h.reshape(S * Q, -1),
                moe["router"]["kernel"].astype(cfg.dtype),
                moe["w1"].astype(cfg.dtype), moe["w2"].astype(cfg.dtype),
                moe["w3"].astype(cfg.dtype),
                k=cfg.num_experts_per_tok, dtype=cfg.dtype, valid=real)
    return x + y.reshape(S, Q, -1), k_pool, v_pool


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
def ragged_forward(cfg, params, cache, tokens, q_len, seen, tables):
    """One ragged forward step -> (last-token logits [S, V], new cache); the
    contract is ``llama.ragged_forward``'s."""
    S, Q = tokens.shape
    pools = {FULL: cache["kv"], SLIDING: cache["window"]}
    rows = {FULL: tables["kv"], SLIDING: tables["window"]}
    at = {FULL: seen, SLIDING: seen - tables["window_base"]}
    window = {FULL: None, SLIDING: cfg.sliding_window}
    count = {kind: pools[kind][0].shape[0] for kind in pools}
    nb = {kind: pool_pages_per_layer(pools[kind][0]) for kind in pools}
    assert _pool_block_size(pools[FULL][0]) == _pool_block_size(pools[SLIDING][0])

    positions = seen[:, None] + jnp.arange(Q)[None, :]
    real = real_slots(q_len, Q).reshape(S * Q)
    # a frequency table a layer type, once, outside the layer loop
    rope = {kind: rotary_tables(positions, *rope_frequencies(
        cfg.head_dim, *cfg.rope(kind))) for kind in pools}

    # every stacked pool is one merged pool on the loop's carry
    # (paged_layer.py, "The layout")
    pools = {kind: merge_layers(pool) for kind, pool in pools.items()}
    x = params["embed_tokens"].astype(cfg.dtype)[tokens]
    nth = {FULL: 0, SLIDING: 0}
    for l, kind in enumerate(cfg.layer_types):
        i = nth[kind]
        nth[kind] += 1
        x, kp, vp = _layer(
            cfg, params[f"layers_{l}"], x, *pools[kind],
            layer_rows(rows[kind], i, nb[kind]), at[kind], q_len, real,
            rope[kind], window[kind], jnp.int32(layer_trash(i, nb[kind])))
        pools[kind] = (kp, vp)

    x = _rmsnorm(x, params["norm"]["scale"], cfg.rms_norm_eps)
    logits = last_token(x, q_len) @ params["lm_head"].astype(cfg.dtype).T
    return logits.astype(jnp.float32), {
        "kv": split_layers(pools[FULL], count[FULL]),
        "window": split_layers(pools[SLIDING], count[SLIDING])}
