"""Plain reference for Mellum2 (JetBrains Mellum2-12B-A2.5B-Instruct): the full
forward pass in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, full masked attention, the expert
layer as a plain sum over the chosen experts; no cache, no batching, no kernel.
It imports nothing of ``deepspeed_tpu``.

The layers, from the published ``config.json`` (the configuration file keeps
its keys):

- Pre-norm block ``x <- x + Attn(RMSNorm(x)); x <- x + MoE(RMSNorm(x))``,
  RMSNorm eps ``rms_norm_eps`` (1e-6), hidden 2304, no biases
  (``attention_bias`` false), a final RMSNorm and an untied head over 98,304.
- Attention: 32 query heads and 4 KV heads of ``head_dim`` 128 (q is 2304 ->
  4096, NOT hidden / heads), groups of 8. ``layer_types`` repeats ``sliding,
  sliding, sliding, full``. Sliding layers: key visible iff ``0 <= q_pos -
  k_pos < sliding_window`` (1024), RoPE default, theta 500,000. Full layers:
  causal, RoPE YaRN: ``inv_freq = interp * (1 - e) + extrap * e`` with ``extrap
  = theta^(-2i/128)``, ``interp = extrap / factor`` (16), ``e = 1 - clip((i -
  low) / (high - low), 0, 1)``, ``low, high = floor, ceil of 128 ln(8192 / (b 2
  pi)) / (2 ln theta)`` for ``b`` = ``beta_fast`` (32) and ``beta_slow`` (1),
  and cos and sin scaled by ``attention_factor`` (1.2772588722239782 = 0.1 ln
  16 + 1) at every position.
- Feed-forward, every layer ``sparse``: ``p = softmax(x W_r)`` over all 64
  experts, the 8 largest, renormalised to sum 1 (``norm_topk_prob``), ``y =
  sum_e p_e W2_e(silu(W1_e x) * W3_e x)`` of width 896; no shared expert;
  ``intermediate_size`` 7168 belongs to ``dense`` layers, of which
  ``mlp_layer_types`` has none.

Assumed (the config has no key for them; its key names ``norm_topk_prob``,
``moe_intermediate_size``, ``max_window_layers``, ``use_sliding_window``,
``head_dim`` are Qwen3-MoE's, so that family's convention is taken): RMSNorm
with a learned scale of 128 on each q and k head before RoPE; a router without
bias, softmax before top-k. The multi-token-prediction head the model card
mentions has no key in the config and is not computed. Departure: RoPE pairs
adjacent columns (x[0::2], x[1::2]) where the published code pairs halves; with
seeded weights one is the other under a fixed permutation of each head's
columns.

``leave_out`` names terms a control drops, to show that the comparison sees
them: ``attention_factor``, ``renormalise``, ``qk_norm``.

Weights are regenerated from the seed (float32 copies of the bfloat16 values
the configuration serves), one layer's attention and ONE EXPERT at a time;
nothing the program made is read. Attention runs in blocks of queries and the
head in blocks of rows, so that a 14k-token request fits the chip.
"""

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights
from benchmark.references.common import HIGHEST, matmul

Q_BLOCK = 512         # queries a block of attention
ROW_BLOCK = 512       # rows a block of the head
SLIDING, FULL = "sliding_attention", "full_attention"
TERMS = ("attention_factor", "renormalise", "qk_norm")


def param_spec(cfg):
    """The parameter tree as the program's ``Mellum2ForCausalLM`` holds it
    (a tier-1 test holds the two lists equal): one subtree a layer, matrices
    bfloat16, norm scales float32, a layer's experts stacked ``[E, ...]``."""
    d, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    H, KV, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    E, F = cfg["num_experts"], cfg["moe_intermediate_size"]
    bf, f32, one = jnp.bfloat16, jnp.float32, ("const", 1.0)
    rows = [(("embed_tokens",), (V, d), 0.02, bf, False),
            (("lm_head",), (V, d), 0.02, bf, False),
            (("norm", "scale"), (d,), one, f32, False)]
    for l in range(L):
        at = lambda *p: (f"layers_{l}",) + p
        lin = lambda name, i, o: (at("self_attn", name, "kernel"), (i, o),
                                  1 / math.sqrt(i), bf, False)
        rows += [
            (at("input_layernorm", "scale"), (d,), one, f32, False),
            (at("post_attention_layernorm", "scale"), (d,), one, f32, False),
            lin("q_proj", d, H * dh), lin("k_proj", d, KV * dh),
            lin("v_proj", d, KV * dh), lin("o_proj", H * dh, d),
            (at("self_attn", "q_norm", "scale"), (dh,), one, f32, False),
            (at("self_attn", "k_norm", "scale"), (dh,), one, f32, False),
            (at("moe", "router", "kernel"), (d, E), 1 / math.sqrt(d), bf, False),
            (at("moe", "w1"), (E, d, F), 1 / math.sqrt(d), bf, True),
            (at("moe", "w3"), (E, d, F), 1 / math.sqrt(d), bf, True),
            (at("moe", "w2"), (E, F, d), 1 / math.sqrt(F), bf, True)]
    return rows


def layer_kinds(cfg):
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def rope_table(cfg, kind, leave_out=()):
    """``(inv_freq [head_dim / 2], scale of cos and sin)`` of a layer type,
    from its section of ``rope_parameters``."""
    p = cfg["rope_parameters"][kind]
    dim, theta = cfg["head_dim"], float(p["rope_theta"])
    i = np.arange(dim // 2, dtype=np.float64)
    extrap = theta ** (-2.0 * i / dim)
    if p["rope_type"] == "default":
        return extrap.astype(np.float32), 1.0
    if p["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {p['rope_type']!r}")
    interp = extrap / p["factor"]
    orig = p["original_max_position_embeddings"]
    turn = lambda b: dim * math.log(orig / (b * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(turn(p["beta_fast"])), 0)
    high = min(math.ceil(turn(p["beta_slow"])), dim - 1)
    e = 1.0 - np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    scale = 1.0 if "attention_factor" in leave_out else float(p["attention_factor"])
    return (interp * (1.0 - e) + extrap * e).astype(np.float32), scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotary(x, inv_freq, scale):
    """x [T, heads, dh] at positions 0..T-1, adjacent pairs."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = (jnp.cos(ang) * scale)[:, None, :], (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def _attention(c, kind, precision, leave_out, p, x):
    """x [T, d] -> x + Attn(RMSNorm(x)) for one sequence."""
    T = x.shape[0]
    H, KV, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps = c["rms_norm_eps"]
    a = p["self_attn"]
    h = _rms(x, p["input_layernorm"]["scale"], eps)
    q = matmul(h, a["q_proj"]["kernel"], precision).reshape(T, H, dh)
    k = matmul(h, a["k_proj"]["kernel"], precision).reshape(T, KV, dh)
    v = matmul(h, a["v_proj"]["kernel"], precision).reshape(T, KV, dh)
    if "qk_norm" not in leave_out:
        q = _rms(q, a["q_norm"]["scale"], eps)
        k = _rms(k, a["k_norm"]["scale"], eps)
    inv_freq, scale = rope_table(c, kind, leave_out)
    q, k = _rotary(q, inv_freq, scale), _rotary(k, inv_freq, scale)
    qg = q.reshape(T, KV, H // KV, dh)
    pos = jnp.arange(T)

    def block(q_blk, q_pos):
        s = jnp.einsum("tkrd,skd->krts", q_blk, k, precision=HIGHEST) / math.sqrt(dh)
        seen = pos[None, :] <= q_pos[:, None]
        if kind == SLIDING:
            seen = seen & (q_pos[:, None] - pos[None, :] < c["sliding_window"])
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("krts,skd->tkrd", jax.nn.softmax(s, -1), v, precision=HIGHEST)

    nb = -(-T // Q_BLOCK)
    if nb == 1:
        o = block(qg, pos)
    else:
        pad = nb * Q_BLOCK - T
        qp = jnp.pad(qg, ((0, pad),) + ((0, 0),) * 3).reshape((nb, Q_BLOCK) + qg.shape[1:])
        pp = jnp.pad(pos, (0, pad), constant_values=T - 1).reshape(nb, Q_BLOCK)
        o = jax.lax.map(lambda args: block(*args), (qp, pp))
        o = o.reshape((nb * Q_BLOCK,) + o.shape[2:])[:T]
    return x + matmul(o.reshape(T, H * dh), a["o_proj"]["kernel"], precision)


def _moe(c, precision, leave_out, p, expert, x):
    """x [N, d] (any tokens, each alone) -> (x + MoE(RMSNorm(x)), near ties).
    ``expert(e)`` gives expert ``e``'s float32 ``(w1, w3, w2)``. The sum runs
    over ALL experts with a gate that is zero where an expert was not chosen.
    Near ties: tokens whose chosen set changes when the router's input is
    rounded to bfloat16 first, as the served path's activations are."""
    E, k = c["num_experts"], c["num_experts_per_tok"]
    h = _rms(x, p["post_attention_layernorm"]["scale"], c["rms_norm_eps"])
    w_r = p["moe"]["router"]["kernel"]
    probs = jax.nn.softmax(matmul(h, w_r, precision), -1)
    top_vals, top_idx = jax.lax.top_k(probs, k)
    if "renormalise" not in leave_out:
        top_vals = top_vals / jnp.sum(top_vals, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], top_idx].set(top_vals)
    rounded = matmul(h.astype(jnp.bfloat16).astype(jnp.float32), w_r, "f32")
    other = jax.lax.top_k(rounded, k)[1]
    ties = jnp.sum(jnp.any(jnp.sort(other, -1) != jnp.sort(top_idx, -1), -1))

    def add(e, y):
        w1, w3, w2 = expert(e)
        act = jax.nn.silu(matmul(h, w1, precision)) * matmul(h, w3, precision)
        return y + jax.lax.dynamic_slice_in_dim(gate, e, 1, 1) * matmul(act, w2, precision)

    return x + jax.lax.fori_loop(0, E, add, jnp.zeros_like(x)), ties


def _f32(t):
    return jax.tree.map(lambda a: a.astype(jnp.float32), t)


def full_logits(cfg, tree, ids, precision="f32", leave_out=()):
    """Logits [T, V] of one sequence of token ids from a whole parameter
    tree: the tests' oracle at small sizes (the chip's comparison regenerates
    the weights instead and gathers rows, below)."""
    with jax.default_matmul_precision("highest"):
        x = tree["embed_tokens"].astype(jnp.float32)[ids]
        for l, kind in enumerate(layer_kinds(cfg)):
            p = _f32(tree[f"layers_{l}"])
            x = _attention(cfg, kind, precision, leave_out, p, x)
            m = p["moe"]
            x, _ = _moe(cfg, precision, leave_out, p,
                        lambda e, m=m: (m["w1"][e], m["w3"][e], m["w2"][e]), x)
        x = _rms(x, tree["norm"]["scale"].astype(jnp.float32), cfg["rms_norm_eps"])
        return matmul(x, tree["lm_head"].astype(jnp.float32).T, precision)


# -- the chip's comparison: weights from the seed, a layer and an expert at a time

def _crc(path):
    return zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF


def _layer_rows(spec, l):
    """The rows of layer ``l`` with their paths below ``layers_<l>``."""
    return [(p[1:], s, f, d, st) for p, s, f, d, st in spec if p[0] == f"layers_{l}"]


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4), donate_argnums=(7,))
def _layer(c_items, kind, rows, precision, leave_out, key, crcs, x):
    """One layer over x [B, T, d]. ``rows`` are one layer's rows of the spec
    (alike for every layer) and ``crcs`` that layer's leaf keys' folds, in
    the rows' order: ``weights.leaf``'s values, with the layer traced."""
    c = _cfg(c_items)
    fold = {path: jax.random.fold_in(key, crcs[i]) for i, (path, *_) in enumerate(rows)}
    shape = {path: (s, f, d) for path, s, f, d, _ in rows}
    flat = [(path, weights._fill(fold[path], s, f, d).astype(jnp.float32))
            for path, s, f, d, st in rows if not st]
    p = weights._nest(flat)

    def expert(e):
        def one(name):
            s, f, d = shape[("moe", name)]
            return weights._fill(jax.random.fold_in(fold[("moe", name)], e),
                                 s[1:], f, d).astype(jnp.float32)
        return one("w1"), one("w3"), one("w2")

    B, T, d = x.shape
    x = jax.lax.map(lambda row: _attention(c, kind, precision, leave_out, p, row), x)
    y, ties = _moe(c, precision, leave_out, p, expert, x.reshape(B * T, d))
    return y.reshape(B, T, d), ties


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _head_gaps(c_items, spec, precision, low, key, x, x_low, rows, toks):
    """Per served token, how far its float32 logit lies below the float32
    best, a block of rows at a time: x [B, T, d], rows, toks [B, N] -> [B, N].
    With ``low`` the token that ``x_low`` under the control puts first takes
    the served token's place."""
    c = _cfg(c_items)
    head = weights.one_leaf(key, spec, ("lm_head",)).astype(jnp.float32)
    scale = weights.one_leaf(key, spec, ("norm", "scale"))
    B, N = rows.shape
    nb = N // ROW_BLOCK

    def block(args):
        r, t = args                                          # [B, ROW_BLOCK]
        h = _rms(jnp.take_along_axis(x, r[:, :, None], 1), scale, c["rms_norm_eps"])
        ref = matmul(h, head.T, "f32")
        if low:
            hl = _rms(jnp.take_along_axis(x_low, r[:, :, None], 1), scale, c["rms_norm_eps"])
            t = jnp.argmax(matmul(hl, head.T, precision), -1)
        at = jnp.take_along_axis(ref, t[:, :, None], -1)[..., 0]
        return jnp.max(ref, -1) - at

    split = lambda a: a.reshape(B, nb, ROW_BLOCK).transpose(1, 0, 2)
    out = jax.lax.map(block, (split(rows), split(toks)))
    return out.transpose(1, 0, 2).reshape(B, N)


def _cfg_items(cfg):
    """The keys the layers read, hashable."""
    rope = tuple(sorted((kind, tuple(sorted(p.items())))
                        for kind, p in cfg["rope_parameters"].items()))
    return tuple(sorted((k, cfg[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
        "sliding_window", "num_experts", "num_experts_per_tok"))) + (("rope_parameters", rope),)


def _cfg(c_items):
    c = dict(c_items)
    c["rope_parameters"] = {kind: dict(p) for kind, p in c["rope_parameters"]}
    return c


def _hidden(cfg, seed, ids, precision, leave_out=()):
    """Hidden states [B, T, d] before the final norm, and the share of
    (token, layer) pairs that are near ties of the router."""
    spec = tuple(param_spec(cfg))
    c_items = _cfg_items(cfg)
    key = weights.base_key(seed)
    embed = jax.jit(lambda k: weights.one_leaf(k, spec, ("embed_tokens",)))(key)
    x = embed[ids].astype(jnp.float32)
    del embed
    rows = tuple(_layer_rows(spec, 0))
    ties = 0
    for l, kind in enumerate(layer_kinds(cfg)):
        crcs = jnp.asarray([_crc((f"layers_{l}",) + path) for path, *_ in rows], jnp.int32)
        x, t = _layer(c_items, kind, rows, precision, tuple(leave_out), key, crcs, x)
        ties += int(t)
    return c_items, spec, key, x, ties / (x.shape[0] * x.shape[1] * cfg["num_hidden_layers"])


def gaps(cfg, seed, prompts, outputs, pad_to, max_new, controls=()):
    """{"served": per served token, how far its float32-reference logit lies
    below the reference's best at that position; each control: the same for
    the token that the control puts first there}. A control is ``"int8"``
    (every matmul fake-quantised) or ``"without:<term>"`` (the float32
    forward with a term of ``TERMS`` left out). The float32 forward runs
    once. Flat lists over the same (request, position) pairs."""
    B = len(prompts)
    # padded to whole blocks of what was served, within the mix's sizes
    up = lambda n, block: -(-n // block) * block
    max_new = min(up(max_new, ROW_BLOCK), up(max(len(o) for o in outputs), ROW_BLOCK))
    pad_to = min(pad_to, up(max(len(p) + len(o) for p, o in zip(prompts, outputs)), Q_BLOCK))
    ids = np.zeros((B, pad_to), np.int32)
    rows = np.zeros((B, max_new), np.int32)
    toks = np.zeros((B, max_new), np.int32)
    valid = np.zeros((B, max_new), bool)
    for b, (p, o) in enumerate(zip(prompts, outputs)):
        seq = np.concatenate([p, o[:-1]])
        ids[b, :len(seq)] = seq
        rows[b, :len(o)] = len(p) - 1 + np.arange(len(o))
        toks[b, :len(o)] = o
        valid[b, :len(o)] = True
    out = {}
    with jax.default_matmul_precision("highest"):
        ids, rows, toks = jnp.asarray(ids), jnp.asarray(rows), jnp.asarray(toks)
        c_items, spec, key, x, ties = _hidden(cfg, seed, ids, "f32")
        print(f"reference mellum2: the router's chosen set changes under bfloat16 rounding "
              f"of its input in {100 * ties:.3f} % of (token, layer) pairs", flush=True)
        out["served"] = _head_gaps(c_items, spec, "f32", False, key, x, x, rows, toks)
        for control in controls:
            precision, leave_out = control, ()
            if control.startswith("without:"):
                precision, leave_out = "f32", (control.split(":", 1)[1],)
                if leave_out[0] not in TERMS:
                    raise ValueError(f"unknown term {leave_out[0]!r}; known: {TERMS}")
            x_low = _hidden(cfg, seed, ids, precision, leave_out)[3]
            out[control] = _head_gaps(c_items, spec, precision, True, key, x, x_low, rows, toks)
    return {name: np.asarray(g)[valid].tolist() for name, g in out.items()}


def served_token_gaps(cfg, seed, prompts, outputs, pad_to, max_new, low_precision=None):
    """``serve.Driver._gaps``'s call: the served tokens' gaps, or with
    ``low_precision`` that control's (``gaps`` has both from one float32
    forward). Returns a flat list."""
    got = gaps(cfg, seed, prompts, outputs, pad_to, max_new,
               (low_precision,) if low_precision else ())
    return got[low_precision or "served"]
