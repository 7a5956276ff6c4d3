"""Plain reference for Kanana-2 (kakaocorp kanana-2-30b-a3b-instruct-2601,
``model_type`` ``deepseek_v3``): the full forward pass in straightforward
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``, full
masked attention with every head's keys and values materialised, the expert
layer as a plain sum over the experts held; no cache, no absorbed form, no
batching, no kernel. It imports nothing of ``deepspeed_tpu``.

The layers, from the published ``config.json`` (the configuration file keeps
its keys). ``x`` [T, 2048]; RMSNorm eps 1e-6 throughout; no biases but the
router's.

- Attention, every layer. ``h = norm(x)``; ``q = h W_q`` -> 32 heads of 192 =
  ``q_nope`` [128] | ``q_pe`` [64] (``q_lora_rank`` null); ``ckv = h W_kv_a``
  [576] = ``c_raw`` [512] | ``k_pe_raw`` [64]; ``c = RMSNorm(c_raw)`` with its own
  scale; ``k_pe`` ONE head shared by all 32. RoPE (theta 1e6, 64 dims,
  ``rope_scaling`` null) on ``q_pe`` and ``k_pe``. ``kv = c W_kv_b`` -> 32 heads of
  ``k_nope`` [128] | ``v`` [128]. Score of head i, query t, key s <= t:
  ``(q_nope_i[t] . k_nope_i[s] + q_pe_i[t] . k_pe[s]) / sqrt(192)``; softmax;
  ``o_i = sum_s p v_i[s]``; ``x += concat(o_i) W_o``.
- Feed-forward, layer 0 (``first_k_dense_replace`` 1): SwiGLU of width 6144.
- Feed-forward, layers 1...: ``s = sigmoid(h W_g)`` over all 128 experts;
  chosen = the 6 largest of ``s + b`` (``e_score_correction_bias``; ``n_group``
  1, ``topk_group`` 1: the group step is the identity); weights ``w = s[chosen]
  / (sum s[chosen] + 1e-20) * 2.448`` (the bias selects and never weighs; the
  normalisation is over all 6 chosen, held here or not); ``y = sum_{e in chosen
  and held} w_e SwiGLU_e(h) + SwiGLU_shared(h)``, the shared one of width 2 x
  768, every token. A token none of whose six are held takes the shared
  expert's output alone, and that partial ``y`` goes on to the next layer: the
  configuration file's ``experts_held`` (``first``, ``count``) is the share, its
  ``n_routed_experts_published`` the router's width. Without those keys every
  expert is held.

Departures. RoPE pairs adjacent columns (x[0::2], x[1::2]); the published
code (``rope_interleave`` true) pairs the same columns and writes the halves
apart, a permutation of the output's columns common to q_pe and k_pe that
leaves every score unchanged. The multi-token-prediction module of the family
has no key in this config and is not computed.

``leave_out`` names terms a control drops, to show that the comparison sees
them: ``k_pe`` (the position term of the score), ``bias`` (selection by the
unbiased scores), ``routed_scale``.

Weights are regenerated from the seed (float32 copies of the bfloat16 values
the configuration serves), one layer's and ONE EXPERT's at a time; nothing the
program made is read. Attention runs in blocks of queries and the head in
blocks of rows, so that a 19k-token request fits the chip.
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
TERMS = ("k_pe", "bias", "routed_scale")


def router_width(cfg):
    return cfg.get("n_routed_experts_published", cfg["n_routed_experts"])


def held(cfg):
    """``(first, count)`` of the router's experts this share holds."""
    share = cfg.get("experts_held")
    if not share:
        return 0, cfg["n_routed_experts"]
    if share["count"] != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held")
    return share["first"], share["count"]


def param_spec(cfg):
    """The parameter tree as the program's ``Kanana2ForCausalLM`` holds it (a
    tier-1 test holds the two lists equal): one subtree a layer, matrices
    bfloat16, norm scales and the router's bias float32, a layer's HELD
    experts stacked ``[count, ...]``, the router over every expert."""
    d, V, L, H = (cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"],
                  cfg["num_attention_heads"])
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    E, (_, count), F = router_width(cfg), held(cfg), cfg["moe_intermediate_size"]
    Fs = cfg["n_shared_experts"] * F
    bf, f32, one = jnp.bfloat16, jnp.float32, ("const", 1.0)
    rows = [(("embed_tokens",), (V, d), 0.02, bf, False),
            (("lm_head",), (V, d), 0.02, bf, False),
            (("norm", "scale"), (d,), one, f32, False)]
    for l in range(L):
        at = lambda *p: (f"layers_{l}",) + p
        lin = lambda path, i, o: (at(*path), (i, o), 1 / math.sqrt(i), bf, False)
        rows += [
            (at("input_layernorm", "scale"), (d,), one, f32, False),
            (at("post_attention_layernorm", "scale"), (d,), one, f32, False),
            lin(("self_attn", "q_proj", "kernel"), d, H * (dn + dr)),
            lin(("self_attn", "kv_a_proj", "kernel"), d, r + dr),
            (at("self_attn", "kv_a_layernorm", "scale"), (r,), one, f32, False),
            lin(("self_attn", "kv_b_proj", "kernel"), r, H * (dn + dv)),
            lin(("self_attn", "o_proj", "kernel"), H * dv, d)]
        if l < cfg["first_k_dense_replace"]:
            rows += [lin(("mlp", "gate_proj", "kernel"), d, cfg["intermediate_size"]),
                     lin(("mlp", "up_proj", "kernel"), d, cfg["intermediate_size"]),
                     lin(("mlp", "down_proj", "kernel"), cfg["intermediate_size"], d)]
            continue
        rows += [
            lin(("moe", "router", "kernel"), d, E),
            (at("moe", "router", "bias"), (E,), 0.02, f32, False),
            (at("moe", "w1"), (count, d, F), 1 / math.sqrt(d), bf, True),
            (at("moe", "w3"), (count, d, F), 1 / math.sqrt(d), bf, True),
            (at("moe", "w2"), (count, F, d), 1 / math.sqrt(F), bf, True),
            lin(("moe", "shared", "w1"), d, Fs),
            lin(("moe", "shared", "w3"), d, Fs),
            lin(("moe", "shared", "w2"), Fs, d)]
    return rows


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotary(x, theta):
    """x [T, heads, dr] at positions 0..T-1, adjacent pairs."""
    dr = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def _attention(c, precision, leave_out, p, x, q_block=Q_BLOCK):
    """x [T, d] -> x + Attn(RMSNorm(x)) for one sequence: the first form,
    every head's keys and values up-projected from the latent."""
    T = x.shape[0]
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    a = p["self_attn"]
    h = _rms(x, p["input_layernorm"]["scale"], eps)
    q = matmul(h, a["q_proj"]["kernel"], precision).reshape(T, H, dn + dr)
    ckv = matmul(h, a["kv_a_proj"]["kernel"], precision)
    latent = _rms(ckv[:, :r], a["kv_a_layernorm"]["scale"], eps)
    kv = matmul(latent, a["kv_b_proj"]["kernel"], precision).reshape(T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_nope, q_pe = q[..., :dn], _rotary(q[..., dn:], theta)
    k_pe = _rotary(ckv[:, None, r:], theta)[:, 0]              # [T, dr]: one head
    pos = jnp.arange(T)

    def block(qn, qp, q_pos):
        s = jnp.einsum("thd,shd->hts", qn, k_nope, precision=HIGHEST)
        if "k_pe" not in leave_out:
            s = s + jnp.einsum("thr,sr->hts", qp, k_pe, precision=HIGHEST)
        s = jnp.where(pos[None, :] <= q_pos[:, None], s / math.sqrt(dn + dr), -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v, precision=HIGHEST)

    nb = -(-T // q_block)
    if nb == 1:
        o = block(q_nope, q_pe, pos)
    else:
        pad = nb * q_block - T
        cut = lambda t: jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)) \
            .reshape((nb, q_block) + t.shape[1:])
        pp = jnp.pad(pos, (0, pad), constant_values=T - 1).reshape(nb, q_block)
        o = jax.lax.map(lambda args: block(*args), (cut(q_nope), cut(q_pe), pp))
        o = o.reshape((nb * q_block,) + o.shape[2:])[:T]
    return x + matmul(o.reshape(T, H * dv), a["o_proj"]["kernel"], precision)


def _swiglu(h, w1, w3, w2, precision):
    return matmul(jax.nn.silu(matmul(h, w1, precision)) * matmul(h, w3, precision),
                  w2, precision)


def _dense(c, precision, p, x):
    h = _rms(x, p["post_attention_layernorm"]["scale"], c["rms_norm_eps"])
    m = p["mlp"]
    return x + _swiglu(h, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                       m["down_proj"]["kernel"], precision)


def router(c, precision, leave_out, p, h):
    """(gate [N, E]: a chosen expert's weight, zero elsewhere; chosen [N, k])
    over ALL the router's experts, held or not."""
    k, scale = c["num_experts_per_tok"], c["routed_scaling_factor"]
    s = jax.nn.sigmoid(matmul(h, p["moe"]["router"]["kernel"], precision))
    select = s if "bias" in leave_out else s + p["moe"]["router"]["bias"]
    _, idx = jax.lax.top_k(select, k)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    if "routed_scale" not in leave_out:
        w = w * scale
    gate = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], idx].set(w)
    return gate, idx


def _moe(c, precision, leave_out, p, expert, x):
    """x [N, d] (any tokens, each alone) -> (x + MoE(RMSNorm(x)), near ties).
    ``expert(j)`` gives the float32 ``(w1, w3, w2)`` of the ``j``-th expert
    HELD, which is the router's expert ``first + j``. The sum runs over the
    held experts with a gate that is zero where one was not chosen. Near
    ties: tokens whose chosen set changes when the router's input is rounded
    to bfloat16 first, as the served path's activations are."""
    first, count = c["held"]
    h = _rms(x, p["post_attention_layernorm"]["scale"], c["rms_norm_eps"])
    gate, idx = router(c, precision, leave_out, p, h)
    other = router(c, "f32", leave_out, p, h.astype(jnp.bfloat16).astype(jnp.float32))[1]
    ties = jnp.sum(jnp.any(jnp.sort(other, -1) != jnp.sort(idx, -1), -1))

    def add(j, y):
        w1, w3, w2 = expert(j)
        return y + jax.lax.dynamic_slice_in_dim(gate, first + j, 1, 1) \
            * _swiglu(h, w1, w3, w2, precision)

    y = jax.lax.fori_loop(0, count, add, jnp.zeros_like(x))
    sh = p["moe"]["shared"]
    return x + y + _swiglu(h, sh["w1"], sh["w3"], sh["w2"], precision), ties


def _f32(t):
    return jax.tree.map(lambda a: a.astype(jnp.float32), t)


def _c(cfg):
    """The keys the layers read."""
    c = {k: cfg[k] for k in (
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rms_norm_eps", "rope_theta", "num_experts_per_tok",
        "routed_scaling_factor", "first_k_dense_replace")}
    c["held"] = held(cfg)
    return c


def full_logits(cfg, tree, ids, precision="f32", leave_out=(), q_block=Q_BLOCK):
    """Logits [T, V] of one sequence of token ids from a whole parameter
    tree: the tests' oracle at small sizes (the chip's comparison regenerates
    the weights instead and gathers rows, below)."""
    c = _c(cfg)
    with jax.default_matmul_precision("highest"):
        x = tree["embed_tokens"].astype(jnp.float32)[ids]
        for l in range(cfg["num_hidden_layers"]):
            p = _f32(tree[f"layers_{l}"])
            x = _attention(c, precision, leave_out, p, x, q_block)
            if l < c["first_k_dense_replace"]:
                x = _dense(c, precision, p, x)
                continue
            m = p["moe"]
            x, _ = _moe(c, precision, leave_out, p,
                        lambda j, m=m: (m["w1"][j], m["w3"][j], m["w2"][j]), x)
        x = _rms(x, tree["norm"]["scale"].astype(jnp.float32), cfg["rms_norm_eps"])
        return matmul(x, tree["lm_head"].astype(jnp.float32).T, precision)


# -- the chip's comparison: weights from the seed, a layer and an expert at a time

def _crc(path):
    return zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF


def _layer_rows(spec, l):
    """The rows of layer ``l`` with their paths below ``layers_<l>``."""
    return tuple((p[1:], s, f, d, st) for p, s, f, d, st in spec if p[0] == f"layers_{l}")


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3), donate_argnums=(6,))
def _layer(c_items, rows, precision, leave_out, key, crcs, x):
    """One layer over x [B, T, d]. ``rows`` are one layer's rows of the spec
    (alike for every layer of its kind, dense or expert) and ``crcs`` that
    layer's leaf keys' folds, in the rows' order: ``weights.leaf``'s values,
    with the layer traced."""
    c = dict(c_items)
    fold = {path: jax.random.fold_in(key, crcs[i]) for i, (path, *_) in enumerate(rows)}
    shape = {path: (s, f, d) for path, s, f, d, _ in rows}
    flat = [(path, weights._fill(fold[path], s, f, d).astype(jnp.float32))
            for path, s, f, d, st in rows if not st]
    p = weights._nest(flat)

    def expert(j):
        def one(name):
            s, f, d = shape[("moe", name)]
            return weights._fill(jax.random.fold_in(fold[("moe", name)], j),
                                 s[1:], f, d).astype(jnp.float32)
        return one("w1"), one("w3"), one("w2")

    B, T, d = x.shape
    x = jax.lax.map(lambda row: _attention(c, precision, leave_out, p, row), x)
    if "mlp" in p:
        return _dense(c, precision, p, x.reshape(B * T, d)).reshape(B, T, d), jnp.int32(0)
    y, ties = _moe(c, precision, leave_out, p, expert, x.reshape(B * T, d))
    return y.reshape(B, T, d), ties


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _head_gaps(eps, spec, precision, low, key, x, x_low, rows, toks):
    """Per served token, how far its float32 logit lies below the float32
    best, a block of rows at a time: x [B, T, d], rows, toks [B, N] -> [B, N].
    With ``low`` the token that ``x_low`` under the control puts first takes
    the served token's place."""
    head = weights.one_leaf(key, spec, ("lm_head",)).astype(jnp.float32)
    scale = weights.one_leaf(key, spec, ("norm", "scale"))
    B, N = rows.shape
    nb = N // ROW_BLOCK

    def block(args):
        r, t = args                                          # [B, ROW_BLOCK]
        h = _rms(jnp.take_along_axis(x, r[:, :, None], 1), scale, eps)
        ref = matmul(h, head.T, "f32")
        if low:
            hl = _rms(jnp.take_along_axis(x_low, r[:, :, None], 1), scale, eps)
            t = jnp.argmax(matmul(hl, head.T, precision), -1)
        at = jnp.take_along_axis(ref, t[:, :, None], -1)[..., 0]
        return jnp.max(ref, -1) - at

    split = lambda a: a.reshape(B, nb, ROW_BLOCK).transpose(1, 0, 2)
    out = jax.lax.map(block, (split(rows), split(toks)))
    return out.transpose(1, 0, 2).reshape(B, N)


def _hidden(cfg, seed, ids, precision, leave_out=()):
    """Hidden states [B, T, d] before the final norm, and the share of
    (token, expert layer) pairs that are near ties of the router."""
    spec = tuple(param_spec(cfg))
    c_items = tuple(sorted(_c(cfg).items()))
    key = weights.base_key(seed)
    embed = jax.jit(lambda k: weights.one_leaf(k, spec, ("embed_tokens",)))(key)
    x = embed[ids].astype(jnp.float32)
    del embed
    ties, expert_layers = 0, 0
    for l in range(cfg["num_hidden_layers"]):
        rows = _layer_rows(spec, l)
        crcs = jnp.asarray([_crc((f"layers_{l}",) + path) for path, *_ in rows], jnp.int32)
        x, t = _layer(c_items, rows, precision, tuple(leave_out), key, crcs, x)
        ties += int(t)
        expert_layers += l >= cfg["first_k_dense_replace"]
    return spec, key, x, ties / max(x.shape[0] * x.shape[1] * expert_layers, 1)


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
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        ids, rows, toks = jnp.asarray(ids), jnp.asarray(rows), jnp.asarray(toks)
        spec, key, x, ties = _hidden(cfg, seed, ids, "f32")
        print(f"reference kanana2: the router's chosen set changes under bfloat16 rounding "
              f"of its input in {100 * ties:.3f} % of (token, expert layer) pairs", flush=True)
        out["served"] = _head_gaps(eps, spec, "f32", False, key, x, x, rows, toks)
        for control in controls:
            precision, leave_out = control, ()
            if control.startswith("without:"):
                precision, leave_out = "f32", (control.split(":", 1)[1],)
                if leave_out[0] not in TERMS:
                    raise ValueError(f"unknown term {leave_out[0]!r}; known: {TERMS}")
            x_low = _hidden(cfg, seed, ids, precision, leave_out)[2]
            out[control] = _head_gaps(eps, spec, precision, True, key, x, x_low, rows, toks)
    return {name: np.asarray(g)[valid].tolist() for name, g in out.items()}


def served_token_gaps(cfg, seed, prompts, outputs, pad_to, max_new, low_precision=None):
    """``serve.Driver._gaps``'s call: the served tokens' gaps, or with
    ``low_precision`` that control's (``gaps`` has both from one float32
    forward). Returns a flat list."""
    got = gaps(cfg, seed, prompts, outputs, pad_to, max_new,
               (low_precision,) if low_precision else ())
    return got[low_precision or "served"]
