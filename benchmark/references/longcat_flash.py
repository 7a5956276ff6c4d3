"""Plain reference for LongCat-Flash-Chat (meituan-longcat; the layer follows
``modeling_longcat_flash.py`` of the source repository): the full forward pass
in straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``, full masked attention with every
head's keys and values materialised, the expert layer as a plain sum over the
experts held plus the identity experts' term; no cache, no absorbed form, no
batching, no kernel. It imports nothing of ``deepspeed_tpu``.

Stream ``x`` [T, 6144], layer ``l``, sub-block ``j`` in {0, 1}; RMSNorm eps
1e-5; no biases but the router's selecting one::

    a0 = x  + MLA[l,0](norm_in[l,0](x))
    h0 = norm_post[l,0](a0)
    m  = MoE[l](h0)                                  # used at the END of the layer
    b0 = a0 + FFN[l,0](h0)                           # SwiGLU, width 12288
    a1 = b0 + MLA[l,1](norm_in[l,1](b0))
    b1 = a1 + FFN[l,1](norm_post[l,1](a1)) + m

- ``MLA(h)``: ``q = q_b(RMSNorm(q_a(h)))`` -> 64 heads of 128 (no position) |
  64 (rotary), ALL of it x ``s_q = sqrt(hidden_size / q_lora_rank)`` = 2; ``ckv
  = kv_a(h)`` -> latent 512 | ``k_pe`` 64; ``c = RMSNorm(latent) x s_kv``, ``s_kv
  = sqrt(hidden_size / kv_lora_rank)`` = sqrt(12); ``[k_nope | v] = kv_b(c)`` a
  head (128 | 128); RoPE (theta 1e7, adjacent pairs, no scaling) on q's 64 and
  on the ONE shared ``k_pe``; scores ``(q_nope . k_nope + q_pe . k_pe) x
  192^-0.5``, causal softmax; ``o_proj`` over 64 x 128.
- ``MoE(h)``: ``p = softmax(h W_r)`` over all 768 columns; chosen = the 12
  largest of ``p + bias`` (``e_score_correction_bias``); weights ``w_e = 6 p_e``
  of the chosen (``routed_scaling_factor``; NOT renormalised, no bias in the
  weight); ``m = sum over chosen e < 512 AND held of w_e SwiGLU_e(h) + (sum
  over chosen e >= 512 of w_e) h``: the router's LAST 256 columns are identity
  experts. No shared expert. The configuration file's ``experts_held``
  (``first``, ``count``) is this chip's share of the 512 real experts, its
  ``n_routed_experts_published`` their count; a chosen real expert that is not
  held adds nothing here, the zero experts are every chip's.

Departures. RoPE pairs adjacent columns (x[0::2], x[1::2]); the published code
(``rope_interleave``) pairs the same columns and writes the halves apart, a
permutation of the output's columns common to q_pe and k_pe that leaves every
score unchanged. The multi-token-prediction module is not computed.

``leave_out`` names terms a control drops, to show that the comparison sees
them: ``zero_experts`` (the identity experts' term), ``k_pe``, ``bias``,
``routed_scale``, ``q_scale``, ``kv_scale``, and ``router_bf16`` (not a term
but a precision: the router's product, softmax and bias in bfloat16).

Weights are regenerated from the seed (float32 copies of the bfloat16 values
the configuration serves), one layer's and ONE EXPERT's at a time; nothing the
program made is read. Attention runs in blocks of queries and the head in
blocks of rows, a sequence at a time, so that a 9k-token request fits the chip.
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
TERMS = ("zero_experts", "k_pe", "bias", "routed_scale", "q_scale", "kv_scale",
         "router_bf16")
ROUTER_BIAS_STD = 3e-5


def real_experts(cfg):
    return cfg.get("n_routed_experts_published", cfg["n_routed_experts"])


def router_width(cfg):
    return real_experts(cfg) + cfg["zero_expert_num"]


def held(cfg):
    """``(first, count)`` of the router's real experts this share holds."""
    share = cfg.get("experts_held")
    if not share:
        return 0, cfg["n_routed_experts"]
    if share["count"] != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held")
    return share["first"], share["count"]


def param_spec(cfg):
    """The parameter tree as the program's ``LongcatFlashForCausalLM`` holds it
    (a tier-1 test holds the two lists equal): one subtree a layer, its two
    attentions, two dense FFNs and four norms named by sub-block, matrices
    bfloat16, norm scales and the router's bias float32, a layer's HELD experts
    stacked ``[count, ...]``, the router over every column. The two inner
    norms' seeded scales are ``1 / s_q`` and ``1 / s_kv`` (the configuration
    file's ``assumed.weights`` says why); the forward applies ``s_q`` and
    ``s_kv`` as published whatever the norms hold."""
    d, V, L, H = (cfg["hidden_size"], cfg["vocab_size"], cfg["num_layers"],
                  cfg["num_attention_heads"])
    r, rq, dn, dr, dv = (cfg["kv_lora_rank"], cfg["q_lora_rank"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    E, (_, count) = router_width(cfg), held(cfg)
    F, Fd = cfg["expert_ffn_hidden_size"], cfg["ffn_hidden_size"]
    bf, f32, one = jnp.bfloat16, jnp.float32, ("const", 1.0)
    scales = _c(cfg)
    rows = [(("embed_tokens",), (V, d), 0.02, bf, False),
            (("lm_head",), (V, d), 0.02, bf, False),
            (("norm", "scale"), (d,), one, f32, False)]
    for l in range(L):
        at = lambda *p: (f"layers_{l}",) + p
        lin = lambda path, i, o: (at(*path), (i, o), 1 / math.sqrt(i), bf, False)
        for j in (0, 1):
            attn, mlp = f"self_attn_{j}", f"mlps_{j}"
            rows += [
                (at(f"input_layernorm_{j}", "scale"), (d,), one, f32, False),
                (at(f"post_attention_layernorm_{j}", "scale"), (d,), one, f32, False),
                lin((attn, "q_a_proj", "kernel"), d, rq),
                (at(attn, "q_a_layernorm", "scale"), (rq,),
                 ("const", 1 / scales["q_scale"]), f32, False),
                lin((attn, "q_b_proj", "kernel"), rq, H * (dn + dr)),
                lin((attn, "kv_a_proj", "kernel"), d, r + dr),
                (at(attn, "kv_a_layernorm", "scale"), (r,),
                 ("const", 1 / scales["kv_scale"]), f32, False),
                lin((attn, "kv_b_proj", "kernel"), r, H * (dn + dv)),
                lin((attn, "o_proj", "kernel"), H * dv, d),
                lin((mlp, "gate_proj", "kernel"), d, Fd),
                lin((mlp, "up_proj", "kernel"), d, Fd),
                lin((mlp, "down_proj", "kernel"), Fd, d)]
        rows += [
            lin(("moe", "router", "kernel"), d, E),
            (at("moe", "router", "bias"), (E,), ROUTER_BIAS_STD, f32, False),
            (at("moe", "w1"), (count, d, F), 1 / math.sqrt(d), bf, True),
            (at("moe", "w3"), (count, d, F), 1 / math.sqrt(d), bf, True),
            (at("moe", "w2"), (count, F, d), 1 / math.sqrt(F), bf, True)]
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


def _attention(c, precision, leave_out, a, h, q_block=Q_BLOCK):
    """h [T, d] (normalised) -> MLA(h) for one sequence: the first form,
    every head's keys and values up-projected from the latent."""
    T = h.shape[0]
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    s_q = 1.0 if "q_scale" in leave_out else c["q_scale"]
    s_kv = 1.0 if "kv_scale" in leave_out else c["kv_scale"]
    q_a = _rms(matmul(h, a["q_a_proj"]["kernel"], precision),
               a["q_a_layernorm"]["scale"], eps)
    q = matmul(q_a, a["q_b_proj"]["kernel"], precision).reshape(T, H, dn + dr) * s_q
    ckv = matmul(h, a["kv_a_proj"]["kernel"], precision)
    latent = _rms(ckv[:, :r], a["kv_a_layernorm"]["scale"], eps) * s_kv
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
    return matmul(o.reshape(T, H * dv), a["o_proj"]["kernel"], precision)


def _swiglu(h, w1, w3, w2, precision):
    return matmul(jax.nn.silu(matmul(h, w1, precision)) * matmul(h, w3, precision),
                  w2, precision)


def _ffn(p, h, precision):
    return _swiglu(h, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                   p["down_proj"]["kernel"], precision)


def router(c, precision, leave_out, p, h):
    """(gate [N, 768]: a chosen column's weight, zero elsewhere; chosen [N, k])
    over ALL the router's columns, real experts (held or not) and zero ones."""
    k, scale = c["moe_topk"], c["routed_scaling_factor"]
    kernel, bias = p["moe"]["router"]["kernel"], p["moe"]["router"]["bias"]
    if "router_bf16" in leave_out:
        bf = jnp.bfloat16
        s = jax.nn.softmax(jnp.dot(h.astype(bf), kernel.astype(bf)), -1)
        select = (s + bias.astype(bf)).astype(jnp.float32)
        s = s.astype(jnp.float32)
    else:
        s = jax.nn.softmax(matmul(h, kernel, precision), -1)
        select = s if "bias" in leave_out else s + bias
    _, idx = jax.lax.top_k(select, k)
    w = jnp.take_along_axis(s, idx, -1)
    if "routed_scale" not in leave_out:
        w = w * scale
    gate = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], idx].set(w)
    return gate, idx


def _moe(c, precision, leave_out, p, expert, h, valid=None):
    """h [N, d] (normalised; any tokens, each alone) -> (MoE(h), counts).
    ``expert(j)`` gives the float32 ``(w1, w3, w2)`` of the ``j``-th expert
    HELD, the router's column ``first + j``. Counts over the ``valid`` tokens
    (None: all), int32 [4]: near ties (tokens whose chosen set changes when
    the router's input is rounded to bfloat16 first, as the served path's
    activations are), rows routed, rows that took a zero expert, rows that
    landed on a held expert."""
    first, count = c["held"]
    E = c["real_experts"]
    gate, idx = router(c, precision, leave_out, p, h)
    other = router(c, "f32", tuple(t for t in leave_out if t != "router_bf16"), p,
                   h.astype(jnp.bfloat16).astype(jnp.float32))[1]
    ok = jnp.ones((h.shape[0],), bool) if valid is None else valid
    ties = jnp.any(jnp.sort(other, -1) != jnp.sort(idx, -1), -1)
    counts = jnp.stack([
        jnp.sum(ties & ok), jnp.sum(ok) * idx.shape[-1],
        jnp.sum((idx >= E) & ok[:, None]),
        jnp.sum((idx >= first) & (idx < first + count) & ok[:, None])]).astype(jnp.int32)

    def add(j, y):
        w1, w3, w2 = expert(j)
        return y + jax.lax.dynamic_slice_in_dim(gate, first + j, 1, 1) \
            * _swiglu(h, w1, w3, w2, precision)

    y = jax.lax.fori_loop(0, count, add, jnp.zeros_like(h))
    if "zero_experts" not in leave_out:
        y = y + jnp.sum(gate[:, E:], -1, keepdims=True) * h
    return y, counts


def _double_layer(c, precision, leave_out, p, expert, x, valid=None, q_block=Q_BLOCK):
    """One sequence x [T, d] through one layer: the module docstring's six
    lines, literally. -> (b1, ``_moe``'s counts)."""
    eps = c["rms_norm_eps"]
    norm = lambda name, v: _rms(v, p[name]["scale"], eps)
    attn = lambda j, v: _attention(c, precision, leave_out, p[f"self_attn_{j}"],
                                   norm(f"input_layernorm_{j}", v), q_block)
    a0 = x + attn(0, x)
    h0 = norm("post_attention_layernorm_0", a0)
    m, counts = _moe(c, precision, leave_out, p, expert, h0, valid)
    b0 = a0 + _ffn(p["mlps_0"], h0, precision)
    a1 = b0 + attn(1, b0)
    b1 = a1 + _ffn(p["mlps_1"], norm("post_attention_layernorm_1", a1), precision) + m
    return b1, counts


def _f32(t):
    return jax.tree.map(lambda a: a.astype(jnp.float32), t)


def _c(cfg):
    """The keys the layers read."""
    c = {k: cfg[k] for k in (
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rms_norm_eps", "rope_theta", "moe_topk", "routed_scaling_factor")}
    c["held"] = held(cfg)
    c["real_experts"] = real_experts(cfg)
    c["q_scale"] = math.sqrt(cfg["hidden_size"] / cfg["q_lora_rank"]) \
        if cfg.get("mla_scale_q_lora", True) else 1.0
    c["kv_scale"] = math.sqrt(cfg["hidden_size"] / cfg["kv_lora_rank"]) \
        if cfg.get("mla_scale_kv_lora", True) else 1.0
    return c


def full_logits(cfg, tree, ids, precision="f32", leave_out=(), q_block=Q_BLOCK):
    """Logits [T, V] of one sequence of token ids from a whole parameter
    tree: the tests' oracle at small sizes (the chip's comparison regenerates
    the weights instead and gathers rows, below)."""
    c = _c(cfg)
    with jax.default_matmul_precision("highest"):
        x = tree["embed_tokens"].astype(jnp.float32)[ids]
        for l in range(cfg["num_layers"]):
            p = _f32(tree[f"layers_{l}"])
            m = p["moe"]
            x, _ = _double_layer(c, precision, leave_out, p,
                                 lambda j, m=m: (m["w1"][j], m["w3"][j], m["w2"][j]),
                                 x, None, q_block)
        x = _rms(x, tree["norm"]["scale"].astype(jnp.float32), cfg["rms_norm_eps"])
        return matmul(x, tree["lm_head"].astype(jnp.float32).T, precision)


def expert_layer(cfg, tree, layer, h, leave_out=()):
    """``(MoE(h), counts)`` of layer ``layer`` of a whole tree on tokens h [N,
    d], each alone: what the shares test sums."""
    p = _f32(tree[f"layers_{layer}"])
    m = p["moe"]
    with jax.default_matmul_precision("highest"):
        return _moe(_c(cfg), "f32", leave_out, p,
                    lambda j: (m["w1"][j], m["w3"][j], m["w2"][j]), h)


# -- the chip's comparison: weights from the seed, a layer and an expert at a time

def _crc(path):
    return zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF


def _layer_rows(spec, l):
    """The rows of layer ``l`` with their paths below ``layers_<l>``."""
    return tuple((p[1:], s, f, d, st) for p, s, f, d, st in spec if p[0] == f"layers_{l}")


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3), donate_argnums=(6,))
def _layer(c_items, rows, precision, leave_out, key, crcs, x, valid):
    """One layer over x [B, T, d], a sequence at a time. ``rows`` are one
    layer's rows of the spec (alike for every layer) and ``crcs`` that layer's
    leaf keys' folds, in the rows' order: ``weights.leaf``'s values, with the
    layer traced. ``valid`` [B, T]: the positions ``_moe`` counts."""
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

    x, counts = jax.lax.map(
        lambda args: _double_layer(c, precision, leave_out, p, expert, *args), (x, valid))
    return x, jnp.sum(counts, 0)


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


def _hidden(cfg, seed, ids, valid, precision, leave_out=()):
    """Hidden states [B, T, d] before the final norm, and ``_moe``'s counts
    summed over layers and the ``valid`` positions."""
    spec = tuple(param_spec(cfg))
    c_items = tuple(sorted(_c(cfg).items()))
    key = weights.base_key(seed)
    embed = jax.jit(lambda k: weights.one_leaf(k, spec, ("embed_tokens",)))(key)
    x = embed[ids].astype(jnp.float32)
    del embed
    counts = np.zeros((4,), np.int64)
    for l in range(cfg["num_layers"]):
        rows = _layer_rows(spec, l)
        crcs = jnp.asarray([_crc((f"layers_{l}",) + path) for path, *_ in rows], jnp.int32)
        x, n = _layer(c_items, rows, precision, tuple(leave_out), key, crcs, x, valid)
        counts += np.asarray(n, np.int64)
    return spec, key, x, dict(zip(("near_ties", "routed_rows", "zero_rows", "held_rows"),
                                  (int(n) for n in counts)))


def gaps(cfg, seed, prompts, outputs, pad_to, max_new, controls=()):
    """{"served": per served token, how far its float32-reference logit lies
    below the reference's best at that position; each control: the same for
    the token that the control puts first there}. A control is ``"int8"`` (every
    matmul fake-quantised) or ``"without:<term>"`` (the float32 forward with a
    term of ``TERMS`` left out). The float32 forward runs once, and a line
    says what share of its routed rows took a zero expert (to lay beside the
    program's device counters). Flat lists over the same (request, position)
    pairs."""
    B = len(prompts)
    # padded to whole blocks of what was served, within the mix's sizes
    up = lambda n, block: -(-n // block) * block
    max_new = min(up(max_new, ROW_BLOCK), up(max(len(o) for o in outputs), ROW_BLOCK))
    pad_to = min(pad_to, up(max(len(p) + len(o) for p, o in zip(prompts, outputs)), Q_BLOCK))
    ids = np.zeros((B, pad_to), np.int32)
    real = np.zeros((B, pad_to), bool)
    rows = np.zeros((B, max_new), np.int32)
    toks = np.zeros((B, max_new), np.int32)
    valid = np.zeros((B, max_new), bool)
    for b, (p, o) in enumerate(zip(prompts, outputs)):
        seq = np.concatenate([p, o[:-1]])
        ids[b, :len(seq)] = seq
        real[b, :len(seq)] = True
        rows[b, :len(o)] = len(p) - 1 + np.arange(len(o))
        toks[b, :len(o)] = o
        valid[b, :len(o)] = True
    out = {}
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        ids, real, rows, toks = (jnp.asarray(a) for a in (ids, real, rows, toks))
        spec, key, x, routing = _hidden(cfg, seed, ids, real, "f32")
        pairs = max(routing["routed_rows"] // cfg["moe_topk"], 1)
        print(f"reference longcat_flash: of {routing['routed_rows']} routed rows "
              f"{100 * routing['zero_rows'] / max(routing['routed_rows'], 1):.3f} % took a zero "
              f"expert and {100 * routing['held_rows'] / max(routing['routed_rows'], 1):.3f} % "
              f"landed on a held one; the router's chosen set changes under bfloat16 rounding "
              f"of its input in {100 * routing['near_ties'] / pairs:.3f} % of (token, expert "
              f"layer) pairs", flush=True)
        out["served"] = _head_gaps(eps, spec, "f32", False, key, x, x, rows, toks)
        for control in controls:
            precision, leave_out = control, ()
            if control.startswith("without:"):
                precision, leave_out = "f32", (control.split(":", 1)[1],)
                if leave_out[0] not in TERMS:
                    raise ValueError(f"unknown term {leave_out[0]!r}; known: {TERMS}")
            x_low = _hidden(cfg, seed, ids, real, precision, leave_out)[2]
            out[control] = _head_gaps(eps, spec, precision, True, key, x, x_low, rows, toks)
    return {name: np.asarray(g)[valid].tolist() for name, g in out.items()}


def served_token_gaps(cfg, seed, prompts, outputs, pad_to, max_new, low_precision=None):
    """``serve.Driver._gaps``'s call: the served tokens' gaps, or with
    ``low_precision`` that control's (``gaps`` has both from one float32
    forward). Returns a flat list."""
    got = gaps(cfg, seed, prompts, outputs, pad_to, max_new,
               (low_precision,) if low_precision else ())
    return got[low_precision or "served"]
