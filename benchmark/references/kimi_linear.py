"""Plain reference for Kimi-Linear (moonshotai Kimi-Linear-48B-A3B-Instruct,
``model_type`` ``kimi_linear``, arXiv:2510.26692): the full forward pass in
straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``; the linear-attention state TOKEN
BY TOKEN (a ``lax.scan`` over the recurrence, never a chunk form), full masked
attention with every head's keys and values materialised, the expert layer a
plain sum over the experts held; no cache, no slots, no batching, no kernel.
It imports nothing of ``deepspeed_tpu``.

The layers, from the published ``config.json`` (the configuration file keeps
its keys). ``x`` [T, 2304]; RMSNorm eps 1e-5; ``x = x + Mixer_l(norm_in_l(x))``,
then ``x = x + FFN_l(norm_post_l(x))``; final RMSNorm; untied head. 0-based
layer ``i`` is MLA if ``i + 1`` is in ``linear_attn_config.full_attn_layers``,
else KDA; layer 0's FFN is a SwiGLU of 9216, every other layer's the experts.

- KDA, 32 heads of 128 (key and value alike), head ``j``: ``q = l2norm(silu(
  conv4(h W_q))) x 128^-0.5``, ``k = l2norm(silu(conv4(h W_k)))``, ``v =
  silu(conv4(h W_v))`` (``conv4``: depthwise causal convolution of 4 taps a
  channel, no bias, ``y_t = sum_i w[i] x_{t-3+i}``; ``l2norm`` over the head's
  128 columns, eps 1e-6); ``g_t = -exp(A_log[j]) softplus(W_f_up (W_f_down h_t)
  + dt_bias)[j]`` in R^128; ``b_t = sigmoid(h_t W_b)[j]``; ``S_t = (I - b_t k_t
  k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T`` from ``S_0 = 0``; ``o_t =
  S_t^T q_t``; ``y_t = W_o [rmsnorm_head(o_t; scale[128]) sigmoid(W_g_up
  (W_g_down h_t) + bias_g)[j]]``.
- MLA: Kanana-2's with NO rotary anywhere (``mla_use_nope``): ``q = h W_q`` ->
  32 x (128 | 64), ``ckv = h W_kv_a`` -> 512 | 64, ``c = rmsnorm(ckv[:512])``,
  ``[k_nope | v] = c W_kv_b`` a head, scores ``(q_nope . k_nope + q_pe . k_pe) x
  192^-0.5``, causal softmax, ``o_proj`` over 32 x 128.
- Experts: ``s = sigmoid(h W_r)`` over all 256; chosen = the 8 largest of ``s +
  e_score_correction_bias``; weights ``s_e / (sum of the 8 + 1e-20) x 2.446``;
  ``y = sum_{e chosen and held} w_e SwiGLU_e(h) + SwiGLU_shared(h)``. The
  configuration file's ``experts_held`` (``first``, ``count``) is the share
  held, ``num_experts_published`` the router's width, ``num_experts`` the
  count held. Without those keys every expert is held.

``leave_out`` names what a control changes, to show that the comparison sees
it: ``decay`` (``a_t = 1``: the state forgets nothing), ``nope`` (rotary
applied to q_pe and k_pe, theta 10000: the positions the model does not
have), ``beta`` (``b_t = 1``), ``bias`` (selection by the unbiased scores),
``routed_scale``.

Weights are regenerated from the seed (float32 copies of the bfloat16 values
the configuration serves; ``A_log`` and ``dt_bias`` mapped from their raw
draws, ``gate_leaves``), one layer's and ONE EXPERT's at a time; nothing the
program made is read. Attention runs in blocks of queries and the head in
blocks of rows, so that a 32k-token request fits the chip.
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
TERMS = ("decay", "nope", "beta", "bias", "routed_scale")


def router_width(cfg):
    return cfg.get("num_experts_published", cfg["num_experts"])


def held(cfg):
    """``(first, count)`` of the router's experts this share holds."""
    share = cfg.get("experts_held")
    if not share:
        return 0, cfg["num_experts"]
    if share["count"] != cfg["num_experts"]:
        raise ValueError("num_experts counts the experts held")
    return share["first"], share["count"]


def is_mla(cfg, l):
    return l + 1 in cfg["linear_attn_config"]["full_attn_layers"]


def gate_leaves(a_log_raw, dt_bias_raw):
    """``A_log`` = log of uniform(1, 16) and ``dt_bias`` = the inverse softplus
    of log-uniform(1e-3, 1e-1), from draws uniform in (-1, 1)."""
    a_log = jnp.log(8.5 + 7.5 * a_log_raw.astype(jnp.float32))
    dt = jnp.exp(math.log(1e-3) + (dt_bias_raw.astype(jnp.float32) + 1.0)
                 * 0.5 * math.log(100.0))
    return a_log, dt + jnp.log(-jnp.expm1(-dt))


def param_spec(cfg):
    """The parameter tree as the program's ``KimiLinearForCausalLM`` holds it
    (a tier-1 test holds the two lists equal): one subtree a layer, matrices
    bfloat16, norm scales, convolutions, the decay's leaves and the router's
    bias float32, a layer's HELD experts stacked ``[count, ...]``. ``A_log``
    and ``dt_bias`` are RAW draws in (-1, 1): ``finish`` maps them."""
    d, V, L, H = (cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"],
                  cfg["num_attention_heads"])
    r, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    lin = cfg["linear_attn_config"]
    Hk, dk, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    W = Hk * dk
    E, (_, count), F = router_width(cfg), held(cfg), cfg["moe_intermediate_size"]
    Fs = cfg["num_shared_experts"] * F
    bf, f32 = jnp.bfloat16, jnp.float32
    one, zero, raw = ("const", 1.0), ("const", 0.0), 1 / math.sqrt(3.0)
    rows = [(("embed_tokens",), (V, d), 0.02, bf, False),
            (("lm_head",), (V, d), 0.02, bf, False),
            (("norm", "scale"), (d,), one, f32, False)]
    for l in range(L):
        at = lambda *p: (f"layers_{l}",) + p
        mat = lambda path, i, o: (at(*path), (i, o), 1 / math.sqrt(i), bf, False)
        rows += [(at("input_layernorm", "scale"), (d,), one, f32, False),
                 (at("post_attention_layernorm", "scale"), (d,), one, f32, False)]
        if is_mla(cfg, l):
            rows += [
                mat(("self_attn", "q_proj", "kernel"), d, H * (dn + dr)),
                mat(("self_attn", "kv_a_proj", "kernel"), d, r + dr),
                (at("self_attn", "kv_a_layernorm", "scale"), (r,), one, f32, False),
                mat(("self_attn", "kv_b_proj", "kernel"), r, H * (dn + dv)),
                mat(("self_attn", "o_proj", "kernel"), H * dv, d)]
        else:
            kda = lambda *p: at("self_attn", *p)
            rows += [
                mat(("self_attn", "q_proj", "kernel"), d, W),
                mat(("self_attn", "k_proj", "kernel"), d, W),
                mat(("self_attn", "v_proj", "kernel"), d, W),
                (kda("q_conv", "kernel"), (K, W), 1 / math.sqrt(K), f32, False),
                (kda("k_conv", "kernel"), (K, W), 1 / math.sqrt(K), f32, False),
                (kda("v_conv", "kernel"), (K, W), 1 / math.sqrt(K), f32, False),
                mat(("self_attn", "f_a_proj", "kernel"), d, dk),
                mat(("self_attn", "f_b_proj", "kernel"), dk, W),
                (kda("dt_bias"), (W,), raw, f32, False),
                (kda("A_log"), (Hk,), raw, f32, False),
                mat(("self_attn", "b_proj", "kernel"), d, Hk),
                mat(("self_attn", "g_a_proj", "kernel"), d, dk),
                mat(("self_attn", "g_b_proj", "kernel"), dk, W),
                (kda("g_b_proj", "bias"), (W,), zero, f32, False),
                (kda("o_norm", "scale"), (dk,), one, f32, False),
                mat(("self_attn", "o_proj", "kernel"), W, d)]
        if l < cfg["first_k_dense_replace"]:
            rows += [mat(("mlp", "gate_proj", "kernel"), d, cfg["intermediate_size"]),
                     mat(("mlp", "up_proj", "kernel"), d, cfg["intermediate_size"]),
                     mat(("mlp", "down_proj", "kernel"), cfg["intermediate_size"], d)]
            continue
        rows += [
            mat(("moe", "router", "kernel"), d, E),
            (at("moe", "router", "bias"), (E,), 0.02, f32, False),
            (at("moe", "w1"), (count, d, F), 1 / math.sqrt(d), bf, True),
            (at("moe", "w3"), (count, d, F), 1 / math.sqrt(d), bf, True),
            (at("moe", "w2"), (count, F, d), 1 / math.sqrt(F), bf, True),
            mat(("moe", "shared", "w1"), d, Fs),
            mat(("moe", "shared", "w3"), d, Fs),
            mat(("moe", "shared", "w2"), Fs, d)]
    return rows


def _finish_attn(attn):
    """A KDA layer's attention subtree with ``A_log`` and ``dt_bias`` mapped
    from their raw draws; an MLA layer's as it is."""
    if "A_log" not in attn:
        return attn
    a_log, dt_bias = gate_leaves(attn["A_log"], attn["dt_bias"])
    return dict(attn, A_log=a_log, dt_bias=dt_bias)


def finish(tree):
    """A whole tree filled from ``param_spec``'s rows as the program serves
    it: every KDA layer's ``A_log`` and ``dt_bias`` mapped (the driver calls
    this on the tree ``weights`` made)."""
    return {name: dict(sub, self_attn=_finish_attn(sub["self_attn"]))
            if name.startswith("layers_") else sub for name, sub in tree.items()}


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotary(x, theta):
    """x [T, heads, dr] at positions 0..T-1, adjacent pairs (the ``nope``
    control alone applies it)."""
    dr = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def _mla(c, precision, leave_out, p, x, q_block=Q_BLOCK):
    """x [T, d] -> x + MLA(RMSNorm(x)) for one sequence: every head's keys
    and values up-projected from the latent, no positions."""
    T = x.shape[0]
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    eps = c["rms_norm_eps"]
    a = p["self_attn"]
    h = _rms(x, p["input_layernorm"]["scale"], eps)
    q = matmul(h, a["q_proj"]["kernel"], precision).reshape(T, H, dn + dr)
    ckv = matmul(h, a["kv_a_proj"]["kernel"], precision)
    latent = _rms(ckv[:, :r], a["kv_a_layernorm"]["scale"], eps)
    kv = matmul(latent, a["kv_b_proj"]["kernel"], precision).reshape(T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_nope, q_pe, k_pe = q[..., :dn], q[..., dn:], ckv[:, r:]  # k_pe: one head
    if "nope" in leave_out:
        q_pe = _rotary(q_pe, float(c["rope_theta"]))
        k_pe = _rotary(k_pe[:, None], float(c["rope_theta"]))[:, 0]
    pos = jnp.arange(T)

    def block(qn, qp, q_pos):
        s = jnp.einsum("thd,shd->hts", qn, k_nope, precision=HIGHEST) \
            + jnp.einsum("thr,sr->hts", qp, k_pe, precision=HIGHEST)
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


def _conv(x, w):
    """Depthwise causal convolution: x [T, C], w [K, C] -> ``y_t = sum_i w[i]
    x_{t-(K-1)+i}`` with zeros before the sequence."""
    K, T = w.shape[0], x.shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], 0)
    return sum(ext[i:i + T] * w[i] for i in range(K))


def _kda(c, precision, leave_out, p, x):
    """x [T, d] -> x + KDA(RMSNorm(x)) for one sequence, the state token by
    token from zero."""
    T = x.shape[0]
    H, dk = c["kda_heads"], c["kda_head_dim"]
    a = p["self_attn"]
    h = _rms(x, p["input_layernorm"]["scale"], c["rms_norm_eps"])
    proj = lambda name: matmul(h, a[name]["kernel"], precision)
    heads = lambda t: t.reshape(T, H, dk)
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    mixed = lambda name: heads(jax.nn.silu(
        _conv(proj(name + "_proj"), a[name + "_conv"]["kernel"])))
    q, k, v = unit(mixed("q")) * dk ** -0.5, unit(mixed("k")), mixed("v")
    f = matmul(proj("f_a_proj"), a["f_b_proj"]["kernel"], precision) + a["dt_bias"]
    g = -jnp.exp(a["A_log"])[:, None] * heads(jax.nn.softplus(f))       # [T, H, dk]
    if "decay" in leave_out:
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(proj("b_proj"))                                # [T, H]
    if "beta" in leave_out:
        beta = jnp.ones_like(beta)

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[:, :, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hcd,hc->hd", S, k_t, precision=HIGHEST))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hcd,hc->hd", S, q_t, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dk), jnp.float32), (q, k, v, g, beta))
    gate = jax.nn.sigmoid(
        matmul(proj("g_a_proj"), a["g_b_proj"]["kernel"], precision) + a["g_b_proj"]["bias"])
    o = _rms(o, a["o_norm"]["scale"], c["rms_norm_eps"]).reshape(T, H * dk) * gate
    return x + matmul(o, a["o_proj"]["kernel"], precision)


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
    k, scale = c["num_experts_per_token"], c["routed_scaling_factor"]
    s = jax.nn.sigmoid(matmul(h, p["moe"]["router"]["kernel"], precision))
    select = s if "bias" in leave_out else s + p["moe"]["router"]["bias"]
    _, idx = jax.lax.top_k(select, k)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    if "routed_scale" not in leave_out:
        w = w * scale
    gate = jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], idx].set(w)
    return gate, idx


def _moe(c, precision, leave_out, p, expert, x, shared=True):
    """x [N, d] (any tokens, each alone) -> (x + MoE(RMSNorm(x)), near ties).
    ``expert(j)`` gives the float32 ``(w1, w3, w2)`` of the ``j``-th expert
    HELD, which is the router's expert ``first + j``; ``shared`` False leaves
    the shared expert out (a share that is not the one counting it). Near
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
    if shared:
        sh = p["moe"]["shared"]
        y = y + _swiglu(h, sh["w1"], sh["w3"], sh["w2"], precision)
    return x + y, ties


def _f32(t):
    return jax.tree.map(lambda a: a.astype(jnp.float32), t)


def _c(cfg):
    """The keys the layers read."""
    c = {k: cfg[k] for k in (
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rms_norm_eps", "rope_theta", "num_experts_per_token",
        "routed_scaling_factor", "first_k_dense_replace")}
    c["kda_heads"] = cfg["linear_attn_config"]["num_heads"]
    c["kda_head_dim"] = cfg["linear_attn_config"]["head_dim"]
    c["held"] = held(cfg)
    return c


def full_logits(cfg, tree, ids, precision="f32", leave_out=(), q_block=Q_BLOCK):
    """Logits [T, V] of one sequence of token ids from a whole parameter
    tree as the program serves it (``finish`` applied): the tests' oracle at
    small sizes (the chip's comparison regenerates the weights instead and
    gathers rows, below)."""
    c = _c(cfg)
    with jax.default_matmul_precision("highest"):
        x = tree["embed_tokens"].astype(jnp.float32)[ids]
        for l in range(cfg["num_hidden_layers"]):
            p = _f32(tree[f"layers_{l}"])
            mixer = _mla if is_mla(cfg, l) else _kda
            x = mixer(c, precision, leave_out, p, x, *((q_block,) if mixer is _mla else ()))
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
    (alike for every layer of its kind: KDA + dense, KDA + experts, MLA +
    experts) and ``crcs`` that layer's leaf keys' folds, in the rows' order:
    ``weights.leaf``'s values, with the layer traced."""
    c = dict(c_items)
    fold = {path: jax.random.fold_in(key, crcs[i]) for i, (path, *_) in enumerate(rows)}
    shape = {path: (s, f, d) for path, s, f, d, _ in rows}
    flat = [(path, weights._fill(fold[path], s, f, d).astype(jnp.float32))
            for path, s, f, d, st in rows if not st]
    p = weights._nest(flat)
    p["self_attn"] = _finish_attn(p["self_attn"])

    def expert(j):
        def one(name):
            s, f, d = shape[("moe", name)]
            return weights._fill(jax.random.fold_in(fold[("moe", name)], j),
                                 s[1:], f, d).astype(jnp.float32)
        return one("w1"), one("w3"), one("w2")

    B, T, d = x.shape
    mixer = _kda if "A_log" in p["self_attn"] else _mla
    x = jax.lax.map(lambda row: mixer(c, precision, leave_out, p, row), x)
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
    forward with a term of ``TERMS`` changed). The float32 forward runs
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
        o = o[:max_new]
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
        print(f"reference kimi_linear: the router's chosen set changes under bfloat16 rounding "
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
