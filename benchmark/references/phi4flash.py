"""Plain reference for Phi-4-mini-flash-reasoning (SambaY, arXiv:2507.06607):
the full forward pass in straightforward ``jax.numpy`` float32, a ``lax.scan``
for the recurrent state, full masked attention, no cache, no kernel.

Every layer ``l`` is ``x <- x + Mixer_l(LN1(x)); x <- x + MLP(LN2(x))`` with
LayerNorm (bias), ``MLP(u) = W_down(SiLU(g) * p)``, ``[g; p] = W_gate_up u``; a
final LayerNorm and the tied head follow. No positional encoding. By layer of
``L`` (32 published), ``half = L / 2``: even ``l < half`` Mamba-1; odd
``l < half`` differential attention within the last ``sliding_window``
positions; ``half`` Mamba-1 that also hands on its scan output ``y`` (before
the gate) as the memory; ``half + 1`` full differential attention whose K and
V every later odd layer (cross) reads again with its own queries; every later
even layer a gated memory unit ``W_2 (memory * SiLU(W_1 u))``.

Departures from the published code, each a fixed permutation of columns under
seeded weights: the two heads of a differential pair are adjacent (q heads
2j, 2j+1; k, v heads 2g, 2g+1, pair j reads g = j // 2); ``A_log`` and the
scan's state are kept ``[d_state, d_inner]`` and the convolution kernel
``[d_conv, d_inner]``. Sizes the published config lacks are the family's
(``assumed`` in the configuration file).

Weights are regenerated from the seed (float32 copies of the bfloat16 values
the configuration serves), one stacked period at a time; nothing the program
made is read. Attention runs in blocks of queries and the head in blocks of
rows, so that a 16k-token request fits the chip beside nothing else.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights
from benchmark.references.common import HIGHEST, matmul

Q_BLOCK = 1024        # queries a block of attention
ROW_BLOCK = 512       # rows a block of the head


def _model_cfg(cfg):
    from types import SimpleNamespace
    a = cfg.get("assumed", {}).get("sizes", {})
    d = cfg["hidden_size"]
    ns = SimpleNamespace(
        vocab_size=cfg["vocab_size"], hidden_size=d,
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        sliding_window=cfg["sliding_window"],
        layer_norm_eps=cfg["layer_norm_eps"],
        mamba_d_state=a.get("mamba_d_state", 16),
        mamba_d_conv=a.get("mamba_d_conv", 4),
        mamba_expand=a.get("mamba_expand", 2),
        mamba_dt_rank=a.get("mamba_dt_rank", -(-d // 16)),
        subln_eps=a.get("subln_eps", 1e-5), dtype=jnp.bfloat16)
    ns.head_dim = d // ns.num_attention_heads
    ns.d_inner = ns.mamba_expand * d
    ns.front_periods = ns.num_hidden_layers // 4
    ns.back_periods = ns.num_hidden_layers // 4 - 1
    return ns


def param_spec(cfg):
    """The parameter tree as the program's ``Phi4FlashForCausalLM`` holds it
    (``deepspeed_tpu/models/phi4flash.py`` is the one place that lists it):
    matrices bfloat16, norms, biases and the scan's constants float32."""
    from deepspeed_tpu.models.phi4flash import param_spec as model_spec
    return model_spec(_model_cfg(cfg), jnp.bfloat16)


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _f32(t):
    return t.astype(jnp.float32)


def _mlp(c, precision, p, x):
    h = _ln(x, p["ln2"], c.layer_norm_eps)
    g, u = jnp.split(matmul(h, _f32(p["mlp"]["gate_up_proj"]["kernel"]), precision), 2, -1)
    return x + matmul(jax.nn.silu(g) * u, _f32(p["mlp"]["down_proj"]["kernel"]), precision)


def _mamba(c, precision, p, x, state_dtype=jnp.float32):
    """x [T, d] -> (x, y [T, d_inner]); the state starts from zero."""
    m = p["mixer"]
    N, R, K = c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv
    T = x.shape[0]
    u = _ln(x, p["ln1"], c.layer_norm_eps)
    a, z = jnp.split(matmul(u, _f32(m["in_proj"]["kernel"]), precision), 2, -1)
    ext = jnp.concatenate([jnp.zeros((K - 1, a.shape[1]), a.dtype), a], 0)
    w = m["conv"]["kernel"]
    cx = jax.nn.silu(sum(ext[i:i + T] * w[i] for i in range(K)) + m["conv"]["bias"])
    dbc = matmul(cx, _f32(m["x_proj"]["kernel"]), precision)
    dt, B, C = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    delta = jax.nn.softplus(matmul(dt, _f32(m["dt_proj"]["kernel"]), precision)
                            + m["dt_proj"]["bias"])
    A = -jnp.exp(m["A_log"])                                   # [N, Di]

    def step(h, xs):
        c_t, d_t, b_t, c_out = xs
        h = jnp.exp(d_t[None, :] * A) * h.astype(jnp.float32) \
            + (d_t * c_t)[None, :] * b_t[:, None]
        y = jnp.sum(h * c_out[:, None], 0) + m["D"] * c_t
        return h.astype(state_dtype), y

    _, y = jax.lax.scan(step, jnp.zeros(A.shape, state_dtype), (cx, delta, B, C))
    out = matmul(y * jax.nn.silu(z), _f32(m["out_proj"]["kernel"]), precision)
    return _mlp(c, precision, p, x + out), y


def _gmu(c, precision, p, x, memory):
    m = p["mixer"]
    u = _ln(x, p["ln1"], c.layer_norm_eps)
    g = jax.nn.silu(matmul(u, _f32(m["in_proj"]["kernel"]), precision))
    return _mlp(c, precision, p, x + matmul(memory * g, _f32(m["out_proj"]["kernel"]),
                                            precision))


def _project_kv(c, precision, p, x):
    """The K and V of a self-attention layer: [T, KV, Dh] each."""
    H, KV, dh = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    m = p["mixer"]
    u = _ln(x, p["ln1"], c.layer_norm_eps)
    w, b = _f32(m["qkv_proj"]["kernel"]), m["qkv_proj"]["bias"]
    kv = matmul(u, w[:, H * dh:], precision) + b[H * dh:]
    T = x.shape[0]
    return kv[:, :KV * dh].reshape(T, KV, dh), kv[:, KV * dh:].reshape(T, KV, dh)


def _diff_attention(c, precision, p, x, k, v, layer, window):
    """Differential attention of layer ``layer`` over x [T, d] against K, V
    [T, KV, Dh] (its own, or the full layer's for a cross layer)."""
    H, dh = c.num_attention_heads, c.head_dim
    m = p["mixer"]
    T = x.shape[0]
    u = _ln(x, p["ln1"], c.layer_norm_eps)
    w, b = _f32(m["qkv_proj"]["kernel"]), m["qkv_proj"]["bias"]
    q = (matmul(u, w[:, :H * dh], precision) + b[:H * dh]).reshape(T, H // 2, 2, dh)
    G = k.shape[1] // 2
    k = k.reshape(T, G, 2, dh)
    v2 = v.reshape(T, G, 2 * dh)                    # [V1_g, V2_g] side by side
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))   # layer may be traced
    lam = jnp.exp(jnp.sum(m["lambda_q1"] * m["lambda_k1"])) \
        - jnp.exp(jnp.sum(m["lambda_q2"] * m["lambda_k2"])) + lam0
    qg = q.reshape(T, G, 2, 2, dh)                  # pair j = 2g + r reads group g
    pos = jnp.arange(T)

    def block(q_blk, q_pos):
        # scores [G, r, i, Tq, T]: half i of pair (g, r) against K_i of g
        s = jnp.einsum("tgrid,sgid->grits", q_blk, k,
                       precision=HIGHEST) / math.sqrt(dh)
        seen = pos[None, :] <= q_pos[:, None]
        if window is not None:
            seen = seen & (pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(seen, s, -jnp.inf)
        o = jnp.einsum("grits,sge->tgrie", jax.nn.softmax(s, -1), v2, precision=HIGHEST)
        d = o[..., 0, :] - lam * o[..., 1, :]       # [Tq, G, r, 2dh]
        d = d * jax.lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + c.subln_eps)
        return d * m["subln"]["scale"] * (1.0 - lam0)

    nb = -(-T // Q_BLOCK)
    if nb == 1:
        d = block(qg, pos)
    else:
        pad = nb * Q_BLOCK - T
        qp = jnp.pad(qg, ((0, pad),) + ((0, 0),) * 4).reshape((nb, Q_BLOCK) + qg.shape[1:])
        pp = jnp.pad(pos, (0, pad), constant_values=T - 1).reshape(nb, Q_BLOCK)
        d = jax.lax.map(lambda a: block(*a), (qp, pp))
        d = d.reshape((nb * Q_BLOCK,) + d.shape[2:])[:T]
    out = matmul(d.reshape(T, H * dh), _f32(m["out_proj"]["kernel"]), precision) \
        + m["out_proj"]["bias"]
    return _mlp(c, precision, p, x + out)


def forward_one(c, precision, tree, x, state_dtype=jnp.float32):
    """All layers over one sequence's embeddings x [T, d] -> hidden [T, d]
    before the final norm; ``tree`` a whole float32-or-bfloat16 tree."""
    half = c.num_hidden_layers // 2

    def front(x, xs):
        p, i = xs
        x, _ = _mamba(c, precision, p["mamba"], x, state_dtype)
        k, v = _project_kv(c, precision, p["window"], x)
        return _diff_attention(c, precision, p["window"], x, k, v, 2 * i + 1,
                               c.sliding_window), None

    x, _ = jax.lax.scan(front, x, (tree["front"], jnp.arange(c.front_periods)))
    x, memory = _mamba(c, precision, tree["middle_mamba"], x, state_dtype)
    k, v = _project_kv(c, precision, tree["full"], x)
    x = _diff_attention(c, precision, tree["full"], x, k, v, half + 1, None)

    def back(x, xs):
        p, i = xs
        x = _gmu(c, precision, p["gmu"], x, memory)
        return _diff_attention(c, precision, p["cross"], x, k, v,
                               half + 3 + 2 * i, None), None

    x, _ = jax.lax.scan(back, x, (tree["back"], jnp.arange(c.back_periods)))
    return x


def full_logits(cfg, tree, ids, precision="f32", state_dtype=jnp.float32):
    """Logits [T, V] of one sequence of token ids from a whole parameter
    tree: the tests' oracle at small sizes (the chip's comparison regenerates
    the weights instead and gathers rows, below)."""
    c = _model_cfg(cfg)
    with jax.default_matmul_precision("highest"):
        tree = jax.tree.map(_f32, tree)
        x = forward_one(c, precision, tree, tree["embed_tokens"][ids], state_dtype)
        x = _ln(x, tree["final_layernorm"], c.layer_norm_eps)
        return matmul(x, tree["embed_tokens"].T, precision)


# -- the chip's comparison: weights from the seed, a stage at a time ---------

def _subtree(key, spec, prefix, layer=None):
    """The float32 leaves under ``prefix``; of stacked ones, period ``layer``."""
    flat = [(p[len(prefix):], weights.leaf(key, p, s, f, d, st, layer if st else None))
            for p, s, f, d, st in spec if p[:len(prefix)] == prefix]
    return jax.tree.map(_f32, weights._nest(flat))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _front_period(c_items, spec, precision, key, i, x):
    c = _ns(c_items)
    p = {"mamba": _subtree(key, spec, ("front", "mamba"), i),
         "window": _subtree(key, spec, ("front", "window"), i)}

    def one(row):
        y, _ = _mamba(c, precision, p["mamba"], row)
        k, v = _project_kv(c, precision, p["window"], y)
        return _diff_attention(c, precision, p["window"], y, k, v, 2 * i + 1,
                               c.sliding_window)
    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _middle(c_items, spec, precision, key, x):
    c = _ns(c_items)
    half = c.num_hidden_layers // 2
    pm, pf = _subtree(key, spec, ("middle_mamba",)), _subtree(key, spec, ("full",))

    def one(row):
        y, memory = _mamba(c, precision, pm, row)
        k, v = _project_kv(c, precision, pf, y)
        return _diff_attention(c, precision, pf, y, k, v, half + 1, None), memory, k, v
    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _back_period(c_items, spec, precision, key, i, x, memory, k, v):
    c = _ns(c_items)
    half = c.num_hidden_layers // 2
    pg = _subtree(key, spec, ("back", "gmu"), i)
    pc = _subtree(key, spec, ("back", "cross"), i)

    def one(args):
        row, mem, k1, v1 = args
        y = _gmu(c, precision, pg, row, mem)
        return _diff_attention(c, precision, pc, y, k1, v1, half + 3 + 2 * i, None)
    return jax.lax.map(one, (x, memory, k, v))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _head_gaps(c_items, spec, precision, low, key, x, x_low, rows, toks):
    """Per served token, how far its float32 logit lies below the float32
    best, a block of rows at a time: x [B, T, d], rows, toks [B, N] -> [B, N].
    With ``low`` the token that ``x_low`` under the low precision puts first
    takes the served token's place."""
    c = _ns(c_items)
    embed = weights.one_leaf(key, spec, ("embed_tokens",)).astype(jnp.float32)
    norm = _subtree(key, spec, ("final_layernorm",))
    B, N = rows.shape
    nb = N // ROW_BLOCK

    def block(args):
        r, t = args                                          # [B, ROW_BLOCK]
        h = _ln(jnp.take_along_axis(x, r[:, :, None], 1), norm, c.layer_norm_eps)
        ref = matmul(h, embed.T, "f32")
        if low:
            hl = _ln(jnp.take_along_axis(x_low, r[:, :, None], 1), norm, c.layer_norm_eps)
            t = jnp.argmax(matmul(hl, embed.T, precision), -1)
        at = jnp.take_along_axis(ref, t[:, :, None], -1)[..., 0]
        return jnp.max(ref, -1) - at

    split = lambda a: a.reshape(B, nb, ROW_BLOCK).transpose(1, 0, 2)
    out = jax.lax.map(block, (split(rows), split(toks)))
    return out.transpose(1, 0, 2).reshape(B, N)


def _ns(c_items):
    from types import SimpleNamespace
    return SimpleNamespace(**dict(c_items))


def _hidden(cfg, seed, ids, precision):
    c = _model_cfg(cfg)
    c_items = tuple(sorted((k, v) for k, v in vars(c).items() if k != "dtype"))
    spec = tuple(param_spec(cfg))
    key = weights.base_key(seed)
    embed = jax.jit(lambda k: weights.one_leaf(k, spec, ("embed_tokens",)))(key)
    x = embed[ids].astype(jnp.float32)
    del embed
    for i in range(c.front_periods):
        x = _front_period(c_items, spec, precision, key, jnp.int32(i), x)
    x, memory, k, v = _middle(c_items, spec, precision, key, x)
    for i in range(c.back_periods):
        x = _back_period(c_items, spec, precision, key, jnp.int32(i), x, memory, k, v)
    return c_items, spec, key, x


def served_token_gaps(cfg, seed, prompts, outputs, pad_to, max_new, low_precision=None):
    """For each request (prompt, served output tokens): how far each served
    token's float32-reference logit lies below the reference's best at that
    position. With ``low_precision`` the token that this precision puts first
    takes the served token's place (the control). Returns a flat list."""
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
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids)
        c_items, spec, key, x = _hidden(cfg, seed, ids, "f32")
        x_low = _hidden(cfg, seed, ids, low_precision)[3] if low_precision else x
        gaps = np.asarray(_head_gaps(c_items, spec, low_precision or "f32",
                                     low_precision is not None, key, x, x_low,
                                     jnp.asarray(rows), jnp.asarray(toks)))
    return gaps[valid].tolist()
