"""Plain reference for the language model of Keye-VL-2.0 (Kwai-Keye
Keye-VL-2.0-30B-A3B, ``model_type`` ``KeyeVL2``): the full forward pass in
straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``: the index scores as an explicit
causal matrix, ``jax.lax.top_k`` for the selected set, a masked softmax over
it, the expert layer as a plain sum over the experts held; no cache, no
batching, no kernel. It imports nothing of ``deepspeed_tpu``.

The layer, from the published ``config.json`` (the configuration file keeps
its keys). ``x`` [T, 2048]; RMSNorm eps 1e-6; no biases.

- Attention's operands. ``h = norm(x)``; ``q = RMSNorm_128(h W_q)`` as 32
  heads of 128, ``k = RMSNorm_128(h W_k)`` as 4 heads, ``v = h W_v`` as 4
  heads; q and k rotated by RoPE (theta 1e7) whose 64 frequency pairs turn by
  the position row that ``mrope_section`` [16, 24, 24] assigns them, from a
  ``[3, T]`` position array (three equal rows for text).
- The indexer. ``qI = h W_qI`` as 16 heads of 64; ``kI = LayerNorm_64(h
  W_kI)``, one head; both rotated by plain RoPE over their 64 columns; ``w =
  (h W_w) * 16^-0.5 * 64^-0.5``. ``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] .
  kI[s])`` for ``s <= t``.
- The selection. ``S_t`` = the ``topk`` (2,048) tokens ``s <= t`` of largest
  ``I[t, s]``: those at or above the last value ``jax.lax.top_k`` returns
  (all of them while ``t < topk``); one set a query, shared by all 32 heads.
- The read. ``o[t, i] = softmax_{s in S_t}(q_i[t] . k_{i // 8}[s] /
  sqrt(128)) v_{i // 8}[s]``; ``x += concat(o) W_o``.
- The experts. ``h2 = norm(x)``; ``p = softmax(h2 W_g)`` over all 128, the 8
  largest, renormalised to sum 1; ``y = sum_{e chosen and held} p_e
  SwiGLU_e(h2)``. The configuration file's ``experts_held`` (``first``,
  ``count``) is the share this chip holds (``num_experts`` counts them,
  ``num_experts_published`` is the router's width): a token none of whose
  eight are held adds nothing, and that partial ``y`` goes on to the next
  layer. Without those keys every expert is held.

Departures (the configuration file's ``assumed``): RoPE pairs adjacent
columns; the indexer's Hadamard rotation and FP8 storage are left out; the
vision tower is not computed.

``leave_out`` names what a control drops, to show that the comparison sees it:
``selection`` (every query reads its whole context: dense attention) and
``indexer`` (the last ``topk`` tokens stand in the selection's place: a
window).

Weights are regenerated from the seed (float32 copies of the bfloat16 values
the configuration serves), one layer's and ONE EXPERT's at a time; nothing the
program made is read. Attention runs in blocks of queries and the head in
blocks of rows, so that a 45k-token request fits the chip.
"""

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights
from benchmark.references.common import HIGHEST, matmul

Q_BLOCK = 256         # queries a block of attention
ROW_BLOCK = 512       # rows a block of the head
TIE_BLOCKS = 8        # the selection's near ties are counted in every 8th block of queries
TERMS = ("selection", "indexer")
# The seeded scales that are not 1 / sqrt(fan_in) (the configuration file's
# ``assumed.weights`` has the readings behind them): the embedding's std, and
# ``o_proj``'s std as a share of 1 / sqrt(fan_in). At seeded weights the indexer is
# independent of attention, so a token swapped at the selection's boundary carries
# an AVERAGE attention weight; with these the attention block is ~2 % of the
# residual stream, not a third of it.
EMBED_STD = 0.1
O_PROJ_SCALE = 0.1


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


def param_spec(cfg):
    """The parameter tree as the program's ``KeyeVL2ForCausalLM`` holds it (a
    tier-1 test holds the two lists equal): one subtree a layer, matrices
    bfloat16, norm scales and biases float32, a layer's HELD experts stacked
    ``[count, ...]``, the router over every expert."""
    d, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    H, KV, Dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    sa = cfg["sa_config"]
    Hi, Di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    E, (_, count), F = router_width(cfg), held(cfg), cfg["moe_intermediate_size"]
    bf, f32, one, zero = jnp.bfloat16, jnp.float32, ("const", 1.0), ("const", 0.0)
    rows = [(("embed_tokens",), (V, d), EMBED_STD, bf, False),
            (("lm_head",), (V, d), 0.02, bf, False),
            (("norm", "scale"), (d,), one, f32, False)]
    for l in range(L):
        at = lambda *p: (f"layers_{l}",) + p
        lin = lambda path, i, o: (at(*path), (i, o), 1 / math.sqrt(i), bf, False)
        rows += [
            (at("input_layernorm", "scale"), (d,), one, f32, False),
            (at("post_attention_layernorm", "scale"), (d,), one, f32, False),
            lin(("self_attn", "q_proj", "kernel"), d, H * Dh),
            lin(("self_attn", "k_proj", "kernel"), d, KV * Dh),
            lin(("self_attn", "v_proj", "kernel"), d, KV * Dh),
            (at("self_attn", "o_proj", "kernel"), (H * Dh, d), O_PROJ_SCALE / math.sqrt(H * Dh), bf,
             False),
            (at("self_attn", "q_norm", "scale"), (Dh,), one, f32, False),
            (at("self_attn", "k_norm", "scale"), (Dh,), one, f32, False),
            lin(("self_attn", "indexer", "wq", "kernel"), d, Hi * Di),
            lin(("self_attn", "indexer", "wk", "kernel"), d, Di),
            (at("self_attn", "indexer", "k_norm", "scale"), (Di,), one, f32, False),
            (at("self_attn", "indexer", "k_norm", "bias"), (Di,), zero, f32, False),
            lin(("self_attn", "indexer", "weights_proj", "kernel"), d, Hi),
            lin(("moe", "router", "kernel"), d, E),
            (at("moe", "w1"), (count, d, F), 1 / math.sqrt(d), bf, True),
            (at("moe", "w3"), (count, d, F), 1 / math.sqrt(d), bf, True),
            (at("moe", "w2"), (count, F, d), 1 / math.sqrt(F), bf, True)]
    return rows


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _layernorm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _rotate(x, ang):
    """x [T, heads, D] by the angles ``ang`` [T, D / 2], adjacent pairs."""
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def _inv_freq(dim, theta):
    return theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)


def mrope_angles(positions, dim, theta, sections):
    """[T, dim / 2] angles of M-RoPE at ``positions`` [3, T]: pair ``i`` turns
    by the position row its section names (the first ``sections[0]`` pairs
    by row 0, the next ``sections[1]`` by row 1, the rest by row 2)."""
    row = np.repeat(np.arange(3), sections)
    pos = positions.astype(jnp.float32)[row, :].T                 # [T, dim / 2]
    return pos * _inv_freq(dim, theta)


def _attention(c, precision, leave_out, p, x, positions=None, q_block=Q_BLOCK, length=None):
    """x [T, d] -> (x + Attn(RMSNorm(x)), queries whose selected set changes
    when the indexer's operands are rounded to bfloat16 first, members that
    change, queries asked) for one sequence. ``length``: the tokens of ``x``
    that are a request's (the rest is padding): a block of queries wholly
    behind it is not computed. The near ties are counted in every
    ``TIE_BLOCKS``-th block of queries (a second ``top_k`` each)."""
    T = x.shape[0]
    H, KV, Dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    Hi, Di, topk = c["indexer_num_heads"], c["indexer_head_dim"], c["topk"]
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    a, ix = p["self_attn"], p["self_attn"]["indexer"]
    pos = jnp.arange(T)
    if positions is None:
        positions = jnp.broadcast_to(pos, (3, T))
    h = _rms(x, p["input_layernorm"]["scale"], eps)
    ang = mrope_angles(positions, Dh, theta, c["mrope_section"])
    q = _rms(matmul(h, a["q_proj"]["kernel"], precision).reshape(T, H, Dh),
             a["q_norm"]["scale"], eps)
    k = _rms(matmul(h, a["k_proj"]["kernel"], precision).reshape(T, KV, Dh),
             a["k_norm"]["scale"], eps)
    v = matmul(h, a["v_proj"]["kernel"], precision).reshape(T, KV, Dh)
    q, k = _rotate(q, ang), _rotate(k, ang)
    # the indexer: plain RoPE at the token's (temporal) position
    ang_i = positions[0].astype(jnp.float32)[:, None] * _inv_freq(Di, theta)
    q_i = _rotate(matmul(h, ix["wq"]["kernel"], precision).reshape(T, Hi, Di), ang_i)
    k_i = _layernorm(matmul(h, ix["wk"]["kernel"], precision),
                     ix["k_norm"]["scale"], ix["k_norm"]["bias"], eps)
    k_i = _rotate(k_i[:, None, :], ang_i)[:, 0]
    w_i = matmul(h, ix["weights_proj"]["kernel"], precision) * (Hi ** -0.5 * Di ** -0.5)
    bf = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
    kk = min(topk, T)

    def index(qi, wi, ki):
        dots = jnp.einsum("thd,sd->ths", qi, ki, precision=HIGHEST)
        return jnp.sum(jax.nn.relu(dots) * wi[:, :, None], axis=1)        # [t, T]

    def chosen(scores, seen):
        """[t, T] bool: the ``topk`` of largest score among the visible."""
        scores = jnp.where(seen, scores, -jnp.inf)
        return seen & (scores >= jax.lax.top_k(scores, kk)[0][:, -1:])

    count_ties = precision == "f32" and not leave_out   # once: not in a control

    def block(qb, qib, wib, q_pos, ties):
        seen = pos[None, :] <= q_pos[:, None]
        changed = jnp.zeros(qb.shape[0], jnp.int32)
        if "selection" in leave_out:
            keep = seen
        elif "indexer" in leave_out:
            keep = seen & (pos[None, :] > q_pos[:, None] - topk)
        else:
            keep = chosen(index(qib, wib, k_i), seen)
            if count_ties:
                changed = jax.lax.cond(
                    ties, lambda: jnp.sum(keep & ~chosen(
                        index(bf(qib), bf(wib), bf(k_i)), seen), axis=1).astype(jnp.int32),
                    lambda: changed)
        qg = qb.reshape(qb.shape[0], KV, H // KV, Dh)
        s = jnp.einsum("tgrd,sgd->grts", qg, k, precision=HIGHEST) / math.sqrt(Dh)
        s = jnp.where(keep[None, None], s, -jnp.inf)
        o = jnp.einsum("grts,sgd->tgrd", jax.nn.softmax(s, -1), v, precision=HIGHEST)
        return o.reshape(qb.shape[0], H * Dh), changed

    nb = -(-T // q_block)
    length = T if length is None else length
    if nb == 1:
        o, changed = block(q, q_i, w_i, pos, True)
        asked = jnp.minimum(length, T)
    else:
        pad = nb * q_block - T
        cut = lambda t: jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)) \
            .reshape((nb, q_block) + t.shape[1:])
        pp = jnp.pad(pos, (0, pad), constant_values=T - 1).reshape(nb, q_block)
        ties = jnp.arange(nb) % TIE_BLOCKS == 0
        skipped = (jnp.zeros((q_block, H * Dh), jnp.float32), jnp.zeros(q_block, jnp.int32))
        o, changed = jax.lax.map(
            lambda args: jax.lax.cond(args[3][0] < length, lambda: block(*args),
                                      lambda: skipped),
            (cut(q), cut(q_i), cut(w_i), pp, ties))
        o = o.reshape(nb * q_block, -1)[:T]
        changed = jnp.where(pp < length, changed, 0).reshape(-1)[:T]
        asked = jnp.sum(jnp.where(ties[:, None], pp < length, False)) if count_ties else 0
    return x + matmul(o, a["o_proj"]["kernel"], precision), \
        jnp.sum(changed > 0), jnp.sum(changed), asked


def _swiglu(h, w1, w3, w2, precision):
    return matmul(jax.nn.silu(matmul(h, w1, precision)) * matmul(h, w3, precision),
                  w2, precision)


def router(c, precision, p, h):
    """(gate [N, E]: a chosen expert's weight, zero elsewhere; chosen [N, k])
    over ALL the router's experts, held or not."""
    probs = jax.nn.softmax(matmul(h, p["moe"]["router"]["kernel"], precision), -1)
    w, idx = jax.lax.top_k(probs, c["num_experts_per_tok"])
    w = w / jnp.sum(w, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], idx].set(w)
    return gate, idx


def _moe(c, precision, p, expert, x):
    """x [N, d] (any tokens, each alone) -> (x + MoE(RMSNorm(x)), near ties).
    ``expert(j)`` gives the float32 ``(w1, w3, w2)`` of the ``j``-th expert
    HELD, which is the router's expert ``first + j``. Near ties: tokens whose
    chosen set changes when the router's input is rounded to bfloat16."""
    first, count = c["held"]
    h = _rms(x, p["post_attention_layernorm"]["scale"], c["rms_norm_eps"])
    gate, idx = router(c, precision, p, h)
    other = router(c, "f32", p, h.astype(jnp.bfloat16).astype(jnp.float32))[1]
    ties = jnp.sum(jnp.any(jnp.sort(other, -1) != jnp.sort(idx, -1), -1))

    def add(j, y):
        w1, w3, w2 = expert(j)
        return y + jax.lax.dynamic_slice_in_dim(gate, first + j, 1, 1) \
            * _swiglu(h, w1, w3, w2, precision)

    return x + jax.lax.fori_loop(0, count, add, jnp.zeros_like(x)), ties


def _f32(t):
    return jax.tree.map(lambda a: a.astype(jnp.float32), t)


def _c(cfg):
    """The keys the layers read."""
    c = {k: cfg[k] for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
        "rope_theta", "num_experts_per_tok")}
    sa = cfg["sa_config"]
    c.update(indexer_num_heads=sa["indexer_num_heads"],
             indexer_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
             mrope_section=tuple(cfg["rope_scaling"]["mrope_section"]),
             held=held(cfg))
    return c


def full_logits(cfg, tree, ids, precision="f32", leave_out=(), positions=None,
                q_block=Q_BLOCK):
    """Logits [T, V] of one sequence of token ids from a whole parameter
    tree: the tests' oracle at small sizes (the chip's comparison regenerates
    the weights instead and gathers rows, below). ``positions`` [3, T]:
    M-RoPE's rows, three times ``arange(T)`` when left out."""
    c = _c(cfg)
    with jax.default_matmul_precision("highest"):
        x = tree["embed_tokens"].astype(jnp.float32)[ids]
        for l in range(cfg["num_hidden_layers"]):
            p = _f32(tree[f"layers_{l}"])
            x = _attention(c, precision, leave_out, p, x, positions, q_block)[0]
            m = p["moe"]
            x, _ = _moe(c, precision, p,
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
def _layer(c_items, rows, precision, leave_out, key, crcs, x, lengths):
    """One layer over x [B, T, d], of which ``lengths`` [B] tokens are a
    request's. ``rows`` are one layer's rows of the spec (alike for every
    layer) and ``crcs`` that layer's leaf keys' folds, in the rows' order:
    ``weights.leaf``'s values, with the layer traced."""
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
    x, queries, members, asked = jax.lax.map(
        lambda args: _attention(c, precision, leave_out, p, args[0], length=args[1]),
        (x, lengths))
    y, ties = _moe(c, precision, p, expert, x.reshape(B * T, d))
    return y.reshape(B, T, d), ties, jnp.sum(queries), jnp.sum(members), jnp.sum(asked)


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


def _hidden(cfg, seed, ids, lengths, precision, leave_out=()):
    """Hidden states [B, T, d] before the final norm, and the near ties: the
    share of (token, layer) pairs whose router set changes under bfloat16
    rounding of its input, the share of the (query, layer) pairs asked (every
    ``TIE_BLOCKS``-th block of queries) whose SELECTED set changes under
    bfloat16 rounding of the indexer's operands, and the members that change
    in such a pair, on average."""
    spec = tuple(param_spec(cfg))
    c_items = tuple(sorted(_c(cfg).items()))
    key = weights.base_key(seed)
    embed = jax.jit(lambda k: weights.one_leaf(k, spec, ("embed_tokens",)))(key)
    x = embed[ids].astype(jnp.float32)
    del embed
    ties = queries = members = asked = 0
    for l in range(cfg["num_hidden_layers"]):
        rows = _layer_rows(spec, l)
        crcs = jnp.asarray([_crc((f"layers_{l}",) + path) for path, *_ in rows], jnp.int32)
        x, t, q, m, a = _layer(c_items, rows, precision, tuple(leave_out), key, crcs, x, lengths)
        ties, queries, members, asked = ties + int(t), queries + int(q), members + int(m), asked + int(a)
    pairs = max(x.shape[0] * x.shape[1] * cfg["num_hidden_layers"], 1)
    return spec, key, x, (ties / pairs, queries / max(asked, 1), members / max(queries, 1))


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
    lengths = np.zeros((B,), np.int32)
    for b, (p, o) in enumerate(zip(prompts, outputs)):
        seq = np.concatenate([p, o[:-1]])
        ids[b, :len(seq)] = seq
        lengths[b] = len(seq)
        rows[b, :len(o)] = len(p) - 1 + np.arange(len(o))
        toks[b, :len(o)] = o
        valid[b, :len(o)] = True
    out = {}
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        ids, rows, toks = jnp.asarray(ids), jnp.asarray(rows), jnp.asarray(toks)
        lengths = jnp.asarray(lengths)
        spec, key, x, (ties, queries, members) = _hidden(cfg, seed, ids, lengths, "f32")
        print(f"reference keye_vl2: under bfloat16 rounding of its input the router's "
              f"chosen set changes in {100 * ties:.3f} % of (token, layer) pairs; under "
              f"bfloat16 rounding of the indexer's operands the selected set changes in "
              f"{100 * queries:.3f} % of the (query, layer) pairs asked (every {TIE_BLOCKS}th "
              f"block of {Q_BLOCK} queries), by {members:.2f} members on average",
              flush=True)
        out["served"] = _head_gaps(eps, spec, "f32", False, key, x, x, rows, toks)
        for control in controls:
            precision, leave_out = control, ()
            if control.startswith("without:"):
                precision, leave_out = "f32", (control.split(":", 1)[1],)
                if leave_out[0] not in TERMS:
                    raise ValueError(f"unknown term {leave_out[0]!r}; known: {TERMS}")
            x_low = _hidden(cfg, seed, ids, lengths, precision, leave_out)[2]
            out[control] = _head_gaps(eps, spec, precision, True, key, x, x_low, rows, toks)
    return {name: np.asarray(g)[valid].tolist() for name, g in out.items()}


def served_token_gaps(cfg, seed, prompts, outputs, pad_to, max_new, low_precision=None):
    """``serve.Driver._gaps``'s call: the served tokens' gaps, or with
    ``low_precision`` that control's (``gaps`` has both from one float32
    forward). Returns a flat list."""
    got = gaps(cfg, seed, prompts, outputs, pad_to, max_new,
               (low_precision,) if low_precision else ())
    return got[low_precision or "served"]
