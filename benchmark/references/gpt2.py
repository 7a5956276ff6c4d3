"""Plain reference for GPT-2 training: forward, loss, gradients, global-norm
clipping and AdamW in straightforward ``jax.numpy`` float32, no kernels.

Follows the published GPT-2 (pre-LN blocks, learned positions, tanh GELU,
tied LM head, next-token cross-entropy, mean over tokens). Departures: none in
the mathematics; gradients are accumulated over blocks of rows and each layer
is recomputed in the backward pass so that a 16 GB chip holds it.
Imports nothing of the program under test; weights come from the seed.
"""

import functools
import math

import jax
import jax.numpy as jnp

from benchmark import weights
from benchmark.references.common import HIGHEST, matmul


def param_spec(cfg):
    """The parameter tree as the program's GPT2LMHeadModel(scan_layers) holds
    it: layer leaves stacked on axis 0 under h/block."""
    d, n_layer, std = cfg["n_embd"], cfg["n_layer"], cfg["initializer_range"]
    f32 = jnp.float32
    one, zero = ("const", 1.0), ("const", 0.0)
    blk = lambda *p: ("h", "block") + p
    L = n_layer
    return [
        (("wte",), (cfg["vocab_size"], d), std, f32, False),
        (("wpe",), (cfg["n_positions"], d), std / 2, f32, False),
        (("ln_f", "scale"), (d,), one, f32, False),
        (("ln_f", "bias"), (d,), zero, f32, False),
        (blk("ln_1", "scale"), (L, d), one, f32, True),
        (blk("ln_1", "bias"), (L, d), zero, f32, True),
        (blk("attn", "c_attn", "kernel"), (L, d, 3 * d), std, f32, True),
        (blk("attn", "c_attn", "bias"), (L, 3 * d), zero, f32, True),
        (blk("attn", "c_proj", "kernel"), (L, d, d), std, f32, True),
        (blk("attn", "c_proj", "bias"), (L, d), zero, f32, True),
        (blk("ln_2", "scale"), (L, d), one, f32, True),
        (blk("ln_2", "bias"), (L, d), zero, f32, True),
        (blk("mlp", "c_fc", "kernel"), (L, d, 4 * d), std, f32, True),
        (blk("mlp", "c_fc", "bias"), (L, 4 * d), zero, f32, True),
        (blk("mlp", "c_proj", "kernel"), (L, 4 * d, d), std, f32, True),
        (blk("mlp", "c_proj", "bias"), (L, d), zero, f32, True),
    ]


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(cfg, precision, x, p):
    B, T, d = x.shape
    H = cfg["n_head"]
    h = _layer_norm(x, p["ln_1"], cfg["layer_norm_epsilon"])
    qkv = matmul(h, p["attn"]["c_attn"]["kernel"], precision) + p["attn"]["c_attn"]["bias"]
    q, k, v = (t.reshape(B, T, H, d // H) for t in jnp.split(qkv, 3, -1))
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HIGHEST) / math.sqrt(d // H)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v, precision=HIGHEST)
    x = x + matmul(a.reshape(B, T, d), p["attn"]["c_proj"]["kernel"], precision) \
        + p["attn"]["c_proj"]["bias"]
    h = _layer_norm(x, p["ln_2"], cfg["layer_norm_epsilon"])
    h = _gelu_new(matmul(h, p["mlp"]["c_fc"]["kernel"], precision) + p["mlp"]["c_fc"]["bias"])
    return x + matmul(h, p["mlp"]["c_proj"]["kernel"], precision) + p["mlp"]["c_proj"]["bias"]


def nll_sum(cfg, precision, params, ids):
    """Sum over rows and positions of -log p(next token); ids [B, T]."""
    T = ids.shape[1]
    x = params["wte"][ids] + params["wpe"][None, :T]
    body = jax.checkpoint(lambda x, p: (_block(cfg, precision, x, p), None))
    x, _ = jax.lax.scan(body, x, params["h"]["block"])
    x = _layer_norm(x, params["ln_f"], cfg["layer_norm_epsilon"])
    logits = matmul(x[:, :-1], params["wte"].T, precision)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.sum(jnp.take_along_axis(logp, ids[:, 1:, None], -1))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _loss_and_grad(cfg_items, precision, rows, params, ids):
    """Mean next-token loss of a batch and its gradient, in blocks of
    ``rows`` rows."""
    cfg = dict(cfg_items)
    B, T = ids.shape
    blocks = ids.reshape(B // rows, rows, T)

    def add(carry, blk):
        s, g = jax.value_and_grad(lambda p: nll_sum(cfg, precision, p, blk))(params)
        return (carry[0] + s, jax.tree.map(jnp.add, carry[1], g)), None

    zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, params))
    (s, g), _ = jax.lax.scan(add, zero, blocks)
    n = B * (T - 1)
    return s / n, jax.tree.map(lambda x: x / n, g)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2, 3))
def _adamw(opt_items, params, m, v, grads, t):
    o = dict(opt_items)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["clip"] / (gnorm + o["clip_eps"]))
    grads = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda m, g: o["b1"] * m + (1 - o["b1"]) * g, m, grads)
    v = jax.tree.map(lambda v, g: o["b2"] * v + (1 - o["b2"]) * g * g, v, grads)
    c1, c2 = 1 - o["b1"] ** t, 1 - o["b2"] ** t
    params = jax.tree.map(
        lambda p, m, v: p - o["lr"] * ((m / c1) / (jnp.sqrt(v / c2) + o["eps"])
                                       + o["weight_decay"] * p), params, m, v)
    return params, m, v, weights.leaf_norms(grads)


def train_steps(cfg, seed, batches, precision="f32", rows=2):
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights. Returns {"loss": [per step], "grad_norm": {leaf: norm of the
    first clipped gradient}, "delta_norm": {leaf: norm of the parameters'
    change after the last step}} as Python floats."""
    spec = param_spec(cfg)
    params = weights.make_params(seed, spec)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    cfg_items = tuple(sorted((k, cfg[k]) for k in
                             ("n_embd", "n_head", "n_layer", "layer_norm_epsilon")))
    opt_items = tuple(sorted(cfg["optimizer_reference"].items()))
    losses, first = [], None
    for t, ids in enumerate(batches, 1):
        rows_t = math.gcd(rows, ids.shape[0])
        loss, grads = _loss_and_grad(cfg_items, precision, rows_t, params, jnp.asarray(ids))
        params, m, v, gn = _adamw(opt_items, params, m, v, grads, jnp.float32(t))
        losses.append(float(loss))
        first = first or {k: float(x) for k, x in gn.items()}
    delta = jax.jit(lambda p, k: weights.leaf_norms(jax.tree.map(
        jnp.subtract, p, weights.full_tree(k, tuple(spec)))))(params, weights.base_key(seed))
    return {"loss": losses, "grad_norm": first,
            "delta_norm": {k: float(x) for k, x in delta.items()}}
