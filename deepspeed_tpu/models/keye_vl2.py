"""The language model of Keye-VL-2.0 (Kwai-Keye Keye-VL-2.0-30B-A3B;
``model_type`` ``KeyeVL2``): a pre-norm decoder whose every attention layer
reads only the ``topk`` cached tokens its learned indexer picks (the
DeepSeek-Sparse-Attention indexer over grouped-query attention), with
sparse-expert layers of 128 softmax-routed experts, 8 a token, no shared one.

This file holds the configuration, the parameter tree and the serving
description; the forward is ``inference/v2/model_implementations/keye_vl2.py``
(serving only). The vision tower is not served: tokens come from the text
vocabulary and carry three equal M-RoPE positions.

Layer ``l``: ``x <- x + Attn_l(RMSNorm(x)); x <- x + MoE_l(RMSNorm(x))``.

Attention. ``q = RMSNorm_head(h W_q)`` as ``heads`` of ``head_dim``, ``k =
RMSNorm_head(h W_k)`` and ``v = h W_v`` as ``kv heads``; q and k rotated by
RoPE whose ``head_dim / 2`` frequency pairs are assigned to three position
rows (temporal, height, width) by ``mrope_section`` (``mrope_tables``).

The indexer, one a layer. ``qI = h W_qI`` as ``indexer_num_heads`` of
``indexer_head_dim``; ``kI = LayerNorm(h W_kI)``, ONE head, kept in the cache
beside k and v; both rotated by plain RoPE over all their columns; ``w = (h
W_w) * indexer_num_heads^-0.5 * indexer_head_dim^-0.5``. The index score of
query ``t`` on cached token ``s <= t`` is ``I[t, s] = sum_j w[t, j]
ReLU(qI[t, j] . kI[s])``; the query reads the ``index_topk`` tokens of largest
``I`` (all of them while ``t < index_topk``), one set a query token and layer,
shared by every head: ``o = softmax over the set (q . k / sqrt(head_dim)) v``.

Experts. ``moe_layer.moe_ffn`` with ``scoring="softmax"``: softmax over all
``num_experts`` in float32, the ``num_experts_per_tok`` largest, renormalised
(``norm_topk_prob``); SwiGLU experts of ``moe_intermediate_size``.
``experts_held = (first, count)``: this tree's ``w1`` / ``w2`` / ``w3`` hold
``count`` of the experts the router scores, and the layer computes their part
of the sum. None: all.

What a sequence keeps (``cache_groups``): K, V and the indexer's key a token
and layer, in ONE paged group whose page has a third leaf.
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.param_rows import init_tree

LANES = 128
#: seeded scales of ``init_params`` that are not 1 / sqrt(fan_in): the
#: embedding's std and ``o_proj``'s std as a share of 1 / sqrt(fan_in), so that
#: the attention block is a few per cent of the residual stream (with a random
#: indexer a token swapped at the selection's boundary carries an average
#: attention weight, which nothing trained would)
EMBED_STD = 0.1
O_PROJ_SCALE = 0.1


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    #: frequency pairs of a head given to the temporal, height and width rows
    mrope_section: Tuple[int, int, int] = (16, 24, 24)
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    #: cached tokens a query reads (``sa_config.topk``)
    index_topk: int = 2048
    #: ``(first, count)`` of the routed experts this tree holds; None: all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "mrope_section",
                           tuple(int(n) for n in self.mrope_section))
        if sum(self.mrope_section) != self.head_dim // 2:
            raise ValueError("KeyeVL2: mrope_section shares out the "
                             "head_dim / 2 frequency pairs")
        if self.experts_held is not None:
            first, count = self.experts_held
            object.__setattr__(self, "experts_held", (int(first), int(count)))
            if not (0 <= first and count > 0
                    and first + count <= self.num_experts):
                raise ValueError("KeyeVL2: experts_held is a range of the "
                                 "router's num_experts")

    @classmethod
    def from_hf(cls, cfg, **over):
        """From the published ``config.json``'s keys (a dict). What the file
        says that this forward does not compute is refused, not ignored."""
        for key, want in (("attention_bias", False), ("decoder_sparse_step", 1),
                          ("mlp_only_layers", []), ("norm_topk_prob", True),
                          ("sliding_window", None),
                          ("use_sliding_window", False),
                          ("tie_word_embeddings", False),
                          ("hidden_act", "silu")):
            if cfg.get(key, want) != want:
                raise ValueError(f"KeyeVL2: {key}={cfg[key]!r} is not served "
                                 f"(only {want!r})")
        rope, sa = cfg["rope_scaling"], cfg["sa_config"]
        if rope.get("rope_type", "default") != "default":
            raise ValueError("KeyeVL2: only the default rope_type is served")
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError("KeyeVL2: the indexer's key is one head")
        names = ("vocab_size", "hidden_size", "num_hidden_layers",
                 "num_attention_heads", "num_key_value_heads", "head_dim",
                 "num_experts", "num_experts_per_tok", "moe_intermediate_size",
                 "max_position_embeddings", "rms_norm_eps", "rope_theta")
        kw = {k: cfg[k] for k in names}
        kw.update(mrope_section=tuple(rope["mrope_section"]),
                  indexer_num_heads=sa["indexer_num_heads"],
                  indexer_head_dim=sa["indexer_head_dim"],
                  index_topk=sa["topk"])
        kw.update(over)
        return cls(**kw)

    @property
    def n_routed_experts(self):
        """The router's width, under the name the engine's spans read."""
        return self.num_experts

    @property
    def experts_in_tree(self):
        return self.experts_held[1] if self.experts_held else self.num_experts

    @property
    def index_row_width(self):
        """Columns of a page's index row: the key's ``indexer_head_dim``
        padded to whole lane tiles (a 64-wide bfloat16 row occupies 128 lanes
        of HBM's tiles anyway, and the paged walk copies whole tiles)."""
        return -(-self.indexer_head_dim // LANES) * LANES

    @property
    def index_weight_scale(self):
        return self.indexer_num_heads ** -0.5 * self.indexer_head_dim ** -0.5

    @staticmethod
    def tiny(**kw):
        d = dict(vocab_size=320, hidden_size=128, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=128,
                 num_experts=8, num_experts_per_tok=2,
                 moe_intermediate_size=128, max_position_embeddings=512,
                 rope_theta=10000.0, mrope_section=(16, 24, 24),
                 indexer_num_heads=4, indexer_head_dim=16, index_topk=24,
                 dtype=jnp.float32)
        d.update(kw)
        return KeyeVL2Config(**d)


def mrope_tables(positions, head_dim, theta, sections):
    """``(cos, sin)`` ``[B, T, 1, head_dim / 2]`` float32 of M-RoPE at
    ``positions`` [3, B, T] (temporal, height, width): frequency pair ``i``
    (``theta ** (-2i / head_dim)``) turns by the position row that
    ``sections`` assigns it, the first ``sections[0]`` pairs by row 0, the
    next ``sections[1]`` by row 1, the rest by row 2. Three equal rows give
    ``rotary_tables``'s."""
    half = head_dim // 2
    inv_freq = jnp.asarray(
        theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim),
        jnp.float32)
    row = np.repeat(np.arange(3), sections)                        # [half]
    assert row.shape == (half,)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq   # [3,B,T,half]
    angles = jnp.take_along_axis(
        angles, jnp.asarray(row)[None, None, None, :], axis=0)[0]
    return jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]


def param_spec(cfg, matrix_dtype=None):
    """The parameter tree as ``(path, shape, fill, dtype, stacked)`` rows, the
    form ``benchmark/weights.py`` fills (``fill`` a std or ``("const", v)``).
    The routed experts' leaves are ``stacked`` over the experts HELD; the
    router's matrix keeps every expert's column."""
    mat = matrix_dtype or cfg.dtype
    f32 = jnp.float32
    d, V = cfg.hidden_size, cfg.vocab_size
    H, KV, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    Hi, Di = cfg.indexer_num_heads, cfg.indexer_head_dim
    E, held, F = cfg.num_experts, cfg.experts_in_tree, cfg.moe_intermediate_size
    one, zero = ("const", 1.0), ("const", 0.0)
    rows = [(("embed_tokens",), (V, d), EMBED_STD, mat, False),
            (("lm_head",), (V, d), 0.02, mat, False),
            (("norm", "scale"), (d,), one, f32, False)]
    for l in range(cfg.num_hidden_layers):
        at = lambda *p: (f"layers_{l}",) + p
        lin = lambda path, i, o: (at(*path), (i, o), 1 / math.sqrt(i), mat, False)
        rows += [
            (at("input_layernorm", "scale"), (d,), one, f32, False),
            (at("post_attention_layernorm", "scale"), (d,), one, f32, False),
            lin(("self_attn", "q_proj", "kernel"), d, H * Dh),
            lin(("self_attn", "k_proj", "kernel"), d, KV * Dh),
            lin(("self_attn", "v_proj", "kernel"), d, KV * Dh),
            (at("self_attn", "o_proj", "kernel"), (H * Dh, d), O_PROJ_SCALE / math.sqrt(H * Dh), mat,
             False),
            (at("self_attn", "q_norm", "scale"), (Dh,), one, f32, False),
            (at("self_attn", "k_norm", "scale"), (Dh,), one, f32, False),
            lin(("self_attn", "indexer", "wq", "kernel"), d, Hi * Di),
            lin(("self_attn", "indexer", "wk", "kernel"), d, Di),
            (at("self_attn", "indexer", "k_norm", "scale"), (Di,), one, f32, False),
            (at("self_attn", "indexer", "k_norm", "bias"), (Di,), zero, f32, False),
            lin(("self_attn", "indexer", "weights_proj", "kernel"), d, Hi),
            lin(("moe", "router", "kernel"), d, E),
            (at("moe", "w1"), (held, d, F), 1 / math.sqrt(d), mat, True),
            (at("moe", "w3"), (held, d, F), 1 / math.sqrt(d), mat, True),
            (at("moe", "w2"), (held, F, d), 1 / math.sqrt(F), mat, True)]
    return rows


class KeyeVL2ForCausalLM:
    """The model as the serving engine takes it: a configuration, a way to
    make a parameter tree, and what it keeps per sequence."""

    def __init__(self, config):
        self.config = config

    def init_params(self, rng):
        """A random tree (normal with each row's std; constants as given)."""
        return init_tree(param_spec(self.config), rng)

    @staticmethod
    def cache_groups(cfg):
        """ONE paged group: K and V a KV head, and beside them in the same
        pages the indexer's key, one head a token and layer."""
        from deepspeed_tpu.inference.v2.ragged.cache_groups import PagedGroup
        return (PagedGroup("kv", cfg.num_hidden_layers, cfg.num_key_value_heads,
                           cfg.head_dim, index_dim=cfg.index_row_width,
                           index_topk=cfg.index_topk),)
