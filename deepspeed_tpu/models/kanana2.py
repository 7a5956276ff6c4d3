"""Kanana-2 (kakaocorp kanana-2-30b-a3b-instruct-2601; ``model_type``
``deepseek_v3``): a pre-norm decoder with latent attention (MLA) in every
layer, one leading dense feed-forward layer, then sparse-expert layers of 128
sigmoid-routed experts, 6 a token, beside 2 shared experts every token takes.

This file holds the configuration, the parameter tree and the serving
description; the forward is ``inference/v2/model_implementations/kanana2.py``
(serving only).

Layer ``l``: ``x <- x + Attn_l(RMSNorm(x)); x <- x + FFN_l(RMSNorm(x))``.

Attention. ``q = h W_q`` -> ``heads`` of ``qk_nope_head_dim + qk_rope_head_dim``
(128 | 64; ``q_lora_rank`` null: no low-rank q). ``ckv = h W_kv_a``
[``kv_lora_rank + qk_rope_head_dim``] = ``c_raw`` [512] | ``k_pe_raw`` [64]; ``c =
RMSNorm(c_raw)`` with its own scale; ``k_pe`` is ONE head shared by all query
heads. RoPE (``rope_theta``, ``qk_rope_head_dim`` dims, ``rope_scaling`` null) on
``q_pe`` and ``k_pe``. ``kv = c W_kv_b`` -> ``heads`` of ``k_nope`` [128] | ``v``
[``v_head_dim``]. Scores ``(q_nope . k_nope + q_pe . k_pe) / sqrt(192)``, causal,
softmax in float32, ``o = p v``, ``x += concat(o) W_o``. What a sequence keeps
is ``c`` and the rotated ``k_pe``: 576 values a token and layer, nothing a
head (``cache_groups``: one paged group of ONE leaf).

Feed-forward. Layers before ``first_k_dense_replace``: SwiGLU of
``intermediate_size``. After: ``moe_layer.moe_ffn`` with ``scoring="sigmoid"``
(scores ``sigmoid(h W_g)``, chosen by score + ``e_score_correction_bias``,
weighed by the unbiased scores normalised over the chosen and scaled by
``routed_scaling_factor``; ``n_group`` 1 and ``topk_group`` 1: no group step)
plus a shared SwiGLU of ``n_shared_experts * moe_intermediate_size``.

A share of the experts. ``experts_held = (first, count)``: this tree's ``w1`` /
``w2`` / ``w3`` hold ``count`` of the ``n_routed_experts`` the router scores;
the layer computes their part of the sum (``moe_layer.py``). None: all.

RoPE pairs ADJACENT columns (``models/llama.py`` ``rotary_apply``), which is
what ``rope_interleave: true`` means in the published code (it de-interleaves
q_pe and k_pe alike before rotating halves: the same pairs, another order of
the output's columns, common to q and k, so every score is the same). The
multi-token-prediction module of the DeepSeek-V3 family has no key in this
config and is not served. The config's ``head_dim`` 64 is the RoPE width;
attention's widths are ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``.
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax.numpy as jnp

from deepspeed_tpu.models.param_rows import init_tree

LANES = 128


@dataclasses.dataclass(frozen=True)
class Kanana2Config:
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 768
    routed_scaling_factor: float = 2.448
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    #: ``(first, count)`` of the routed experts this tree holds; None: all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.experts_held is not None:
            first, count = self.experts_held
            object.__setattr__(self, "experts_held", (int(first), int(count)))
            if not (0 <= first and count > 0
                    and first + count <= self.n_routed_experts):
                raise ValueError("Kanana2: experts_held is a range of the "
                                 "router's n_routed_experts")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("Kanana2: first_k_dense_replace counts layers")

    @classmethod
    def from_hf(cls, cfg, **over):
        """From the published ``config.json``'s keys (a dict). What the file
        says that this forward does not compute is refused, not ignored."""
        for key, want in (("q_lora_rank", None), ("rope_scaling", None),
                          ("n_group", 1), ("topk_group", 1),
                          ("scoring_func", "sigmoid"), ("norm_topk_prob", True),
                          ("moe_layer_freq", 1), ("attention_bias", False),
                          ("tie_word_embeddings", False)):
            if cfg.get(key, want) != want:
                raise ValueError(f"Kanana2: {key}={cfg[key]!r} is not served "
                                 f"(only {want!r})")
        keys = [f.name for f in dataclasses.fields(cls)
                if f.name not in ("experts_held", "dtype")]
        kw = {k: cfg[k] for k in keys}
        kw.update(over)
        return cls(**kw)

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row_width(self):
        """Columns of a page's row: the latent and the rotated position part
        (576), padded to whole lane tiles (640): a row that does not fill its
        last tile occupies it in HBM all the same, and the paged walk copies
        whole tiles (``ops/pallas/paged_attention.py``)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // LANES) * LANES

    @property
    def softmax_scale(self):
        return self.qk_head_dim ** -0.5

    @property
    def num_expert_layers(self):
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def experts_in_tree(self):
        return self.experts_held[1] if self.experts_held else self.n_routed_experts

    def is_dense(self, layer):
        return layer < self.first_k_dense_replace

    @staticmethod
    def tiny(**kw):
        d = dict(vocab_size=320, hidden_size=128, intermediate_size=256,
                 num_hidden_layers=3, num_attention_heads=4, kv_lora_rank=128,
                 qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                 first_k_dense_replace=1, n_routed_experts=16,
                 n_shared_experts=2, num_experts_per_tok=3,
                 moe_intermediate_size=128, max_position_embeddings=512,
                 rope_theta=10000.0, dtype=jnp.float32)
        d.update(kw)
        return Kanana2Config(**d)


def param_spec(cfg, matrix_dtype=None):
    """The parameter tree as ``(path, shape, fill, dtype, stacked)`` rows, the
    form ``benchmark/weights.py`` fills (``fill`` a std or ``("const", v)``).
    The routed experts' leaves are ``stacked`` over the experts HELD; the
    router's matrix and bias keep every expert's column."""
    mat = matrix_dtype or cfg.dtype
    f32 = jnp.float32
    d, V, H = cfg.hidden_size, cfg.vocab_size, cfg.num_attention_heads
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    E, held, F = cfg.n_routed_experts, cfg.experts_in_tree, cfg.moe_intermediate_size
    Fs = cfg.n_shared_experts * F
    one = ("const", 1.0)
    rows = [(("embed_tokens",), (V, d), 0.02, mat, False),
            (("lm_head",), (V, d), 0.02, mat, False),
            (("norm", "scale"), (d,), one, f32, False)]
    for l in range(cfg.num_hidden_layers):
        at = lambda *p: (f"layers_{l}",) + p
        lin = lambda path, i, o: (at(*path), (i, o), 1 / math.sqrt(i), mat, False)
        rows += [
            (at("input_layernorm", "scale"), (d,), one, f32, False),
            (at("post_attention_layernorm", "scale"), (d,), one, f32, False),
            lin(("self_attn", "q_proj", "kernel"), d, H * cfg.qk_head_dim),
            lin(("self_attn", "kv_a_proj", "kernel"), d, r + dr),
            (at("self_attn", "kv_a_layernorm", "scale"), (r,), one, f32, False),
            lin(("self_attn", "kv_b_proj", "kernel"), r,
                H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            lin(("self_attn", "o_proj", "kernel"), H * cfg.v_head_dim, d)]
        if cfg.is_dense(l):
            rows += [lin(("mlp", "gate_proj", "kernel"), d, cfg.intermediate_size),
                     lin(("mlp", "up_proj", "kernel"), d, cfg.intermediate_size),
                     lin(("mlp", "down_proj", "kernel"), cfg.intermediate_size, d)]
            continue
        rows += [
            lin(("moe", "router", "kernel"), d, E),
            # e_score_correction_bias: small and non-zero, so that it changes
            # some selections and no weight
            (at("moe", "router", "bias"), (E,), 0.02, f32, False),
            (at("moe", "w1"), (held, d, F), 1 / math.sqrt(d), mat, True),
            (at("moe", "w3"), (held, d, F), 1 / math.sqrt(d), mat, True),
            (at("moe", "w2"), (held, F, d), 1 / math.sqrt(F), mat, True),
            lin(("moe", "shared", "w1"), d, Fs),
            lin(("moe", "shared", "w3"), d, Fs),
            lin(("moe", "shared", "w2"), Fs, d)]
    return rows


class Kanana2ForCausalLM:
    """The model as the serving engine takes it: a configuration, a way to
    make a parameter tree, and what it keeps per sequence."""

    def __init__(self, config):
        self.config = config

    def init_params(self, rng):
        """A random tree (normal with each row's std; constants as given)."""
        return init_tree(param_spec(self.config), rng)

    @staticmethod
    def cache_groups(cfg):
        """ONE paged group of one leaf: a latent row a token and layer, read
        for the scores by its whole width and for the values by its first
        ``kv_lora_rank`` columns. No V pool beside it."""
        from deepspeed_tpu.inference.v2.ragged.cache_groups import PagedGroup
        return (PagedGroup("kv", cfg.num_hidden_layers, 1, cfg.latent_row_width,
                           leaves=1, value_dim=cfg.kv_lora_rank),)
