"""Kimi-Linear (moonshotai Kimi-Linear-48B-A3B-Instruct; ``model_type``
``kimi_linear``; arXiv:2510.26692): a pre-norm decoder whose layers are three
of gated delta-rule linear attention (KDA) to one of latent attention (MLA)
WITHOUT positions, one leading dense feed-forward layer, then sparse-expert
layers of 256 sigmoid-routed experts, 8 a token, beside one shared expert.

This file holds the configuration, the parameter tree and the serving
description; the forward is
``inference/v2/model_implementations/kimi_linear.py`` (serving only).

Layer ``l`` (0-based): ``x <- x + Mixer_l(RMSNorm(x)); x <- x + FFN_l(RMSNorm(x))``.
The mixer is MLA where ``l + 1`` is in ``linear_attn_config.full_attn_layers``,
else KDA; the FFN is a SwiGLU of ``intermediate_size`` before
``first_k_dense_replace``, after it the expert layer.

KDA, ``H = linear_attn_config.num_heads`` heads of ``head_dim`` (key and value
alike), a head ``j``::

    q = l2norm(silu(conv(h W_q))) * head_dim^-0.5;  k = l2norm(silu(conv(h W_k)))
    v = silu(conv(h W_v))           # conv: depthwise, causal, short_conv_kernel_size taps, no bias
    g_t = -exp(A_log[j]) * softplus(W_f_up (W_f_down h_t) + dt_bias)[j]    # [head_dim], <= 0
    b_t = sigmoid(h_t W_b)[j]
    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T       # [head_dim, head_dim], float32
    o_t = S_t^T q_t
    y_t = W_o [rmsnorm(o_t; o_norm) * sigmoid(W_g_up (W_g_down h_t) + bias_g)[j]]

What a sequence keeps of a KDA layer is the state ``S`` (``H x head_dim x
head_dim`` float32) and the last ``taps - 1`` inputs of the three convolutions:
a slot (``cache_groups``). Of an MLA layer, Kanana-2's latent row a token
(``models/kanana2.py``): the normalised latent and the shared ``k_pe``, which
with ``mla_use_nope`` is NOT rotated (64 more shared key columns); a page.

The expert layer is Kanana-2's rule (``moe_layer.moe_ffn(scoring="sigmoid")``)
at this config's widths; ``experts_held = (first, count)`` says which of the
``num_experts`` the router scores this tree's ``w1`` / ``w2`` / ``w3`` hold.

The config's top-level ``head_dim`` (72 = hidden / heads) is read by neither
mixer. The multi-token-prediction module (``num_nextn_predict_layers`` 0) is
absent.
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax.numpy as jnp

from deepspeed_tpu.models.param_rows import init_tree

LANES = 128


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    num_experts: int = 256
    num_shared_experts: int = 1
    num_experts_per_token: int = 8
    moe_intermediate_size: int = 1024
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    #: 1-based, as published (``linear_attn_config.full_attn_layers``); every
    #: other layer is KDA
    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    #: ``(first, count)`` of the routed experts this tree holds; None: all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        object.__setattr__(self, "full_attn_layers",
                           tuple(int(l) for l in self.full_attn_layers))
        if self.experts_held is not None:
            first, count = self.experts_held
            object.__setattr__(self, "experts_held", (int(first), int(count)))
            if not (0 <= first and count > 0
                    and first + count <= self.num_experts):
                raise ValueError("KimiLinear: experts_held is a range of the "
                                 "router's num_experts")
        if not all(1 <= l <= self.num_hidden_layers for l in self.full_attn_layers):
            raise ValueError("KimiLinear: full_attn_layers are 1-based layers "
                             "of the stack")
        if not self.full_attn_layers:
            raise ValueError("KimiLinear: the serving description's first "
                             "group is the MLA layers' pages: one at least")

    @classmethod
    def from_hf(cls, cfg, **over):
        """From the published ``config.json``'s keys (a dict). What the file
        says that this forward does not compute is refused, not ignored."""
        for key, want in (("q_lora_rank", None), ("mla_use_nope", True),
                          ("num_expert_group", 1), ("topk_group", 1),
                          ("moe_router_activation_func", "sigmoid"),
                          ("moe_renormalize", True), ("moe_layer_freq", 1),
                          ("num_nextn_predict_layers", 0),
                          ("tie_word_embeddings", False)):
            if cfg.get(key, want) != want:
                raise ValueError(f"KimiLinear: {key}={cfg[key]!r} is not served "
                                 f"(only {want!r})")
        lin = cfg["linear_attn_config"]
        L = cfg["num_hidden_layers"]
        if sorted(lin["kda_layers"] + lin["full_attn_layers"]) != list(range(1, L + 1)):
            raise ValueError("KimiLinear: kda_layers and full_attn_layers "
                             "together are every layer once")
        keys = [f.name for f in dataclasses.fields(cls) if f.name not in (
            "experts_held", "dtype", "full_attn_layers", "kda_num_heads",
            "kda_head_dim", "short_conv_kernel_size")]
        kw = {k: cfg[k] for k in keys}
        kw.update(full_attn_layers=tuple(lin["full_attn_layers"]),
                  kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
                  short_conv_kernel_size=lin["short_conv_kernel_size"])
        kw.update(over)
        return cls(**kw)

    # what ``kanana2.latent_mla`` and ``moe_layer.dispatch_report`` read
    @property
    def num_experts_per_tok(self):
        return self.num_experts_per_token

    @property
    def n_routed_experts(self):
        return self.num_experts

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row_width(self):
        """Columns of a page's row: the latent and the shared position-free
        key part (576), padded to whole lane tiles (640), as Kanana-2's."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // LANES) * LANES

    @property
    def softmax_scale(self):
        return self.qk_head_dim ** -0.5

    @property
    def num_expert_layers(self):
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def experts_in_tree(self):
        return self.experts_held[1] if self.experts_held else self.num_experts

    @property
    def kda_width(self):
        return self.kda_num_heads * self.kda_head_dim

    def is_dense(self, layer):
        return layer < self.first_k_dense_replace

    def layer_kind(self, layer):
        """``"mla"`` or ``"kda"`` for the 0-based ``layer``."""
        return "mla" if layer + 1 in self.full_attn_layers else "kda"

    @property
    def mla_layers(self):
        return tuple(l for l in range(self.num_hidden_layers)
                     if self.layer_kind(l) == "mla")

    @property
    def kda_layers(self):
        return tuple(l for l in range(self.num_hidden_layers)
                     if self.layer_kind(l) == "kda")

    @staticmethod
    def tiny(**kw):
        d = dict(vocab_size=320, hidden_size=128, intermediate_size=256,
                 num_hidden_layers=4, num_attention_heads=4, kv_lora_rank=128,
                 qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                 first_k_dense_replace=1, num_experts=16, num_shared_experts=1,
                 num_experts_per_token=3, moe_intermediate_size=128,
                 full_attn_layers=(3,), kda_num_heads=2, kda_head_dim=32,
                 dtype=jnp.float32)
        d.update(kw)
        return KimiLinearConfig(**d)


def gate_leaves(a_log_raw, dt_bias_raw):
    """The two leaves of the decay that are not seeded like a matrix, from
    draws ``u`` uniform in (-1, 1) (a row of fill ``1 / sqrt(3)``): ``A_log`` =
    log of uniform(1, 16); ``dt_bias`` the inverse softplus of log-uniform(1e-3,
    1e-1), so that a state remembers tens to thousands of tokens."""
    a_log = jnp.log(8.5 + 7.5 * a_log_raw.astype(jnp.float32))
    dt = jnp.exp(math.log(1e-3) + (dt_bias_raw.astype(jnp.float32) + 1.0)
                 * 0.5 * math.log(100.0))
    return a_log, dt + jnp.log(-jnp.expm1(-dt))


def param_spec(cfg, matrix_dtype=None):
    """The parameter tree as ``(path, shape, fill, dtype, stacked)`` rows, the
    form ``benchmark/weights.py`` fills (``fill`` a std or ``("const", v)``).
    ``A_log`` and ``dt_bias`` are listed RAW (uniform in (-1, 1)):
    ``finish_params`` maps them (``gate_leaves``). The routed experts' leaves
    are ``stacked`` over the experts HELD; the router keeps every column."""
    mat = matrix_dtype or cfg.dtype
    f32 = jnp.float32
    d, V, H = cfg.hidden_size, cfg.vocab_size, cfg.num_attention_heads
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    Hk, dk, K = cfg.kda_num_heads, cfg.kda_head_dim, cfg.short_conv_kernel_size
    W = cfg.kda_width
    E, held, F = cfg.num_experts, cfg.experts_in_tree, cfg.moe_intermediate_size
    Fs = cfg.num_shared_experts * F
    one, zero, raw = ("const", 1.0), ("const", 0.0), 1 / math.sqrt(3.0)
    rows = [(("embed_tokens",), (V, d), 0.02, mat, False),
            (("lm_head",), (V, d), 0.02, mat, False),
            (("norm", "scale"), (d,), one, f32, False)]
    for l in range(cfg.num_hidden_layers):
        at = lambda *p: (f"layers_{l}",) + p
        lin = lambda path, i, o: (at(*path), (i, o), 1 / math.sqrt(i), mat, False)
        rows += [(at("input_layernorm", "scale"), (d,), one, f32, False),
                 (at("post_attention_layernorm", "scale"), (d,), one, f32, False)]
        if cfg.layer_kind(l) == "mla":
            rows += [
                lin(("self_attn", "q_proj", "kernel"), d, H * cfg.qk_head_dim),
                lin(("self_attn", "kv_a_proj", "kernel"), d, r + dr),
                (at("self_attn", "kv_a_layernorm", "scale"), (r,), one, f32, False),
                lin(("self_attn", "kv_b_proj", "kernel"), r,
                    H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                lin(("self_attn", "o_proj", "kernel"), H * cfg.v_head_dim, d)]
        else:
            kda = lambda *p: at("self_attn", *p)
            rows += [
                lin(("self_attn", "q_proj", "kernel"), d, W),
                lin(("self_attn", "k_proj", "kernel"), d, W),
                lin(("self_attn", "v_proj", "kernel"), d, W),
                (kda("q_conv", "kernel"), (K, W), 1 / math.sqrt(K), f32, False),
                (kda("k_conv", "kernel"), (K, W), 1 / math.sqrt(K), f32, False),
                (kda("v_conv", "kernel"), (K, W), 1 / math.sqrt(K), f32, False),
                lin(("self_attn", "f_a_proj", "kernel"), d, dk),
                lin(("self_attn", "f_b_proj", "kernel"), dk, W),
                (kda("dt_bias"), (W,), raw, f32, False),
                (kda("A_log"), (Hk,), raw, f32, False),
                lin(("self_attn", "b_proj", "kernel"), d, Hk),
                lin(("self_attn", "g_a_proj", "kernel"), d, dk),
                lin(("self_attn", "g_b_proj", "kernel"), dk, W),
                (kda("g_b_proj", "bias"), (W,), zero, f32, False),
                (kda("o_norm", "scale"), (dk,), one, f32, False),
                lin(("self_attn", "o_proj", "kernel"), W, d)]
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


def finish_params(cfg, tree):
    """A tree filled from ``param_spec``'s rows with every KDA layer's
    ``A_log`` and ``dt_bias`` mapped from their raw draws (``gate_leaves``;
    the draws are clipped into (-1, 1), which a uniform fill already is)."""
    clip = lambda a: jnp.clip(a, -0.999, 0.999)
    out = dict(tree)
    for l in cfg.kda_layers:
        layer = dict(tree[f"layers_{l}"])
        attn = dict(layer["self_attn"])
        attn["A_log"], attn["dt_bias"] = gate_leaves(
            clip(attn["A_log"]), clip(attn["dt_bias"]))
        layer["self_attn"] = attn
        out[f"layers_{l}"] = layer
    return out


class KimiLinearForCausalLM:
    """The model as the serving engine takes it: a configuration, a way to
    make a parameter tree, and what it keeps per sequence."""

    def __init__(self, config):
        self.config = config

    def init_params(self, rng):
        """A random tree (normal with each row's std; constants as given;
        the decay's two leaves as ``finish_params`` maps them)."""
        return finish_params(self.config, init_tree(param_spec(self.config), rng))

    @staticmethod
    def cache_groups(cfg):
        """Three kinds side by side: ONE paged group of one leaf over the MLA
        layers alone (a latent row a token and MLA layer: plane ``p`` is the
        ``p``-th MLA layer's, three layers in four have no page), one slot
        group of two leaves over the KDA layers (the three convolutions'
        tails, and the matrix state a head in float32) and the expert layers'
        device counts. A slot's tails are stored in whole tiles of FOUR rows
        (``taps - 1`` = 3 rows of ``3 W`` values and one of zeros that nothing
        reads): the chip's tiling pads three bfloat16 rows to four anyway, and
        declared three rows a slot the compiler kept the pool compact between
        uses and re-laid ALL of it around every KDA layer's gather and scatter
        of a dispatch's rows (17 % of the serving cell's busy time; PERF.md,
        PR 56, also for why the row is not flat)."""
        from deepspeed_tpu.inference.v2.model_implementations.moe_layer import COUNTS
        from deepspeed_tpu.inference.v2.ragged.cache_groups import (
            CounterGroup, PagedGroup, SlotGroup)
        M = len(cfg.kda_layers)
        H, dk = cfg.kda_num_heads, cfg.kda_head_dim
        return (PagedGroup("kv", len(cfg.mla_layers), 1, cfg.latent_row_width,
                           leaves=1, value_dim=cfg.kv_lora_rank),
                SlotGroup("state", (
                    ("conv", (M, -(-(cfg.short_conv_kernel_size - 1) // 4) * 4,
                              3 * cfg.kda_width), jnp.dtype(cfg.dtype).name),
                    ("kda", (M, H, dk, dk), "float32"))),
                CounterGroup("counters", COUNTS))
