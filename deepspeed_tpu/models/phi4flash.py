"""Phi-4-mini-flash-reasoning (SambaY, arXiv:2507.06607): a decoder whose
first half alternates Mamba-1 and window-512 differential attention, whose
one full-attention layer's K and V are read again by every second layer of the
second half (cross layers), and whose other second-half layers are gated
memory units over the last Mamba layer's scan output. No positional encoding.

This file holds the configuration, the parameter tree and the serving
description; the forward is ``inference/v2/model_implementations/phi4flash.py``
(serving only: at 16 B a parameter no cut of this model trains on one chip).

Layer ``l`` of ``L = num_hidden_layers`` (the published 32), ``half = L // 2``:

    l < half, even   Mamba-1                       ("front" period, first)
    l < half, odd    differential attention, window ("front" period, second)
    l == half        Mamba-1, hands on its scan output y as the memory
    l == half + 1    differential attention, full; its K, V are the "kv" pages
    l > half+1, even gated memory unit              ("back" period, first)
    l > half+1, odd  differential cross attention   ("back" period, second)

The tree stacks the ``half // 2`` front periods and the ``(L - half - 2) // 2``
back periods so that each run is one ``lax.scan``.

Layouts chosen here (seeded weights make each a fixed permutation of the
published one): the SSM state is ``[d_state, d_inner]`` and ``A_log`` likewise
(d_inner is the TPU's lane dimension); the convolution kernel is
``[d_conv, d_inner]``; the two halves of a differential pair are ADJACENT
heads (q heads 2j, 2j+1; k and v heads 2g, 2g+1), so a pair of K or V heads of
64 is one page row of 128.
"""

import dataclasses
import math
from typing import Any

import jax.numpy as jnp

from deepspeed_tpu.models.param_rows import init_tree


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    max_position_embeddings: int = 262144
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    # not in the published config: the family's convention
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Any = None        # None: ceil(hidden_size / 16)
    subln_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.mamba_dt_rank is None:
            object.__setattr__(self, "mamba_dt_rank",
                               -(-self.hidden_size // 16))
        L = self.num_hidden_layers
        if L % 4 or L < 8 or self.mb_per_layer != 2:
            raise ValueError("Phi4Flash: num_hidden_layers must be a multiple "
                             "of 4 (>= 8) and mb_per_layer 2")
        if self.num_attention_heads % 4 or \
                self.num_attention_heads != 2 * self.num_key_value_heads:
            raise ValueError("Phi4Flash: differential pairs need heads = "
                             "2 x kv heads, a multiple of 4")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    @property
    def front_periods(self):
        return self.num_hidden_layers // 4

    @property
    def back_periods(self):
        return self.num_hidden_layers // 4 - 1

    @property
    def mamba_layers(self):
        return self.front_periods + 1

    @staticmethod
    def tiny(**kw):
        d = dict(vocab_size=320, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=8, num_attention_heads=4,
                 num_key_value_heads=2, max_position_embeddings=512,
                 sliding_window=8, mamba_d_state=8, dtype=jnp.float32)
        d.update(kw)
        return Phi4FlashConfig(**d)


def lambda_init(layer):
    """The differential attention's fixed part of lambda, by layer index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def param_spec(cfg, matrix_dtype=None):
    """The parameter tree as ``(path, shape, fill, dtype, stacked)`` rows, the
    form ``benchmark/weights.py`` fills (``fill`` a std or ``("const", v)``);
    ``stacked`` rows carry their run's period count first."""
    mat = matrix_dtype or cfg.dtype
    f32 = jnp.float32
    d, f, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    H, KV, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    Di, N, K, R = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
    one, zero = ("const", 1.0), ("const", 0.0)
    rows = []

    def add(prefix, n, path, shape, fill, dtype):
        stacked = n is not None
        rows.append((prefix + path, ((n,) if stacked else ()) + shape, fill,
                     dtype, stacked))

    def norms_and_mlp(prefix, n):
        for ln in ("ln1", "ln2"):
            add(prefix, n, (ln, "scale"), (d,), one, f32)
            add(prefix, n, (ln, "bias"), (d,), zero, f32)
        add(prefix, n, ("mlp", "gate_up_proj", "kernel"), (d, 2 * f), 1 / math.sqrt(d), mat)
        add(prefix, n, ("mlp", "down_proj", "kernel"), (f, d), 1 / math.sqrt(f), mat)

    def mamba(prefix, n):
        norms_and_mlp(prefix, n)
        m = lambda *p: ("mixer",) + p
        add(prefix, n, m("in_proj", "kernel"), (d, 2 * Di), 1 / math.sqrt(d), mat)
        add(prefix, n, m("conv", "kernel"), (K, Di), 1 / math.sqrt(K), f32)
        add(prefix, n, m("conv", "bias"), (Di,), 0.02, f32)
        add(prefix, n, m("x_proj", "kernel"), (Di, R + 2 * N), 1 / math.sqrt(Di), mat)
        # A = -1 and softplus(b_dt) ~ 0.01 with a small W_dt: the state
        # remembers some hundreds of tokens, so a state dropped between two
        # chunks of a prompt shows in the logits
        add(prefix, n, m("dt_proj", "kernel"), (R, Di), 0.1 / math.sqrt(R), mat)
        add(prefix, n, m("dt_proj", "bias"), (Di,), ("const", math.log(math.expm1(0.01))), f32)
        add(prefix, n, m("A_log"), (N, Di), zero, f32)
        add(prefix, n, m("D"), (Di,), one, f32)
        add(prefix, n, m("out_proj", "kernel"), (Di, d), 1 / math.sqrt(Di), mat)

    def attention(prefix, n, cross):
        norms_and_mlp(prefix, n)
        m = lambda *p: ("mixer",) + p
        width = H * dh if cross else (H + 2 * KV) * dh
        add(prefix, n, m("qkv_proj", "kernel"), (d, width), 1 / math.sqrt(d), mat)
        add(prefix, n, m("qkv_proj", "bias"), (width,), 0.02, f32)
        add(prefix, n, m("out_proj", "kernel"), (H * dh, d), 1 / math.sqrt(H * dh), mat)
        add(prefix, n, m("out_proj", "bias"), (d,), 0.02, f32)
        for v in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            add(prefix, n, m(v), (dh,), 0.1, f32)
        add(prefix, n, m("subln", "scale"), (2 * dh,), one, f32)

    def gmu(prefix, n):
        norms_and_mlp(prefix, n)
        add(prefix, n, ("mixer", "in_proj", "kernel"), (d, Di), 1 / math.sqrt(d), mat)
        add(prefix, n, ("mixer", "out_proj", "kernel"), (Di, d), 1 / math.sqrt(Di), mat)

    add((), None, ("embed_tokens",), (V, d), 0.02, mat)
    add((), None, ("final_layernorm", "scale"), (d,), one, f32)
    add((), None, ("final_layernorm", "bias"), (d,), zero, f32)
    mamba(("front", "mamba"), cfg.front_periods)
    attention(("front", "window"), cfg.front_periods, cross=False)
    mamba(("middle_mamba",), None)
    attention(("full",), None, cross=False)
    gmu(("back", "gmu"), cfg.back_periods)
    attention(("back", "cross"), cfg.back_periods, cross=True)
    return rows


class Phi4FlashForCausalLM:
    """The model as the serving engine takes it: a configuration, a way to
    make a parameter tree, and what it keeps per sequence."""

    def __init__(self, config):
        self.config = config

    def init_params(self, rng):
        """A random tree (normal with each row's std; constants as given)."""
        return init_tree(param_spec(self.config), rng)

    @staticmethod
    def cache_groups(cfg):
        """Three kinds of state side by side: the full layer's pages (read by
        every cross layer), the window layers' pages (freed as they leave the
        window) and a slot of recurrent state for the Mamba layers. A page row
        is a PAIR of K (or V) heads: ``kv_heads // 2`` rows of ``2 x head_dim``."""
        from deepspeed_tpu.inference.v2.ragged.cache_groups import (
            PagedGroup, SlotGroup)
        pairs, width = cfg.num_key_value_heads // 2, 2 * cfg.head_dim
        M = cfg.mamba_layers
        conv_dtype = jnp.dtype(cfg.dtype).name
        return (PagedGroup("kv", 1, pairs, width),
                PagedGroup("window", cfg.front_periods, pairs, width,
                           window=cfg.sliding_window),
                SlotGroup("state", (
                    ("conv", (M, cfg.mamba_d_conv - 1, cfg.d_inner), conv_dtype),
                    ("ssm", (M, cfg.mamba_d_state, cfg.d_inner), "float32"))))
