"""Mellum2 (JetBrains Mellum2-12B-A2.5B-Instruct): a pre-norm decoder whose
every layer has a sparse-expert feed-forward part (64 routed experts, 8 a
token, no shared expert) and whose attention repeats three sliding-window
layers (window 1024, default RoPE) to each full layer (causal, YaRN RoPE).

This file holds the configuration, the parameter tree and the serving
description; the forward is ``inference/v2/model_implementations/mellum2.py``
(serving only).

Layer ``l``: ``x <- x + Attn_l(RMSNorm(x)); x <- x + MoE_l(RMSNorm(x))``.
Attention: q ``hidden -> heads x head_dim`` (``head_dim`` is stated, 128, and
is NOT ``hidden / heads``), k and v ``hidden -> kv_heads x head_dim``, no
biases; RMSNorm with a learned scale of ``head_dim`` on each q and k head,
then RoPE by the layer's type; ``layer_types[l]`` is ``sliding_attention``
(key visible iff ``0 <= q_pos - k_pos < sliding_window``) or
``full_attention`` (causal). MoE: ``p = softmax(x W_r)`` over all experts, the
``k`` largest renormalised to sum 1, ``y = sum_e p_e W2_e(silu(W1_e x) * W3_e
x)``.

Not in the published config, taken from the convention of the family whose key
names it uses (Qwen3-MoE): the q/k norm, a router without bias with softmax
before top-k. The multi-token-prediction head the model card mentions has no
key in the config and is not served. RoPE pairs ADJACENT columns
(``models/llama.py`` ``rotary_apply``) where the published code pairs halves:
with seeded weights one is the other under a fixed permutation of each head's
columns.

The tree keeps one subtree a layer (``layers_<l>``), the experts stacked
``[E, ...]``: a layer's expert weights are whole buffers that the grouped GEMM
reads in place.
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax.numpy as jnp

from deepspeed_tpu.models.param_rows import init_tree

SLIDING, FULL = "sliding_attention", "full_attention"
PERIOD = (SLIDING, SLIDING, SLIDING, FULL)


def _frozen(d):
    return tuple(sorted(d.items())) if d else None


@dataclasses.dataclass(frozen=True)
class Mellum2Config:
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    sliding_window: int = 1024
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    #: None: ``PERIOD`` repeated (the published pattern)
    layer_types: Optional[Tuple[str, ...]] = None
    #: ``rope_parameters`` as the published config nests it, by layer type;
    #: a dict is frozen to sorted items so that the config stays hashable
    rope_sliding: Any = (("rope_theta", 500000.0), ("rope_type", "default"))
    rope_full: Any = (("attention_factor", 1.2772588722239782),
                      ("beta_fast", 32), ("beta_slow", 1), ("factor", 16),
                      ("original_max_position_embeddings", 8192),
                      ("rope_theta", 500000.0), ("rope_type", "yarn"))
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        for name in ("rope_sliding", "rope_full"):
            v = getattr(self, name)
            if isinstance(v, dict):
                object.__setattr__(self, name, _frozen(v))
        if self.layer_types is None:
            L = self.num_hidden_layers
            object.__setattr__(self, "layer_types",
                               (PERIOD * (L // 4 + 1))[:L])
        else:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError("Mellum2: layer_types names num_hidden_layers "
                             "layers, each sliding_attention or full_attention")
        if FULL not in self.layer_types or SLIDING not in self.layer_types:
            raise ValueError("Mellum2: layers of both types are served "
                             "(a stack of one type is the llama family's)")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("Mellum2: heads must be a multiple of kv heads")

    @classmethod
    def from_hf(cls, cfg, **over):
        """From the published ``config.json``'s keys (a dict)."""
        rope = cfg["rope_parameters"]
        keys = ("vocab_size", "hidden_size", "num_hidden_layers",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "num_experts", "num_experts_per_tok", "moe_intermediate_size",
                "sliding_window", "max_position_embeddings", "rms_norm_eps")
        kw = {k: cfg[k] for k in keys}
        if "layer_types" in cfg:
            kw["layer_types"] = tuple(
                cfg["layer_types"][:cfg["num_hidden_layers"]])
        kw.update(rope_sliding=dict(rope[SLIDING]), rope_full=dict(rope[FULL]))
        kw.update(over)
        return cls(**kw)

    def layers_of(self, kind):
        return tuple(l for l, t in enumerate(self.layer_types) if t == kind)

    def rope(self, kind):
        """``(theta, yarn parameters or None)`` of a layer type."""
        p = dict(self.rope_full if kind == FULL else self.rope_sliding)
        return float(p["rope_theta"]), (p if p.get("rope_type") == "yarn" else None)

    @staticmethod
    def tiny(**kw):
        d = dict(vocab_size=320, hidden_size=64, num_hidden_layers=8,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                 num_experts=8, num_experts_per_tok=2,
                 moe_intermediate_size=32, sliding_window=8,
                 max_position_embeddings=512,
                 rope_sliding={"rope_type": "default", "rope_theta": 10000.0},
                 rope_full={"rope_type": "yarn", "rope_theta": 10000.0,
                            "factor": 4, "original_max_position_embeddings": 16,
                            "beta_fast": 32, "beta_slow": 1,
                            "attention_factor": 0.1 * math.log(4) + 1.0},
                 dtype=jnp.float32)
        d.update(kw)
        return Mellum2Config(**d)


def param_spec(cfg, matrix_dtype=None):
    """The parameter tree as ``(path, shape, fill, dtype, stacked)`` rows, the
    form ``benchmark/weights.py`` fills (``fill`` a std or ``("const", v)``).
    The expert leaves are ``stacked`` over their experts."""
    mat = matrix_dtype or cfg.dtype
    f32 = jnp.float32
    d, V = cfg.hidden_size, cfg.vocab_size
    H, KV, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    E, F = cfg.num_experts, cfg.moe_intermediate_size
    one = ("const", 1.0)
    rows = [(("embed_tokens",), (V, d), 0.02, mat, False),
            (("lm_head",), (V, d), 0.02, mat, False),
            (("norm", "scale"), (d,), one, f32, False)]
    for l in range(cfg.num_hidden_layers):
        at = lambda *p: (f"layers_{l}",) + p
        lin = lambda name, i, o: (at("self_attn", name, "kernel"), (i, o),
                                  1 / math.sqrt(i), mat, False)
        rows += [
            (at("input_layernorm", "scale"), (d,), one, f32, False),
            (at("post_attention_layernorm", "scale"), (d,), one, f32, False),
            lin("q_proj", d, H * dh), lin("k_proj", d, KV * dh),
            lin("v_proj", d, KV * dh), lin("o_proj", H * dh, d),
            (at("self_attn", "q_norm", "scale"), (dh,), one, f32, False),
            (at("self_attn", "k_norm", "scale"), (dh,), one, f32, False),
            (at("moe", "router", "kernel"), (d, E), 1 / math.sqrt(d), mat, False),
            (at("moe", "w1"), (E, d, F), 1 / math.sqrt(d), mat, True),
            (at("moe", "w3"), (E, d, F), 1 / math.sqrt(d), mat, True),
            (at("moe", "w2"), (E, F, d), 1 / math.sqrt(F), mat, True)]
    return rows


class Mellum2ForCausalLM:
    """The model as the serving engine takes it: a configuration, a way to
    make a parameter tree, and what it keeps per sequence."""

    def __init__(self, config):
        self.config = config

    def init_params(self, rng):
        """A random tree (normal with each row's std; constants as given)."""
        return init_tree(param_spec(self.config), rng)

    @staticmethod
    def cache_groups(cfg):
        """Two paged groups and no slot group: the full layers' pages, which
        live as long as the sequence, and the sliding layers' pages, freed
        once every later query has left them behind."""
        from deepspeed_tpu.inference.v2.ragged.cache_groups import PagedGroup
        KV, dh = cfg.num_key_value_heads, cfg.head_dim
        return (PagedGroup("kv", len(cfg.layers_of(FULL)), KV, dh),
                PagedGroup("window", len(cfg.layers_of(SLIDING)), KV, dh,
                           window=cfg.sliding_window))
