"""LongCat-Flash-Chat (meituan-longcat; ``modeling_longcat_flash.py`` of the
source repository): a decoder of shortcut-connected DOUBLE layers. One layer
is two latent-attention (MLA) sub-blocks and two dense SwiGLU FFNs in series
and ONE sparse-expert layer that reads the stream after the first attention
and whose output joins the stream only after the second FFN (ScMoE), so that
it can run beside everything between. The router is wider than its experts:
its last ``zero_expert_num`` columns are identity experts that compute nothing.

This file holds the configuration, the parameter tree, the serving description
and a dense forward without a cache (the tests' twin of the plain reference);
the serving forward is ``inference/v2/model_implementations/longcat_flash.py``.

Layer ``l``, sub-block ``j`` in {0, 1}, stream ``x``, RMSNorm eps 1e-5, no bias::

    a0 = x  + MLA[l,0](norm_in[l,0](x))
    h0 = norm_post[l,0](a0)
    m  = MoE[l](h0)                        # the shortcut: added at the END
    b0 = a0 + FFN[l,0](h0)
    a1 = b0 + MLA[l,1](norm_in[l,1](b0))
    b1 = a1 + FFN[l,1](norm_post[l,1](a1)) + m

``MLA(h)``: ``q = q_b(RMSNorm(q_a(h)))`` -> heads of ``qk_nope_head_dim`` |
``qk_rope_head_dim`` (128 | 64), ALL of it x ``s_q = sqrt(hidden_size /
q_lora_rank)`` (``mla_scale_q_lora``); ``ckv = kv_a(h)`` -> latent
[``kv_lora_rank``] | ``k_pe`` [64]; ``c = RMSNorm(latent) x s_kv``, ``s_kv =
sqrt(hidden_size / kv_lora_rank)`` (``mla_scale_kv_lora``); ``[k_nope | v] =
kv_b(c)`` a head; RoPE (``rope_theta``, adjacent pairs, no scaling) on q's 64
and the ONE shared ``k_pe``; scores ``(q_nope . k_nope + q_pe . k_pe) x
192^-0.5``, causal softmax in float32; ``o_proj`` over heads x ``v_head_dim``.
What a sequence keeps is ``c`` and the rotated ``k_pe``, a row a token and
SUB-BLOCK: ``cache_groups`` counts ``2 x num_layers`` planes, the first family
for which a paged group's planes are not its layers.

``MoE(h)``: ``p = softmax(h W_r)`` in float32 over all ``n_routed_experts +
zero_expert_num`` columns; chosen = the ``moe_topk`` largest of ``p + bias``
(``e_score_correction_bias``); weights ``routed_scaling_factor x p`` of the
chosen, NOT renormalised; ``m = sum over chosen real e of w_e SwiGLU_e(h) +
(sum over chosen zero e of w_e) h``. No shared expert. ``moe_layer.moe_ffn``
computes it (``scoring="softmax_bias"``, ``zero_experts``).

A share of the experts. ``experts_held = (first, count)``: this tree's ``w1`` /
``w2`` / ``w3`` hold ``count`` of the ``n_routed_experts`` REAL experts; the
zero experts are every share's. None: all.

Not served: the multi-token-prediction module. The head is untied.
"""

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.param_rows import init_tree

LANES = 128

#: what the family's forward adds to its counter group, a dispatch and summed
#: over its expert layers (``moe_layer.COUNTS``), and the dispatches themselves
COUNTER_FIELDS = ("routed_rows", "zero_rows", "held_rows", "experts_hit",
                  "dispatches")


#: std of the seeded ``e_score_correction_bias`` (uniform in +-5.2e-5): with
#: seeded weights the 12th largest of 768 probabilities is ~6.9e-3 and lies
#: ~1.6e-4 above the 13th (median; 2.4e-5 at the tenth of tokens where they
#: are closest), so this bias changes the chosen set of ~7.5 % of tokens and,
#: by the published rule, no weight
ROUTER_BIAS_STD = 3e-5


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    #: ``(first, count)`` of the real experts this tree holds; None: all
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.zero_expert_type != "identity":
            raise ValueError("LongcatFlash: zero experts are identity experts")
        if self.experts_held is not None:
            first, count = self.experts_held
            object.__setattr__(self, "experts_held", (int(first), int(count)))
            if not (0 <= first and count > 0
                    and first + count <= self.n_routed_experts):
                raise ValueError("LongcatFlash: experts_held is a range of "
                                 "the router's n_routed_experts real experts")

    @classmethod
    def from_hf(cls, cfg, **over):
        """From the published ``config.json``'s keys (a dict). What the file
        says that this forward does not compute is refused, not ignored."""
        for key, want in (("rope_scaling", None), ("attention_bias", False),
                          ("attention_method", "MLA"), ("router_bias", False),
                          ("norm_topk_prob", False),
                          ("tie_word_embeddings", False)):
            if cfg.get(key, want) != want:
                raise ValueError(f"LongcatFlash: {key}={cfg[key]!r} is not "
                                 f"served (only {want!r})")
        keys = [f.name for f in dataclasses.fields(cls)
                if f.name not in ("experts_held", "dtype")]
        kw = {k: cfg[k] for k in keys if k in cfg}
        kw.update(over)
        return cls(**kw)

    # what the serving code shared with Kanana-2 reads, under its names
    @property
    def num_hidden_layers(self):
        return self.num_layers

    @property
    def num_experts_per_tok(self):
        return self.moe_topk

    @property
    def num_expert_layers(self):
        return self.num_layers

    @property
    def router_width(self):
        return self.n_routed_experts + self.zero_expert_num

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row_width(self):
        """Columns of a page's row: the latent and the rotated position part
        (576) padded to whole lane tiles (640), as Kanana-2's."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // LANES) * LANES

    @property
    def softmax_scale(self):
        return self.qk_head_dim ** -0.5

    @property
    def q_scale(self):
        return math.sqrt(self.hidden_size / self.q_lora_rank) \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self):
        return math.sqrt(self.hidden_size / self.kv_lora_rank) \
            if self.mla_scale_kv_lora else 1.0

    @property
    def experts_in_tree(self):
        return self.experts_held[1] if self.experts_held else self.n_routed_experts

    @staticmethod
    def tiny(**kw):
        d = dict(vocab_size=320, hidden_size=256, ffn_hidden_size=256,
                 expert_ffn_hidden_size=128, num_layers=2,
                 num_attention_heads=4, kv_lora_rank=128, q_lora_rank=64,
                 qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                 n_routed_experts=16, zero_expert_num=8, moe_topk=4,
                 max_position_embeddings=512, rope_theta=10000.0,
                 dtype=jnp.float32)
        d.update(kw)
        return LongcatFlashConfig(**d)


def param_spec(cfg, matrix_dtype=None):
    """The parameter tree as ``(path, shape, fill, dtype, stacked)`` rows, the
    form ``benchmark/weights.py`` fills. A layer's two attentions, two dense
    FFNs and four norms carry their sub-block in the name; the routed experts'
    leaves are ``stacked`` over the experts HELD; the router's matrix and bias
    keep every column, the zero experts' last. The two inner norms' scales are
    filled with ``1 / s_q`` and ``1 / s_kv``, what a trained model's norms
    would have to be of the order of for the published factors to leave
    scores of order 1: with all-ones norms and fan-in-scaled seeded matrices
    the scores' spread is ``s_q s_kv`` = 6.9 times a DeepSeek-V3 tree's, the
    softmax picks one key, a rounding flips which, and no precision can be
    told from another (PERF.md section 2: the int8 control then reads 2.4
    and the program 1.25)."""
    mat = matrix_dtype or cfg.dtype
    f32 = jnp.float32
    d, V, H = cfg.hidden_size, cfg.vocab_size, cfg.num_attention_heads
    r, rq, dr = cfg.kv_lora_rank, cfg.q_lora_rank, cfg.qk_rope_head_dim
    held, F, Fd = cfg.experts_in_tree, cfg.expert_ffn_hidden_size, cfg.ffn_hidden_size
    one = ("const", 1.0)
    rows = [(("embed_tokens",), (V, d), 0.02, mat, False),
            (("lm_head",), (V, d), 0.02, mat, False),
            (("norm", "scale"), (d,), one, f32, False)]
    for l in range(cfg.num_layers):
        at = lambda *p: (f"layers_{l}",) + p
        lin = lambda path, i, o: (at(*path), (i, o), 1 / math.sqrt(i), mat, False)
        for j in (0, 1):
            attn, mlp = f"self_attn_{j}", f"mlps_{j}"
            rows += [
                (at(f"input_layernorm_{j}", "scale"), (d,), one, f32, False),
                (at(f"post_attention_layernorm_{j}", "scale"), (d,), one, f32, False),
                lin((attn, "q_a_proj", "kernel"), d, rq),
                (at(attn, "q_a_layernorm", "scale"), (rq,),
                 ("const", 1 / cfg.q_scale), f32, False),
                lin((attn, "q_b_proj", "kernel"), rq, H * cfg.qk_head_dim),
                lin((attn, "kv_a_proj", "kernel"), d, r + dr),
                (at(attn, "kv_a_layernorm", "scale"), (r,),
                 ("const", 1 / cfg.kv_scale), f32, False),
                lin((attn, "kv_b_proj", "kernel"), r,
                    H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                lin((attn, "o_proj", "kernel"), H * cfg.v_head_dim, d),
                lin((mlp, "gate_proj", "kernel"), d, Fd),
                lin((mlp, "up_proj", "kernel"), d, Fd),
                lin((mlp, "down_proj", "kernel"), Fd, d)]
        rows += [
            lin(("moe", "router", "kernel"), d, cfg.router_width),
            (at("moe", "router", "bias"), (cfg.router_width,), ROUTER_BIAS_STD,
             f32, False),
            (at("moe", "w1"), (held, d, F), 1 / math.sqrt(d), mat, True),
            (at("moe", "w3"), (held, d, F), 1 / math.sqrt(d), mat, True),
            (at("moe", "w2"), (held, F, d), 1 / math.sqrt(F), mat, True)]
    return rows


def dense_forward(cfg, params, ids):
    """Logits [T, V] of one sequence of token ids: the layer's equations over
    the whole sequence with full masked attention, every head's keys and
    values up-projected, the expert layer through ``moe_ffn``'s einsum. No
    cache; the tests hold it to the plain reference and the served path to
    both."""
    from deepspeed_tpu.inference.v2.model_implementations.llama import _rmsnorm
    from deepspeed_tpu.inference.v2.model_implementations.moe_layer import moe_ffn
    from deepspeed_tpu.models.llama import (
        rope_frequencies, rotary_apply, rotary_tables)
    T = ids.shape[0]
    H, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    eps, dt = cfg.rms_norm_eps, cfg.dtype
    rope = rotary_tables(jnp.arange(T)[None], *rope_frequencies(
        cfg.qk_rope_head_dim, cfg.rope_theta))
    causal = jnp.tril(jnp.ones((T, T), bool))

    def attention(a, h):
        w = lambda name: a[name]["kernel"].astype(dt)
        q = _rmsnorm(h @ w("q_a_proj"), a["q_a_layernorm"]["scale"], eps) @ w("q_b_proj")
        q = q.reshape(T, H, -1) * cfg.q_scale
        ckv = h @ w("kv_a_proj")
        c = _rmsnorm(ckv[:, :r], a["kv_a_layernorm"]["scale"], eps) * cfg.kv_scale
        kv = (c.astype(dt) @ w("kv_b_proj")).reshape(T, H, dn + dv)
        q_pe = rotary_apply(q[None, ..., dn:], *rope)[0]
        k_pe = rotary_apply(ckv[None, :, None, r:], *rope)[0, :, 0]
        s = jnp.einsum("thd,shd->hts", q[..., :dn], kv[..., :dn]) \
            + jnp.einsum("thr,sr->hts", q_pe, k_pe)
        s = jnp.where(causal, s.astype(jnp.float32) * cfg.softmax_scale, -jnp.inf)
        o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1).astype(dt), kv[..., dn:])
        return o.reshape(T, H * dv) @ w("o_proj")

    def ffn(m, h):
        w = lambda name: m[name]["kernel"].astype(dt)
        return (jax.nn.silu(h @ w("gate_proj")) * (h @ w("up_proj"))) @ w("down_proj")

    x = params["embed_tokens"].astype(dt)[ids]
    for l in range(cfg.num_layers):
        p = params[f"layers_{l}"]
        norm = lambda name, v: _rmsnorm(v, p[name]["scale"], eps)
        moe = p["moe"]
        a0 = x + attention(p["self_attn_0"], norm("input_layernorm_0", x))
        h0 = norm("post_attention_layernorm_0", a0)
        m = moe_ffn(h0, moe["router"]["kernel"].astype(dt), moe["w1"].astype(dt),
                    moe["w2"].astype(dt), moe["w3"].astype(dt), k=cfg.moe_topk,
                    dtype=dt, force_einsum=True, scoring="softmax_bias",
                    score_bias=moe["router"]["bias"],
                    routed_scale=cfg.routed_scaling_factor,
                    experts_held=cfg.experts_held,
                    zero_experts=cfg.zero_expert_num)
        b0 = a0 + ffn(p["mlps_0"], h0)
        a1 = b0 + attention(p["self_attn_1"], norm("input_layernorm_1", b0))
        x = a1 + ffn(p["mlps_1"], norm("post_attention_layernorm_1", a1)) + m
    x = _rmsnorm(x, params["norm"]["scale"], eps)
    return (x @ params["lm_head"].astype(dt).T).astype(jnp.float32)


class LongcatFlashForCausalLM:
    """The model as the serving engine takes it: a configuration, a way to
    make a parameter tree, and what it keeps between dispatches."""

    def __init__(self, config):
        self.config = config

    def init_params(self, rng):
        """A random tree (normal with each row's std; constants as given)."""
        return init_tree(param_spec(self.config), rng)

    @staticmethod
    def cache_groups(cfg):
        """ONE paged group of one leaf whose planes are the layers' SUB-BLOCKS
        (plane ``2 l + j``: a latent row a token and attention), and the
        counter group the expert layers add to (``COUNTER_FIELDS``)."""
        from deepspeed_tpu.inference.v2.ragged.cache_groups import (
            CounterGroup, PagedGroup)
        return (PagedGroup("kv", 2 * cfg.num_layers, 1, cfg.latent_row_width,
                           leaves=1, value_dim=cfg.kv_lora_rank),
                CounterGroup("counters", COUNTER_FIELDS))
