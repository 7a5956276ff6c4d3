"""Parallel-residual decoder families: Falcon and Phi (TPU-native flax).

Reference support surface: Falcon and Phi are two of the eight v2 serving
families (``inference/v2/engine_factory.py:68-129``, ``model_implementations/
{falcon,phi}``) and v1 injection containers. Both use the *parallel* residual
``x + attn(ln(x)) + mlp(ln(x))`` (one shared input layernorm) rather than the
sequential GPT/llama block; they differ in:

- Falcon: no linear biases, fused MQA/GQA qkv projection, full rotary,
  GELU MLP (dense_h_to_4h/dense_4h_to_h), tied lm_head optional.
- Phi: biases everywhere (incl. lm_head), separate q/k/v + dense, PARTIAL
  rotary (only the first ``rotary_dim`` of each head rotates), GELU MLP
  (fc1/fc2), final layernorm with bias.

One configurable module covers both; ``falcon.py`` / ``phi.py`` provide the
family configs. Non-scanned layer naming (``layers_{i}``) like mixtral.
"""

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    current_policy as remat_policy)
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.llama import rotary_embed


@dataclasses.dataclass(frozen=True)
class ParallelBlockConfig:
    vocab_size: int = 65024
    hidden_size: int = 4544
    intermediate_size: int = 18176
    num_hidden_layers: int = 32
    num_attention_heads: int = 71
    num_key_value_heads: int = 1          # MQA (falcon-7b) by default
    max_position_embeddings: int = 2048
    layer_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rotary_pct: float = 1.0               # phi/neox/gptj: partial rotary fraction
    use_bias: bool = False                # phi/neox: True
    qkv_bias: Any = None                  # gptj: False while mlp has biases
    dense_bias: Any = None                # (None -> use_bias)
    mlp_bias: Any = None
    fused_qkv: bool = True                # falcon/neox layout; phi/gptj: False
    dual_layernorm: bool = False          # neox: mlp reads its own LN of x
    gelu_exact: bool = True               # falcon/neox: erf; phi/gptj tanh
    lm_head_bias: bool = False            # phi/gptj: True (falcon: never)
    tie_lm_head: bool = False
    remat: bool = True
    dtype: Any = jnp.bfloat16

    def _bias(self, which):
        v = getattr(self, which)
        return self.use_bias if v is None else bool(v)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def rotary_dim(self):
        rd = int(self.head_dim * self.rotary_pct)
        return rd - rd % 2


def partial_rotary(x, positions, theta, rotary_dim):
    """Rotate only the leading ``rotary_dim`` of each head (phi-style)."""
    if rotary_dim >= x.shape[-1]:
        return rotary_embed(x, positions, theta)
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([rotary_embed(rot, positions, theta), rest], axis=-1)


class _LN(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
        return ((x32 - mu) * jax.lax.rsqrt(var + self.eps) * scale + bias).astype(self.dtype)


class ParallelBlock(nn.Module):
    config: ParallelBlockConfig
    use_cache: bool = False  # module attribute: stays static under nn.remat

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        use_cache = self.use_cache
        B, T, D = x.shape
        H, KV, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        h = _LN(cfg.layer_norm_eps, cfg.dtype, name="input_layernorm")(x)
        # neox-style dual LN: the MLP branch normalizes x independently
        hm = _LN(cfg.layer_norm_eps, cfg.dtype,
                 name="post_attention_layernorm")(x) \
            if cfg.dual_layernorm else h

        dense = lambda feats, name, bias: nn.Dense(feats, use_bias=bias,
                                                   dtype=cfg.dtype, name=name)
        qb = cfg._bias("qkv_bias")
        if cfg.fused_qkv:
            qkv = dense((H + 2 * KV) * Dh, "query_key_value", qb)(h)
            q = qkv[..., : H * Dh].reshape(B, T, H, Dh)
            k = qkv[..., H * Dh: (H + KV) * Dh].reshape(B, T, KV, Dh)
            v = qkv[..., (H + KV) * Dh:].reshape(B, T, KV, Dh)
        else:
            q = dense(H * Dh, "q_proj", qb)(h).reshape(B, T, H, Dh)
            k = dense(KV * Dh, "k_proj", qb)(h).reshape(B, T, KV, Dh)
            v = dense(KV * Dh, "v_proj", qb)(h).reshape(B, T, KV, Dh)
        q = partial_rotary(q, positions, cfg.rope_theta, cfg.rotary_dim)
        k = partial_rotary(k, positions, cfg.rope_theta, cfg.rotary_dim)

        from deepspeed_tpu.ops.flash_attention import NEG_INF, mha
        if use_cache:
            L = cfg.max_position_embeddings
            ck = self.variable("cache", "cached_key", jnp.zeros, (B, L, KV, Dh), cfg.dtype)
            cv = self.variable("cache", "cached_value", jnp.zeros, (B, L, KV, Dh), cfg.dtype)
            ci = self.variable("cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            idx = ci.value
            ck.value = jax.lax.dynamic_update_slice(ck.value, k.astype(cfg.dtype), (0, idx, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(cv.value, v.astype(cfg.dtype), (0, idx, 0, 0))
            ci.value = idx + T
            key_pos = jnp.arange(L)[None, :]
            qry_pos = idx + jnp.arange(T)[:, None]
            bias = jnp.where(key_pos <= qry_pos, 0.0, NEG_INF)[None, None]
            rep = H // KV
            qg = q.reshape(B, T, KV, rep, Dh)
            scale = 1.0 / (Dh ** 0.5)
            logits = jnp.einsum("btkrd,bskd->bkrts", qg, ck.value).astype(jnp.float32) * scale
            logits = logits + bias[:, 0][:, None, None]
            probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
            attn = jnp.einsum("bkrts,bskd->btkrd", probs, cv.value).reshape(B, T, H * Dh)
        else:
            attn = mha(q, k, v, causal=True).reshape(B, T, H * Dh)
        attn_out = dense(D, "dense", cfg._bias("dense_bias"))(attn)

        mb = cfg._bias("mlp_bias")
        act = nn.gelu(dense(cfg.intermediate_size, "fc1", mb)(hm),
                      approximate=not cfg.gelu_exact)
        mlp = dense(cfg.hidden_size, "fc2", mb)(act)
        return x + attn_out + mlp


class ParallelBlockForCausalLM(nn.Module):
    """Falcon/Phi causal LM; returns loss when the batch carries labels."""
    config: ParallelBlockConfig

    @nn.compact
    def __call__(self, batch, deterministic=True, use_cache=False, positions=None):
        cfg = self.config
        if isinstance(batch, dict):
            input_ids = batch["input_ids"]
            labels = batch.get("labels")
        else:
            input_ids, labels = batch, None
        B, T = input_ids.shape
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        x = embed.astype(cfg.dtype)[input_ids]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        block_cls = nn.remat(ParallelBlock, prevent_cse=False,
                             policy=remat_policy()) \
            if (cfg.remat and not use_cache) else ParallelBlock
        for i in range(cfg.num_hidden_layers):
            x = block_cls(cfg, use_cache, name=f"layers_{i}")(x, positions)
        x = _LN(cfg.layer_norm_eps, cfg.dtype, name="final_layernorm")(x)
        head = embed if cfg.tie_lm_head else self.param(
            "lm_head", nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        hb = self.param("lm_head_bias", nn.initializers.zeros,
                        (cfg.vocab_size,), jnp.float32) \
            if (cfg.lm_head_bias and not cfg.tie_lm_head) else None
        if labels is None or hb is not None:
            # the biased head (phi) keeps the dense path — the fused CE has
            # no bias slot; falcon-size vocabs without bias go fused
            logits = x @ head.astype(cfg.dtype).T
            if hb is not None:
                logits = logits + hb.astype(cfg.dtype)
            if labels is None:
                return logits
            from deepspeed_tpu.models.losses import next_token_loss
            return next_token_loss(logits, labels)
        from deepspeed_tpu.models.losses import lm_head_next_token_loss
        return lm_head_next_token_loss(x, head, labels)

    # --- ZeRO-Infinity streaming protocol (runtime/zero/param_offload.py) ---
    # Covers falcon/phi/gptj/gpt-neox in one place (per-layer subtrees
    # stacked at split, like models/mixtral.py).
    @nn.nowrap
    def streaming_plan(self):
        return {"num_blocks": self.config.num_hidden_layers}

    @nn.nowrap
    def streaming_split(self, params):
        L = self.config.num_hidden_layers
        resident = {k: v for k, v in params.items()
                    if not k.startswith("layers_")}
        stacked = jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
                               *[params[f"layers_{i}"] for i in range(L)])
        return resident, stacked

    @nn.nowrap
    def streaming_merge(self, resident, stacked):
        out = dict(resident)
        for i in range(self.config.num_hidden_layers):
            out[f"layers_{i}"] = jax.tree.map(lambda x: x[i], stacked)
        return out

    @nn.nowrap
    def streaming_apply(self, resident, fetch, batch, deterministic=True,
                        rng=None):
        cfg = self.config
        if isinstance(batch, dict):
            input_ids, labels = batch["input_ids"], batch.get("labels")
        else:
            input_ids, labels = batch, None
        B, T = input_ids.shape
        embed = resident["embed_tokens"]
        x = embed.astype(cfg.dtype)[input_ids]
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        block = ParallelBlock(cfg)

        def body(carry, i):
            bp = fetch(i)
            return block.apply({"params": bp}, carry, positions), None

        body = jax.checkpoint(body, prevent_cse=False)
        x, _ = jax.lax.scan(body, x, jnp.arange(cfg.num_hidden_layers))
        x = _LN(cfg.layer_norm_eps, cfg.dtype).apply(
            {"params": resident["final_layernorm"]}, x)
        head = embed if cfg.tie_lm_head else resident["lm_head"]
        hb = resident.get("lm_head_bias") \
            if (cfg.lm_head_bias and not cfg.tie_lm_head) else None
        if labels is None or hb is not None:
            logits = x @ head.astype(cfg.dtype).T
            if hb is not None:
                logits = logits + hb.astype(cfg.dtype)
            if labels is None:
                return logits
            from deepspeed_tpu.models.losses import next_token_loss
            return next_token_loss(logits, labels)
        from deepspeed_tpu.models.losses import lm_head_next_token_loss
        return lm_head_next_token_loss(x, head, labels)

    def param_specs(self, params):
        """Megatron TP: qkv/fc1 column-split, dense/fc2 row-split, vocab-split
        embeddings (same pattern as models/llama.py)."""
        def spec_for(path, leaf):
            names = "/".join(str(getattr(p, "key", getattr(p, "name", ""))) for p in path)
            if getattr(leaf, "ndim", 0) <= 1:
                return None
            if "embed_tokens" in names or "lm_head" in names:
                return P("tp", None)
            if any(s in names for s in ("query_key_value", "q_proj", "k_proj",
                                        "v_proj", "fc1")):
                return P(None, "tp")
            if any(s in names for s in ("dense/", "fc2")) or names.endswith("dense/kernel"):
                return P("tp", None)
            return None

        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        specs = [spec_for(p, l) for p, l in flat]
        return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(params), specs)
