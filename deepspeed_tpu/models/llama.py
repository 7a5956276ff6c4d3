"""Llama model family (flagship) — TPU-native flax implementation.

Covers the reference's Llama support surface (inference containers
``module_inject/containers/llama.py``, v2 model implementation
``inference/v2/model_implementations/llama_v2``) as a first-class training +
inference model: RMSNorm, rotary embeddings, SwiGLU MLP, grouped-query
attention. Same TPU design as gpt2.py: scan-over-layers + remat + TP
PartitionSpecs (Megatron column/row pattern).
"""

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    current_policy as remat_policy)
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    attention_bias: bool = False      # qkv bias (Qwen2-family)
    attention_out_bias: bool = False  # o_proj bias too (InternLM-family)
    sliding_window: Any = None        # local-window attention (Mistral-family)
    # None/"flash": the Pallas flash kernel (XLA fallback). "ring": blockwise
    # context parallelism over the sp mesh axis (ops/ring_attention.py) — K/V
    # rotate around the ring via ppermute, sequence length scales linearly
    # with ring size; requires the global topology's sp axis > 1.
    attention_impl: Any = None
    head_dim: Any = None              # explicit override (Mistral-Nemo style);
    # None derives hidden_size // num_attention_heads (resolved in __post_init__)
    scan_layers: bool = True
    remat: bool = True
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.hidden_size // self.num_attention_heads)

    @staticmethod
    def tiny(**kw):
        return LlamaConfig(vocab_size=512, hidden_size=64, intermediate_size=128,
                           num_hidden_layers=2, num_attention_heads=4,
                           num_key_value_heads=2, max_position_embeddings=128, **kw)

    @staticmethod
    def llama2_7b(**kw):
        return LlamaConfig(**kw)

    @staticmethod
    def llama2_13b(**kw):
        return LlamaConfig(hidden_size=5120, intermediate_size=13824,
                           num_hidden_layers=40, num_attention_heads=40,
                           num_key_value_heads=40, **kw)

    @staticmethod
    def llama2_70b(**kw):
        return LlamaConfig(hidden_size=8192, intermediate_size=28672,
                           num_hidden_layers=80, num_attention_heads=64,
                           num_key_value_heads=8, **kw)

    def num_parameters(self):
        c = self
        qo = c.num_attention_heads * c.head_dim
        per_layer = (c.hidden_size * qo  # q
                     + 2 * c.hidden_size * c.num_key_value_heads * c.head_dim  # k,v
                     + qo * c.hidden_size  # o
                     + 3 * c.hidden_size * c.intermediate_size  # gate,up,down
                     + 2 * c.hidden_size)  # norms
        return (c.vocab_size * c.hidden_size * 2  # embed + lm_head
                + c.num_hidden_layers * per_layer + c.hidden_size)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(self.dtype)


def rotary_embed(x, positions, theta=10000.0):
    """Apply rotary position embeddings. x: [B, T, H, Dh]."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,T,dh/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    rx1 = x1 * cos - x2 * sin
    rx2 = x2 * cos + x1 * sin
    out = jnp.stack([rx1, rx2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def rope_frequencies(head_dim, theta, yarn=None):
    """``(inv_freq [head_dim / 2], scale)`` of one kind of layer: the default
    ``theta ** (-2i / head_dim)`` with scale 1, or with ``yarn`` (the
    ``rope_parameters`` section of such a layer type: ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``) YaRN's blend ``interp * (1 - e) + extrap * e``,
    ``interp = extrap / factor``, ``e`` falling from 1 to 0 between the
    dimensions that turn ``beta_fast`` and ``beta_slow`` times over the
    original length, with cos and sin scaled by ``attention_factor`` (None:
    ``0.1 ln(factor) + 1``). Plain Python and numpy: a model computes its
    tables once, outside its layer loop."""
    import math

    import numpy as np
    i = np.arange(0, head_dim, 2, dtype=np.float64)
    extrap = theta ** (-i / head_dim)
    if not yarn:
        return jnp.asarray(extrap, jnp.float32), 1.0
    factor = float(yarn["factor"])
    orig = yarn["original_max_position_embeddings"]

    def turns(beta):          # the dimension that turns ``beta`` times
        return head_dim * math.log(orig / (beta * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns(yarn.get("beta_fast", 32))), 0)
    high = min(math.ceil(turns(yarn.get("beta_slow", 1))), head_dim - 1)
    ramp = np.clip((i / 2 - low) / max(high - low, 1e-3), 0.0, 1.0)
    e = 1.0 - ramp
    inv_freq = (extrap / factor) * (1.0 - e) + extrap * e
    scale = yarn.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return jnp.asarray(inv_freq, jnp.float32), float(scale)


def rotary_tables(positions, inv_freq, scale=1.0):
    """``(cos, sin)`` ``[B, T, 1, Dh / 2]`` float32 of ``rope_frequencies``'s
    result at ``positions`` [B, T]."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return (jnp.cos(angles) * scale)[:, :, None, :], \
        (jnp.sin(angles) * scale)[:, :, None, :]


def rotary_apply(x, cos, sin):
    """``rotary_embed`` with the tables given (``rotary_tables``): the same
    adjacent-column pairs. x: [B, T, H, Dh]."""
    x1, x2 = x[..., ::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, deterministic=True, use_cache=False):
        cfg = self.config
        B, T, D = x.shape
        H, KV, Dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        dense = lambda feats, name, bias=False: nn.Dense(
            feats, use_bias=bias, dtype=cfg.dtype, name=name)
        ab = cfg.attention_bias
        q = dense(H * Dh, "q_proj", ab)(x).reshape(B, T, H, Dh)
        k = dense(KV * Dh, "k_proj", ab)(x).reshape(B, T, KV, Dh)
        v = dense(KV * Dh, "v_proj", ab)(x).reshape(B, T, KV, Dh)
        q = rotary_embed(q, positions, cfg.rope_theta)
        k = rotary_embed(k, positions, cfg.rope_theta)
        from deepspeed_tpu.ops.flash_attention import mha, NEG_INF

        if use_cache:
            # KV cache over a fixed max_position window; works for both prefill
            # (T = prompt length at index 0) and incremental decode (T = 1).
            # Functional analog of the reference's inference KV-cache kernels
            # (csrc/transformer/inference/csrc/pt_binding.cpp attention path).
            L = cfg.max_position_embeddings
            cached_k = self.variable("cache", "cached_key", jnp.zeros,
                                     (B, L, KV, Dh), cfg.dtype)
            cached_v = self.variable("cache", "cached_value", jnp.zeros,
                                     (B, L, KV, Dh), cfg.dtype)
            cache_index = self.variable("cache", "cache_index",
                                        lambda: jnp.zeros((), jnp.int32))
            idx = cache_index.value
            cached_k.value = jax.lax.dynamic_update_slice(
                cached_k.value, k.astype(cfg.dtype), (0, idx, 0, 0))
            cached_v.value = jax.lax.dynamic_update_slice(
                cached_v.value, v.astype(cfg.dtype), (0, idx, 0, 0))
            cache_index.value = idx + T
            k, v = cached_k.value, cached_v.value
            # position j attends iff j <= idx + i (past + causal-within-block)
            key_pos = jnp.arange(L)[None, :]
            qry_pos = idx + jnp.arange(T)[:, None]
            visible = key_pos <= qry_pos
            if cfg.sliding_window:
                visible = visible & (key_pos > qry_pos - cfg.sliding_window)
            bias = jnp.where(visible, 0.0, NEG_INF)
            # grouped-query attention against the un-repeated cache: expanding
            # only the [B,T,H,Dh] query (not the [B,L,KV,Dh] cache) keeps decode
            # memory traffic at 1x the cache size
            rep = H // KV
            qg = q.reshape(B, T, KV, rep, Dh)
            scale = 1.0 / (Dh ** 0.5)
            logits = jnp.einsum("btkrd,bskd->bkrts", qg, k).astype(jnp.float32) * scale
            logits = logits + bias[None, None, None]
            probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
            out = jnp.einsum("bkrts,bskd->btkrd", probs, v).reshape(B, T, H, Dh)
        elif cfg.attention_impl == "ring":
            # context parallelism: sequence stays sharded over sp; K/V blocks
            # rotate on ICI (ring_attention.py). GQA keys/values expand to
            # full heads first — the ring recurrence is per-head.
            from deepspeed_tpu.ops.ring_attention import ring_attention_sharded
            from deepspeed_tpu.parallel import groups
            topo = groups.get_topology()
            if topo.sp_size <= 1:
                raise ValueError(
                    "attention_impl='ring' needs an sp mesh axis > 1 "
                    "(sequence_parallel_size in the engine config)")
            rep = H // KV
            if rep > 1:
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            out = ring_attention_sharded(q, k, v, topo.mesh, causal=True)
        else:
            # GQA k/v pass through un-repeated — both mha implementations
            # handle head grouping internally (flash kernel maps q head h to
            # kv head h // rep in its index maps; no rep× HBM traffic).
            # Mistral-style sliding window goes through the kernel's window
            # parameter (whole-block skipping, O(T·W)) — never a [T,T] bias.
            out = mha(q, k, v, causal=True,
                      window=cfg.sliding_window or None)
        out = out.reshape(B, T, H * Dh)
        return dense(D, "o_proj", cfg.attention_out_bias)(out)


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = lambda feats, name: nn.Dense(feats, use_bias=False, dtype=cfg.dtype, name=name)
        gate = nn.silu(dense(cfg.intermediate_size, "gate_proj")(x))
        up = dense(cfg.intermediate_size, "up_proj")(x)
        return dense(cfg.hidden_size, "down_proj")(gate * up)


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, positions, deterministic=True, use_cache=False):
        cfg = self.config
        x = x + LlamaAttention(cfg, name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_layernorm")(x),
            positions, deterministic, use_cache=use_cache)
        x = x + LlamaMLP(cfg, name="mlp")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="post_attention_layernorm")(x))
        return x


class ScanLlamaBlock(nn.Module):
    config: LlamaConfig
    use_cache: bool = False

    @nn.compact
    def __call__(self, carry, _):
        x, positions = carry
        x = LlamaBlock(self.config, name="block")(x, positions,
                                                  use_cache=self.use_cache)
        return (x, positions), None


class LlamaForCausalLM(nn.Module):
    """Returns LM loss when batch carries ``labels`` (DeepSpeed convention)."""
    config: LlamaConfig

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

        if cfg.scan_layers:
            block = ScanLlamaBlock
            if cfg.remat and not use_cache:
                block = nn.remat(ScanLlamaBlock, prevent_cse=False,
                                 policy=remat_policy())
            Scanned = nn.scan(block,
                              variable_axes={"params": 0, "cache": 0},
                              split_rngs={"params": True, "dropout": True},
                              length=cfg.num_hidden_layers,
                              metadata_params={nn.meta.PARTITION_NAME: "layers"})
            (x, _), _ = Scanned(cfg, use_cache, name="layers")((x, positions), None)
        else:
            block_cls = nn.remat(LlamaBlock, prevent_cse=False,
                                 policy=remat_policy()) \
                if (cfg.remat and not use_cache) else LlamaBlock
            for i in range(cfg.num_hidden_layers):
                x = block_cls(cfg, name=f"layers_{i}")(x, positions, deterministic,
                                                       use_cache=use_cache)

        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        lm_head = self.param("lm_head", nn.initializers.normal(0.02),
                             (cfg.vocab_size, cfg.hidden_size), jnp.float32)

        if labels is None:
            return x @ lm_head.astype(cfg.dtype).T
        # training: fused chunked linear+CE for large vocabs — never
        # materializes the [B, T, V] logits (models/losses.py)
        from deepspeed_tpu.models.losses import lm_head_next_token_loss
        return lm_head_next_token_loss(x, lm_head, labels)

    # --- ZeRO-Infinity streaming protocol (runtime/zero/param_offload.py) ---
    # The engine's offload_param mode drives the layer stack through these
    # instead of __call__: block weights are fetched from the host/NVMe tier
    # inside the scan body, so HBM never holds the stacked parameters.
    @nn.nowrap
    def streaming_plan(self):
        if not self.config.scan_layers:
            return None
        return {"num_blocks": self.config.num_hidden_layers}

    @nn.nowrap
    def streaming_split(self, params):
        """(resident, stacked): resident leaves stay device-side (the
        ``stage3_param_persistence_threshold`` analog), stacked leaves carry
        the leading scan dim and live in the host tier."""
        resident = {k: v for k, v in params.items() if k != "layers"}
        return resident, params["layers"]["block"]

    @nn.nowrap
    def streaming_merge(self, resident, stacked):
        out = dict(resident)
        out["layers"] = {"block": stacked}
        return out

    @nn.nowrap
    def streaming_apply(self, resident, fetch, batch, deterministic=True,
                        rng=None, prefetch_depth=0):
        """Forward pass with per-block parameter streaming. ``fetch(i)``
        returns block ``i``'s parameter tree (engine-provided, differentiable;
        its backward routes the block's grads to the host tier). ``rng`` (a
        PRNGKey) is folded per block for stochastic layers. ``prefetch_depth``
        keeps that many blocks' fetches in flight ahead of compute
        (overlap_schedule.scheduled_scan; 0 = fetch at use). Numerics are
        identical to ``__call__`` — same modules, same order."""
        cfg = self.config
        if isinstance(batch, dict):
            input_ids, labels = batch["input_ids"], batch.get("labels")
        else:
            input_ids, labels = batch, None
        B, T = input_ids.shape
        x = resident["embed_tokens"].astype(cfg.dtype)[input_ids]
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        block = LlamaBlock(cfg)

        def block_fn(carry, bp, i):
            rngs = {"dropout": jax.random.fold_in(rng, i)} \
                if (rng is not None and not deterministic) else None
            return block.apply({"params": bp}, carry, positions,
                               deterministic, rngs=rngs)

        # save-nothing remat regardless of the configured policy: a policy
        # that saved the fetched weights would pin all L blocks in HBM and
        # defeat the tier. Backward re-streams each block (the reference
        # re-gathers partitions for backward the same way).
        from deepspeed_tpu.runtime.zero.overlap_schedule import scheduled_scan
        x = scheduled_scan(block_fn, x, cfg.num_hidden_layers, fetch,
                           prefetch_depth=prefetch_depth, remat=True)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype).apply(
            {"params": resident["norm"]}, x)
        lm_head = resident["lm_head"]
        if labels is None:
            return x @ lm_head.astype(cfg.dtype).T
        from deepspeed_tpu.models.losses import lm_head_next_token_loss
        return lm_head_next_token_loss(x, lm_head, labels)

    def param_specs(self, params):
        """Megatron-style TP specs: q/k/v/gate/up column-split, o/down row-split,
        embeddings vocab-split."""
        cfg = self.config

        def spec_for(path, leaf):
            names = "/".join(str(getattr(p, "key", getattr(p, "name", ""))) for p in path)
            scan_prefix = (None,) if (cfg.scan_layers and "layers/" in names) else ()
            if leaf.ndim == 1 + len(scan_prefix):
                return None
            if "embed_tokens" in names or "lm_head" in names:
                return P("tp", None)
            if any(k in names for k in ("q_proj", "k_proj", "v_proj", "gate_proj", "up_proj")):
                return P(*scan_prefix, None, "tp")
            if any(k in names for k in ("o_proj", "down_proj")):
                return P(*scan_prefix, "tp", None)
            return None

        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        specs = [spec_for(path, leaf) for path, leaf in flat]
        return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(params), specs)


def llama_flops_per_token(cfg: LlamaConfig, seq_len: int) -> float:
    """Training FLOPs/token ≈ 6N + attention quadratic term."""
    return 6 * cfg.num_parameters() + 12 * cfg.num_hidden_layers * cfg.hidden_size * seq_len
