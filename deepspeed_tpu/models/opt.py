"""OPT model family (TPU-native flax implementation).

Reference support: v1 kernel-injection container
(``module_inject/containers/opt.py``) and v2 implementation
(``inference/v2/model_implementations/opt``, ``engine_factory.py:99``).
Architecture vs GPT-2: learned positional embeddings with OPT's +2 offset,
biased projections, ReLU FFN, pre-LayerNorm, untied final LN. Same TPU
design as gpt2.py: scan-over-layers + remat + TP param specs.
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
class OPTConfig:
    vocab_size: int = 50272
    hidden_size: int = 768
    ffn_dim: int = 3072
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 2048
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    scan_layers: bool = True
    remat: bool = True
    dtype: Any = jnp.bfloat16

    POSITION_OFFSET = 2  # OPT reserves positions 0/1 (HF modeling_opt)

    @staticmethod
    def tiny(**kw):
        return OPTConfig(vocab_size=512, hidden_size=64, ffn_dim=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         max_position_embeddings=128, **kw)

    @staticmethod
    def opt_125m(**kw):
        return OPTConfig(**kw)

    @staticmethod
    def opt_1_3b(**kw):
        return OPTConfig(hidden_size=2048, ffn_dim=8192, num_hidden_layers=24,
                         num_attention_heads=32, **kw)

    @staticmethod
    def opt_13b(**kw):
        return OPTConfig(hidden_size=5120, ffn_dim=20480, num_hidden_layers=40,
                         num_attention_heads=40, **kw)

    @staticmethod
    def opt_30b(**kw):
        return OPTConfig(hidden_size=7168, ffn_dim=28672, num_hidden_layers=48,
                         num_attention_heads=56, **kw)


class OPTAttention(nn.Module):
    config: OPTConfig

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.config
        B, T, D = x.shape
        H = cfg.num_attention_heads
        Dh = D // H
        dense = lambda name: nn.Dense(D, use_bias=True, dtype=cfg.dtype, name=name)
        q = dense("q_proj")(x).reshape(B, T, H, Dh)
        k = dense("k_proj")(x).reshape(B, T, H, Dh)
        v = dense("v_proj")(x).reshape(B, T, H, Dh)
        from deepspeed_tpu.ops.flash_attention import mha
        out = mha(q, k, v, causal=True).reshape(B, T, D)
        return dense("out_proj")(out)


class OPTBlock(nn.Module):
    config: OPTConfig

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.config
        ln = lambda name: nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                                       dtype=cfg.dtype, name=name)
        x = x + OPTAttention(cfg, name="self_attn")(
            ln("self_attn_layer_norm")(x), deterministic)
        h = ln("final_layer_norm")(x)
        h = nn.Dense(cfg.ffn_dim, dtype=cfg.dtype, name="fc1")(h)
        h = nn.relu(h)
        h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="fc2")(h)
        h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return x + h


class ScanOPTBlock(nn.Module):
    # deterministic is a static FIELD (see ScanBloomBlock note)
    config: OPTConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, x, _):
        x = OPTBlock(self.config, name="block")(x, self.deterministic)
        return x, None


class OPTForCausalLM(nn.Module):
    """Loss when batch carries ``labels``, else logits (engine convention)."""
    config: OPTConfig

    @nn.compact
    def __call__(self, batch, deterministic=True):
        cfg = self.config
        if isinstance(batch, dict):
            input_ids = batch["input_ids"]
            labels = batch.get("labels")
        else:
            input_ids, labels = batch, None
        B, T = input_ids.shape
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        pos = self.param("embed_positions", nn.initializers.normal(0.01),
                         (cfg.max_position_embeddings + cfg.POSITION_OFFSET,
                          cfg.hidden_size), jnp.float32)
        x = embed.astype(cfg.dtype)[input_ids] + \
            pos.astype(cfg.dtype)[None, cfg.POSITION_OFFSET:cfg.POSITION_OFFSET + T]
        x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)

        if cfg.scan_layers:
            block = ScanOPTBlock
            if cfg.remat:
                block = nn.remat(ScanOPTBlock, prevent_cse=False,
                                 policy=remat_policy())
            Scanned = nn.scan(block, variable_axes={"params": 0},
                              split_rngs={"params": True, "dropout": True},
                              length=cfg.num_hidden_layers,
                              metadata_params={nn.meta.PARTITION_NAME: "layers"})
            x, _ = Scanned(cfg, deterministic, name="layers")(x, None)
        else:
            blk = nn.remat(OPTBlock, prevent_cse=False,
                           policy=remat_policy()) if cfg.remat else OPTBlock
            for i in range(cfg.num_hidden_layers):
                x = blk(cfg, name=f"layers_{i}")(x, deterministic)

        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                         name="final_layer_norm")(x)
        if labels is None:
            return x @ embed.astype(cfg.dtype).T  # tied embeddings
        from deepspeed_tpu.models.losses import lm_head_next_token_loss
        return lm_head_next_token_loss(x, embed, labels)

    # --- ZeRO-Infinity streaming protocol (runtime/zero/param_offload.py) ---
    @nn.nowrap
    def streaming_plan(self):
        if not self.config.scan_layers:
            return None
        return {"num_blocks": self.config.num_hidden_layers}

    @nn.nowrap
    def streaming_split(self, params):
        resident = {k: v for k, v in params.items() if k != "layers"}
        return resident, params["layers"]["block"]

    @nn.nowrap
    def streaming_merge(self, resident, stacked):
        out = dict(resident)
        out["layers"] = {"block": stacked}
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
        x = embed.astype(cfg.dtype)[input_ids] + \
            resident["embed_positions"].astype(cfg.dtype)[
                None, cfg.POSITION_OFFSET:cfg.POSITION_OFFSET + T]
        stochastic = rng is not None and not deterministic and cfg.dropout > 0
        if stochastic:
            x = nn.Dropout(cfg.dropout).apply(
                {}, x, deterministic=False,
                rngs={"dropout": jax.random.fold_in(rng, -1)})
        block = OPTBlock(cfg)

        def body(carry, i):
            bp = fetch(i)
            rngs = {"dropout": jax.random.fold_in(rng, i)} if stochastic else None
            return block.apply({"params": bp}, carry, deterministic,
                               rngs=rngs), None

        body = jax.checkpoint(body, prevent_cse=False)
        x, _ = jax.lax.scan(body, x, jnp.arange(cfg.num_hidden_layers))
        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype).apply(
            {"params": resident["final_layer_norm"]}, x)
        if labels is None:
            return x @ embed.astype(cfg.dtype).T
        from deepspeed_tpu.models.losses import lm_head_next_token_loss
        return lm_head_next_token_loss(x, embed, labels)

    def param_specs(self, params):
        """Megatron column/row TP pattern over q/k/v/fc1 (column) and
        out_proj/fc2 (row)."""
        cfg = self.config

        def spec_for(path, leaf):
            names = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
            joined = "/".join(names)
            scan_prefix = (None,) if (cfg.scan_layers and "layers" in names) else ()
            col = any(n in joined for n in ("q_proj", "k_proj", "v_proj", "fc1"))
            row = any(n in joined for n in ("out_proj", "fc2"))
            if leaf.ndim == 1 + len(scan_prefix):
                if col:
                    return P(*scan_prefix, "tp")
                return P(*scan_prefix) if scan_prefix else None
            if "embed_tokens" in joined:
                return P("tp", None)
            if col:
                return P(*scan_prefix, None, "tp")
            if row:
                return P(*scan_prefix, "tp", None)
            return None

        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        specs = [spec_for(path, leaf) for path, leaf in flat]
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(params), specs)
