"""Mixtral (sparse MoE) model family.

Covers the reference's Mixtral support (``inference/v2/model_implementations/
mixtral``) as a first-class training+inference model: Llama backbone with a
top-2-of-8 expert MLP per layer, experts sharded over the ``ep`` mesh axis via
the MoE layer (``deepspeed_tpu/moe``). The per-layer router aux losses are
summed into the LM loss with ``router_aux_loss_coef`` exactly as HF Mixtral
does.
"""

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import flax.linen as nn
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    current_policy as remat_policy)
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.llama import LlamaAttention, LlamaConfig, RMSNorm
from deepspeed_tpu.moe.sharded_moe import MOELayer


@dataclasses.dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_local_experts: int = 8
    num_experts_per_tok: int = 2
    router_aux_loss_coef: float = 0.02
    capacity_factor: float = 2.0
    # "indices" (routed gather/scatter, default) | "einsum" (GShard oracle) |
    # "gmm" (megablox grouped GEMM, capacity-free; needs 128-aligned dims)
    moe_backend: str = "indices"
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    remat: bool = True
    dtype: Any = jnp.bfloat16

    @staticmethod
    def tiny(**kw):
        return MixtralConfig(vocab_size=512, hidden_size=64, intermediate_size=128,
                             num_hidden_layers=2, num_attention_heads=4,
                             num_key_value_heads=2, num_local_experts=4,
                             max_position_embeddings=128, **kw)

    @staticmethod
    def mixtral_8x7b(**kw):
        return MixtralConfig(**kw)

    def as_llama(self):
        return LlamaConfig(vocab_size=self.vocab_size, hidden_size=self.hidden_size,
                           intermediate_size=self.intermediate_size,
                           num_hidden_layers=self.num_hidden_layers,
                           num_attention_heads=self.num_attention_heads,
                           num_key_value_heads=self.num_key_value_heads,
                           max_position_embeddings=self.max_position_embeddings,
                           rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
                           dtype=self.dtype)


class MixtralExpertMLP(nn.Module):
    config: MixtralConfig

    # grouped-GEMM backend contract (moe/sharded_moe.py dispatch_mode="gmm"):
    # silu(x@w1) * (x@w3) @ w2, kernels listed gate/up/down
    GMM_COMPAT = ("w1", "w3", "w2")

    def gmm_shapes(self, d_model):
        f = self.config.intermediate_size
        return {"w1": (d_model, f), "w3": (d_model, f), "w2": (f, d_model)}

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = lambda feats, name: nn.Dense(feats, use_bias=False, dtype=cfg.dtype, name=name)
        gate = nn.silu(dense(cfg.intermediate_size, "w1")(x))
        up = dense(cfg.intermediate_size, "w3")(x)
        return dense(cfg.hidden_size, "w2")(gate * up)


class MixtralBlock(nn.Module):
    config: MixtralConfig

    @nn.compact
    def __call__(self, x, positions, train=True):
        cfg = self.config
        x = x + LlamaAttention(cfg.as_llama(), name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_layernorm")(x), positions)
        moe_out, l_aux, _ = MOELayer(
            lambda: MixtralExpertMLP(cfg),
            num_experts=cfg.num_local_experts,
            k=cfg.num_experts_per_tok,
            capacity_factor=cfg.capacity_factor,
            eval_capacity_factor=cfg.capacity_factor,
            dispatch_mode=cfg.moe_backend,
            name="block_sparse_moe")(
                RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="post_attention_layernorm")(x),
                train)
        return x + moe_out, l_aux


class MixtralForCausalLM(nn.Module):
    config: MixtralConfig

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
        x = embed.astype(cfg.dtype)[input_ids]
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))

        total_aux = 0.0
        block_cls = nn.remat(MixtralBlock, prevent_cse=False,
                             policy=remat_policy(),
                             static_argnums=(3,)) if cfg.remat else MixtralBlock
        for i in range(cfg.num_hidden_layers):
            x, l_aux = block_cls(cfg, name=f"layers_{i}")(x, positions,
                                                          not deterministic)
            total_aux = total_aux + l_aux

        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        lm_head = self.param("lm_head", nn.initializers.normal(0.02),
                             (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        if labels is None:
            return x @ lm_head.astype(cfg.dtype).T
        from deepspeed_tpu.models.losses import lm_head_next_token_loss
        lm_loss = lm_head_next_token_loss(x, lm_head, labels)
        return lm_loss + cfg.router_aux_loss_coef * total_aux / cfg.num_hidden_layers

    # --- ZeRO-Infinity streaming protocol (runtime/zero/param_offload.py) ---
    # MoE is the headline Infinity workload: expert weights dominate the
    # parameter count (reference zero/parameter_offload.py was built for
    # trillion-param MoE on few devices). Mixtral's layers are homogeneous
    # per-layer subtrees (layers_i); the split stacks them so the host tier
    # streams one block — attention + ALL its experts — at a time.
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
        x = resident["embed_tokens"].astype(cfg.dtype)[input_ids]
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        block = MixtralBlock(cfg)

        def body(carry, i):
            h, aux = carry
            bp = fetch(i)
            rngs = {"dropout": jax.random.fold_in(rng, i)} \
                if (rng is not None and not deterministic) else None
            h, l_aux = block.apply({"params": bp}, h, positions,
                                   not deterministic, rngs=rngs)
            return (h, aux + l_aux.astype(jnp.float32)), None

        body = jax.checkpoint(body, prevent_cse=False)
        (x, total_aux), _ = jax.lax.scan(
            body, (x, jnp.float32(0.0)), jnp.arange(cfg.num_hidden_layers))
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype).apply(
            {"params": resident["norm"]}, x)
        lm_head = resident["lm_head"]
        if labels is None:
            return x @ lm_head.astype(cfg.dtype).T
        from deepspeed_tpu.models.losses import lm_head_next_token_loss
        lm_loss = lm_head_next_token_loss(x, lm_head, labels)
        return lm_loss + cfg.router_aux_loss_coef * total_aux / cfg.num_hidden_layers

    def param_specs(self, params):
        """TP specs for attention + ep sharding for stacked experts."""
        def spec_for(path, leaf):
            names = "/".join(str(getattr(p, "key", getattr(p, "name", ""))) for p in path)
            if "experts" in names:
                if leaf.ndim >= 2:
                    # [E, in, out] expert kernels: ep on expert axis, tp on the
                    # column/row dim matching Megatron pattern
                    if "w1" in names or "w3" in names:
                        return P("ep", None, "tp")
                    if "w2" in names:
                        return P("ep", "tp", None)
                return P("ep")
            if leaf.ndim == 1:
                return None
            if "embed_tokens" in names or "lm_head" in names:
                return P("tp", None)
            if any(k in names for k in ("q_proj", "k_proj", "v_proj")):
                return P(None, "tp")
            if "o_proj" in names:
                return P("tp", None)
            return None

        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        specs = [spec_for(p, l) for p, l in flat]
        return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(params), specs)
