"""Evoformer (DS4Science) attention — MSA attention with pair biases.

Reference ``deepspeed/ops/deepspeed4science/evoformer_attn.py`` (CUTLASS fMHA
kernels under ``csrc/deepspeed4science/evoformer_attn/``): attention over MSA
rows/columns with two additive biases — a [B, 1, 1, 1, Nk] residue mask and a
[B, 1, H, Nq, Nk] pair bias — as used by OpenFold/AlphaFold triangle blocks.

TPU design: the two biases broadcast-sum into the flash kernel's single
additive-bias slot (``ops/pallas/flash_attention.py`` handles [B|1, H|1, N, N]
biases natively), with leading MSA dims folded into the batch. Shapes follow
the reference API: Q/K/V ``[*, N, H, D]`` with leading ``[B, S]`` MSA dims.
"""

import jax.numpy as jnp

from deepspeed_tpu.ops.flash_attention import mha, mha_reference
from deepspeed_tpu.ops.registry import OpBuilder, register_op_builder


def DS4Sci_EvoformerAttention(Q, K, V, biases):
    """Evoformer attention (reference API parity).

    Q/K/V: ``[B, S, N, H, D]`` (batch, MSA rows, residues, heads, head dim).
    biases: list of additive biases broadcastable to ``[B, S, H, N, N]`` —
    conventionally ``bias1`` [B, 1, 1, 1, N] (residue mask) and ``bias2``
    [B, 1, H, N, N] (pair bias). Returns ``[B, S, N, H, D]``.
    """
    B, S, N, H, D = Q.shape
    bias = None
    for b in biases:
        bias = b if bias is None else bias + b
    q = Q.reshape(B * S, N, H, D)
    k = K.reshape(B * S, N, H, D)
    v = V.reshape(B * S, N, H, D)
    if bias is not None:
        bias = bias.astype(jnp.float32)
        # expand the residue dims, but keep batch/MSA/head dims singleton — a
        # dense [B*S, H, N, N] fp32 bias at evoformer scale would be GBs of
        # HBM for nothing
        bias = jnp.broadcast_to(bias, bias.shape[:3] + (N, N))
        _, bS, bH = bias.shape[0], bias.shape[1], bias.shape[2]
        if bias.shape[0] == 1 and bS == 1:
            bias = bias.reshape(1, bH, N, N)
        elif bS == 1 and B > 1:
            # per-complex bias with batch folded: materialization is the only
            # layout mha's batch indexing understands here
            bias = jnp.broadcast_to(bias, (B, S, bH, N, N)) \
                .reshape(B * S, bH, N, N)
        else:
            bias = jnp.broadcast_to(bias, (B, S, H, N, N)).reshape(B * S, H, N, N)
    out = mha(q, k, v, bias=bias, causal=False)
    return out.reshape(B, S, N, H, D)


def evoformer_attn_reference(Q, K, V, biases):
    """Pure-einsum twin for numerics tests."""
    logits = jnp.einsum("bsqhd,bskhd->bshqk", Q, K).astype(jnp.float32)
    logits = logits / (Q.shape[-1] ** 0.5)
    for b in biases:
        logits = logits + b
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    return jnp.einsum("bshqk,bskhd->bsqhd", probs.astype(Q.dtype), V)


@register_op_builder
class EvoformerAttnBuilder(OpBuilder):
    """Parity slot for op_builder/evoformer_attn.py: the flash-attention
    kernel with additive bias IS the fast path."""
    NAME = "evoformer_attn"

    def pallas_impl(self):
        from deepspeed_tpu.ops.pallas.flash_attention import flash_mha  # noqa: F401
        return DS4Sci_EvoformerAttention

    def reference_impl(self):
        return DS4Sci_EvoformerAttention
