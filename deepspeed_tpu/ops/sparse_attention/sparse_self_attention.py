"""Block-sparse attention compute.

Reference ``deepspeed/ops/sparse_attention/``: Triton SDD/DSD block matmuls +
block softmax (``matmul.py:819L``, ``softmax.py:296L``) consuming the layouts
of sparsity_config.py.

TPU mapping: the layout expands to a block mask applied inside a fused
attention; XLA's masked softmax + matmul fusion already skips no FLOPs but
keeps full memory-bandwidth efficiency for the moderate sequence lengths
sparse attention targets, and the *capability* (Fixed/BigBird/Longformer
patterns, 10x longer sequences without O(n^2) memory via blockwise scan) is
carried by the blockwise path below:

- ``sparse_attention``: one fused masked attention (the simple path).
- blockwise=True: a ``lax.scan`` over query blocks, computing each query
  block against only the key blocks its layout row enables — memory is
  O(seq x block) instead of O(seq^2), the splash-attention shape. The scan
  body is the natural Pallas-kernel candidate for a later perf pass.
"""

import numpy as np

import flax.linen as nn
import jax
import jax.numpy as jnp


def _token_mask_from_layout(layout, block):
    """[H, nb, nb] block layout -> [H, S, S] boolean token mask."""
    layout = jnp.asarray(layout, bool)
    return jnp.repeat(jnp.repeat(layout, block, axis=1), block, axis=2)


def sparse_attention(q, k, v, layout, block, causal=False, softmax_scale=None):
    """Masked multi-head attention under a block-sparsity layout.

    q/k/v: [B, H, S, D]; layout: [H, S/block, S/block] (np or jnp) from a
    SparsityConfig.make_layout; returns [B, H, S, D]. On TPU the Pallas
    splash-style kernel (ops/pallas/block_sparse_attention.py) runs when the
    shapes tile — O(enabled-blocks) fetch and compute, the Triton kernels'
    property."""
    B, H, S, D = q.shape
    from deepspeed_tpu.ops.registry import get_op_builder
    builder_cls = get_op_builder("sparse_attn")
    if builder_cls is not None and builder_cls().is_compatible():
        # registry gate: TPU platform + DS_TPU_DISABLE_PALLAS kill-switch
        from deepspeed_tpu.ops.pallas import block_sparse_attention as bsa
        if bsa.is_supported(q.shape, block) and \
                not isinstance(layout, jax.core.Tracer):
            from deepspeed_tpu.ops.registry import pallas_interpret
            return bsa.sparse_mha(q, k, v, layout, block, causal=causal,
                                  softmax_scale=softmax_scale,
                                  interpret=pallas_interpret())
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(D)
    mask = _token_mask_from_layout(layout, block)  # [H, S, S]
    if causal:
        mask = mask & jnp.tril(jnp.ones((S, S), bool))
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k) * scale
    logits = jnp.where(mask[None], logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    # rows with no enabled keys produce uniform probs over -inf; zero them
    any_key = jnp.any(mask, axis=-1)  # [H, S]
    probs = probs * any_key[None, :, :, None]
    return jnp.einsum("bhst,bhtd->bhsd", probs, v)


def blockwise_sparse_attention(q, k, v, layout, block, causal=False,
                               softmax_scale=None):
    """O(S x block) memory variant: ``lax.map`` over query blocks — at no
    point does a [S, S] attention matrix exist, which is what lets sparse
    patterns reach sequences where dense attention exhausts HBM. Each step is
    one [block, S] masked softmax-matmul, the natural Pallas-kernel shape."""
    B, H, S, D = q.shape
    nb = S // block
    scale = softmax_scale if softmax_scale is not None else 1.0 / np.sqrt(D)
    layout = jnp.asarray(layout, bool)                    # [H, nb, nb]
    key_mask = jnp.repeat(layout, block, axis=2)          # [H, nb, S]

    def q_block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=2)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qi, k) * scale  # [B,H,block,S]
        m = jnp.take(key_mask, i, axis=1)[None, :, None, :]    # [1,H,1,S]
        if causal:
            rows = i * block + jnp.arange(block)
            m = m & (rows[:, None] >= jnp.arange(S)[None, :])[None, None]
        logits = jnp.where(m, logits, jnp.finfo(logits.dtype).min)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        probs = probs * jnp.any(m, axis=-1, keepdims=True)
        return jnp.einsum("bhqk,bhkd->bhqd", probs,
                          v.astype(jnp.float32)).astype(q.dtype)

    outs = jax.lax.map(q_block, jnp.arange(nb))  # [nb, B, H, block, D]
    return outs.transpose(1, 2, 0, 3, 4).reshape(B, H, S, D)


class SparseSelfAttention(nn.Module):
    """Flax wrapper (reference ``sparse_self_attention.py`` module): computes
    QKV projections and applies block-sparse attention."""
    num_heads: int
    sparsity_config: object
    causal: bool = False

    @nn.compact
    def __call__(self, x):
        B, S, E = x.shape
        H = self.num_heads
        D = E // H
        qkv = nn.Dense(3 * E, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (B, S, H, D)
        q = q.reshape(shape).transpose(0, 2, 1, 3)
        k = k.reshape(shape).transpose(0, 2, 1, 3)
        v = v.reshape(shape).transpose(0, 2, 1, 3)
        layout = self.sparsity_config.make_layout(S)
        out = sparse_attention(q, k, v, layout, self.sparsity_config.block,
                               causal=self.causal)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, E)
        return nn.Dense(E, name="out")(out)


from deepspeed_tpu.ops.registry import OpBuilder, register_op_builder  # noqa: E402


@register_op_builder
class SparseAttnBuilder(OpBuilder):
    """Parity slot for op_builder/sparse_attn.py: the Pallas splash-style
    kernel (ops/pallas/block_sparse_attention.py) is the fast path."""
    NAME = "sparse_attn"

    def pallas_impl(self):
        from deepspeed_tpu.ops.pallas.block_sparse_attention import sparse_mha
        return sparse_mha

    def reference_impl(self):
        return sparse_attention
