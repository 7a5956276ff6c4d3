"""Ring attention — blockwise context parallelism.

The reference has NO ring attention (SURVEY §5: long context = Ulysses +
sparse attention); on TPU, ring attention over the ``sp`` axis is the natural
context-parallel capability filling that slot: each rank holds a sequence
block of Q/K/V, K/V blocks rotate around the ring via ``ppermute`` on ICI, and
attention accumulates with the online-softmax (flash) recurrence, so the full
[T, T] score matrix never materializes on one chip and sequence length scales
linearly with ring size.

Called inside shard_map with the ring axis bound. Causal masking uses global
positions derived from ``axis_index``.
"""

import jax
import jax.numpy as jnp
from jax import lax


NEG_INF = -1e30


def ring_attention(q, k, v, axis_name="sp", causal=True, softmax_scale=None):
    """q, k, v: local blocks [B, Tb, H, Dh] (sequence sharded over axis_name).

    Returns local attention output [B, Tb, H, Dh].
    """
    B, Tb, H, Dh = q.shape
    scale = softmax_scale if softmax_scale is not None else 1.0 / (Dh ** 0.5)
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)

    q32 = q.astype(jnp.float32) * scale
    q_pos = my * Tb + jnp.arange(Tb)  # global positions of my queries

    # online softmax state
    acc = jnp.zeros((B, Tb, H, Dh), jnp.float32)
    row_max = jnp.full((B, H, Tb), NEG_INF, jnp.float32)
    row_sum = jnp.zeros((B, H, Tb), jnp.float32)

    def step(carry, i):
        acc, row_max, row_sum, kb, vb = carry
        src = (my - i) % n  # whose KV block we currently hold
        k_pos = src * Tb + jnp.arange(Tb)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q32, kb.astype(jnp.float32))
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask[None, None], logits, NEG_INF)
        blk_max = jnp.max(logits, axis=-1)
        new_max = jnp.maximum(row_max, blk_max)
        # renormalize previous accumulator
        correction = jnp.exp(row_max - new_max)
        probs = jnp.exp(logits - new_max[..., None])
        new_sum = row_sum * correction + jnp.sum(probs, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", probs, vb.astype(jnp.float32))
        new_acc = acc * jnp.transpose(correction, (0, 2, 1))[..., None] + pv
        # rotate kv to the next rank (ring)
        perm = [(r, (r + 1) % n) for r in range(n)]
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (new_acc, new_max, new_sum, kb, vb), None

    (acc, row_max, row_sum, _, _), _ = lax.scan(
        step, (acc, row_max, row_sum, k, v), jnp.arange(n))

    denom = jnp.maximum(jnp.transpose(row_sum, (0, 2, 1))[..., None], 1e-30)
    return (acc / denom).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=True):
    """Convenience wrapper: shard_map ring_attention over sequence axis 1.
    q,k,v: global [B, T, H, Dh] arrays."""
    from jax.sharding import PartitionSpec as P
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return fn(q, k, v)
