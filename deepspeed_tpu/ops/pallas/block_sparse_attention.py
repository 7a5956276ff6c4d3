"""Pallas block-sparse attention (splash-attention shape).

Capability analog of the reference's Triton block-sparse kernels
(``deepspeed/ops/sparse_attention/{matmul.py,softmax.py}`` — SDD/DSD block
matmuls + block softmax over Fixed/BigBird/Longformer layouts from
``sparsity_config.py``), built for the TPU pipeline model:

- the static [H, nq, nk] block layout is compacted host-side into per-(head,
  query-block) lists of enabled key-block indices plus counts;
- the lists are scalar-prefetched, and the K/V BlockSpec index maps read them
  directly: the pipeline DMAs exactly the enabled blocks (indices past the
  count clamp to the last enabled one, which Pallas de-duplicates) — both
  HBM traffic and MXU FLOPs are O(enabled blocks), the Triton kernels'
  property;
- online-softmax scratch carries (m, l, acc) across the enabled-block
  iterations per query block.

Backward runs through the blockwise-scan XLA path (same masked-softmax
function, O(S x block) memory) via custom_vjp recompute.

Layout convention matches ``ops/sparse_attention``: q/k/v [B, H, S, D].
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9
LANES = 128


def compact_layout(layout, causal, block):
    """[H, nq, nk] 0/1 layout -> (cols [H, nq, C], counts [H, nq]) int32.

    Causal folds in by dropping blocks entirely above the diagonal; C is the
    max enabled count over all (h, iq); padding repeats the last enabled
    index (or 0 when a row has none — counts gates the compute). Pure
    vectorized numpy: the layout must be concrete (host-side schedule)."""
    if isinstance(layout, jax.core.Tracer):
        raise TypeError("block-sparse kernel schedules are built host-side; "
                        "pass a concrete (numpy) layout, not a traced array")
    layout = np.asarray(layout, bool).copy()
    H, nq, nk = layout.shape
    if causal:
        # equal q/k block sizes: a block is fully above the diagonal iff ik > iq
        layout &= np.tril(np.ones((nq, nk), bool))[None]
    counts = layout.sum(axis=-1).astype(np.int32)
    C = max(int(counts.max()), 1)
    # stable argsort of ~layout lists enabled column indices first, ascending
    order = np.argsort(~layout, axis=-1, kind="stable")[:, :, :C].astype(np.int32)
    slot = np.arange(C)[None, None, :]
    last = np.take_along_axis(
        order, np.maximum(counts - 1, 0)[:, :, None], axis=-1)
    cols = np.where(slot < counts[:, :, None], order, last)
    cols = np.where(counts[:, :, None] == 0, 0, cols).astype(np.int32)
    return cols, counts


def _kernel(cols_ref, counts_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, block, n_steps, causal, scale):
    h, iq, j = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j < counts_ref[h, iq])
    def _body():
        q = q_ref[0, 0]                       # [block, D]
        k = k_ref[0, 0]                       # [block, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            ik = cols_ref[h, iq, j]
            qpos = iq * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = ik * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0, 0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

    @pl.when(j == n_steps - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # rows with zero enabled keys output 0 (matches the dense path's
        # zeroing of fully-masked rows)
        out = jnp.where(l > 0.0, acc_scr[...] / l_safe, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def _forward(q, k, v, cols, counts, block, causal, scale, interpret):
    B, H, S, D = q.shape
    nq = S // block
    C = cols.shape[-1]

    def kv_index(b, h, iq, j, cols_ref, counts_ref):
        return (b, h, cols_ref[h, iq, j], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nq, C),
        in_specs=[
            pl.BlockSpec((1, 1, block, D),
                         lambda b, h, iq, j, c, n: (b, h, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block, D), kv_index, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block, D), kv_index, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, block, D),
                               lambda b, h, iq, j, c, n: (b, h, iq, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, block=block, n_steps=C, causal=causal,
                               scale=scale)
    with jax.named_scope("block_sparse_attention"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            name="block_sparse_attention",
            interpret=interpret,
        )(cols, counts, q, k, v)


def sparse_mha(q, k, v, layout, block, causal=False, softmax_scale=None,
               interpret=False):
    """Block-sparse attention with O(enabled-blocks) fetch+compute.

    q/k/v: [B, H, S, D]; layout: [H, S/block, S/block]. Gradients flow via
    the blockwise-scan XLA twin (same function, recomputed)."""
    B, H, S, D = q.shape
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    cols, counts = compact_layout(layout, causal, block)
    cols = jnp.asarray(cols)
    counts = jnp.asarray(counts)
    layout_arr = np.asarray(layout)

    @jax.custom_vjp
    def run(q, k, v):
        return _forward(q, k, v, cols, counts, block, causal, scale, interpret)

    def run_fwd(q, k, v):
        return run(q, k, v), (q, k, v)

    def run_bwd(res, g):
        q, k, v = res
        from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import (
            blockwise_sparse_attention)
        _, vjp = jax.vjp(
            lambda q_, k_, v_: blockwise_sparse_attention(
                q_, k_, v_, layout_arr, block, causal=causal,
                softmax_scale=scale), q, k, v)
        return vjp(g)

    run.defvjp(run_fwd, run_bwd)
    # SPMD: batch-only sharding over the active mesh's data axes. Heads stay
    # replicated — the compacted layout (cols/counts) is a closed-over
    # host-side constant indexed by GLOBAL head, so slicing it per TP shard
    # would need a head-offset plumbed into the kernel; batch sharding is
    # exact and covers the data-parallel axes that dominate the mesh.
    # No free block knobs (``block`` is fixed by the caller's sparsity
    # layout) but the dispatch still routes through the tuning table so
    # coverage/telemetry treat all five kernels uniformly.
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.registry import sharded_kernel_call
    block_config = registry.resolve_block_config(
        "sparse_mha", {"s": S, "block": block, "dh": D}, q.dtype)
    return sharded_kernel_call(
        run, [q, k, v], [("data", None, None, None)] * 3,
        ("data", None, None, None), name="sparse_mha",
        block_config=block_config)


def is_supported(q_shape, block):
    B, H, S, D = q_shape
    return S % block == 0 and block % 8 == 0 and D <= 256
