"""Pallas paged (blocked-flash) attention for the ragged inference engine.

Capability analog of the reference's blocked_flash kernel family
(``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash/``), designed for
the TPU pipeline model rather than translated:

- grid ``(seqs, kv_heads, max_blocks)`` with the KV-block dimension innermost;
- the block table and ``seen`` lengths are **scalar-prefetched**
  (``PrefetchScalarGridSpec``) so the K/V BlockSpec index maps read the block
  table directly — the pipeline DMAs exactly the pool blocks the sequence
  owns;
- blocks past the sequence's live length clamp to the last valid index: Pallas
  skips re-fetching a block whose index equals the previous grid step's, so
  HBM traffic is O(seen), not O(max_context) — the VERDICT's gather-all fix;
- online-softmax state (m, l, acc) for the whole q-head group lives in VMEM
  scratch across the block iterations (decode flash attention).

Layouts: q [S, Q, H, Dh] (Q = new-token budget, 1 for pure decode);
k/v pools [NB, KV, bs, Dh] — (bs, Dh) are the minor dims so each grid step's
block is a legal Mosaic tile; block_tables [S, MB]; seen [S]. Output matches q.
GQA runs natively: grid is over KV heads, each step attends the whole
``rep = H // KV`` query-head group against one KV block.

int8 KV (``k_scale``/``v_scale`` given): pools are int8 with per-token fp32
scales in side pools [NB, KV, 1, bs] — the scale tile is a [1, bs] lane row
DMA'd through the SAME block-table index map as its page, so HBM reads stay
int8-sized and the dequant fuses into the flash loop in VMEM. No transposes:
``k``'s per-token scale folds into the score *columns* after the QK dot
(``sij * ks``), ``v``'s folds into ``p``'s columns before the PV dot
(``(p * vs) @ v``) — both are lane-broadcast multiplies.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9

LANES = 128


def _kernel(bt_ref, seen_ref, qlen_ref, jcap_ref, *refs, bs, nb_grid, rep,
            q_tokens, scale, window, quantized):
    if quantized:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        ks_ref = vs_ref = None
    s, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    seen_s = seen_ref[s]
    qlen_s = qlen_ref[s]
    total = seen_s + qlen_s                       # live keys incl. this step's
    # block j holds key positions [j*bs, (j+1)*bs); run while any are live
    should_run = j * bs < total

    @pl.when(should_run)
    def _body():
        # q rows: the rep query heads of this kv head, all q tokens: [rep*Q, Dh]
        q = q_ref[0, 0]                           # [rep*Q, Dh]
        k = k_ref[0, 0]                           # [bs, Dh]
        v = v_ref[0, 0]
        if quantized:
            # int8 page tiles dequantize HERE, in VMEM — fp KV never exists
            # in HBM. The QK dot runs on the raw int8 values (widened to the
            # q dtype; +-127 is exact in bf16) and each key's scale folds
            # into its score column afterwards.
            k = k.astype(q.dtype)
        sij = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32) * scale
        if quantized:
            sij = sij * ks_ref[0, 0]              # [rep*Q, bs] * [1, bs]
        # causal over the ragged sequence: key pos <= seen + qi
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, sij.shape, 1)
        qi = jax.lax.broadcasted_iota(jnp.int32, sij.shape, 0) % q_tokens
        visible = kpos <= seen_s + qi
        if window is not None:  # Mistral-style sliding window
            visible = jnp.logical_and(visible, kpos > seen_s + qi - window)
        sij = jnp.where(visible, sij, NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(sij, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(sij - m_cur)
        l_cur = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)
        if quantized:
            # per-token v scale folds into p's columns before the PV dot:
            # (p * vs) @ v_int == p @ (v_int * vs^T) without the transpose
            pv = jax.lax.dot_general((p * vs_ref[0, 0]).astype(jnp.float32),
                                     v.astype(jnp.float32),
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        else:
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

    @pl.when(j == nb_grid - 1)
    def _finish():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def paged_mha(q, k_pool, v_pool, block_tables, seen, q_len, *,
              k_scale=None, v_scale=None, softmax_scale=None, window=None,
              interpret=False):
    """Blocked-flash attention over paged KV. See module docstring for shapes.

    SPMD: routed through the kernel dispatcher — sequences (the ``S`` batch
    dim of q/block_tables/seen/q_len) shard over the active mesh's data axes;
    KV heads (and with them the grouped query heads) shard over the TP axis,
    which slices the pools' ``KV`` dim while the block pool itself (``NB``)
    stays replicated so global block-table indices remain valid per shard.
    The int8 scale pools shard exactly like their pages (KV dim on the TP
    axis, NB replicated).

    No free block knobs (the KV block size comes from the pool layout), but
    the dispatch still routes through the tuning table so coverage and the
    tuned|ladder_fallback telemetry treat all five kernels uniformly.
    """
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.registry import sharded_kernel_call

    quantized = k_scale is not None
    block_config = registry.resolve_block_config(
        "paged_mha", {"bs": k_pool.shape[2], "dh": q.shape[-1]}, q.dtype)

    def call(q_, kp_, vp_, bt_, sn_, ql_, *scales):
        ks_, vs_ = scales if quantized else (None, None)
        return _paged_mha_local(q_, kp_, vp_, bt_, sn_, ql_,
                                k_scale=ks_, v_scale=vs_,
                                softmax_scale=softmax_scale, window=window,
                                interpret=interpret)

    def accept(shard_shapes):
        (_, _, h, _), (_, kv, _, _) = shard_shapes[0], shard_shapes[1]
        return kv >= 1 and h % kv == 0

    inputs = [q, k_pool, v_pool, block_tables, seen, q_len]
    roles = [("data", None, "head", None), (None, "head", None, None),
             (None, "head", None, None), ("data", None), ("data",), ("data",)]
    if quantized:
        inputs += [k_scale, v_scale]
        roles += [(None, "head", None, None), (None, "head", None, None)]
    return sharded_kernel_call(
        call, inputs, roles,
        ("data", None, "head", None), accept=accept, name="paged_mha",
        block_config=block_config)


def _paged_mha_local(q, k_pool, v_pool, block_tables, seen, q_len, *,
                     k_scale=None, v_scale=None, softmax_scale=None,
                     window=None, interpret=False):
    S, Q, H, Dh = q.shape
    NB, KV, bs, _ = k_pool.shape
    MB = block_tables.shape[1]
    rep = H // KV
    scale = softmax_scale if softmax_scale is not None else Dh ** -0.5
    quantized = k_scale is not None

    # [S, Q, H, Dh] -> [S, KV, rep*Q, Dh]: rows grouped by kv head
    qt = q.reshape(S, Q, KV, rep, Dh).transpose(0, 2, 3, 1, 4) \
         .reshape(S, KV, rep * Q, Dh)
    seen = seen.astype(jnp.int32)
    q_len = q_len.astype(jnp.int32)
    # clamp dead blocks to the last live one -> identical index -> no re-fetch
    live_blocks = jnp.maximum((seen + q_len + bs - 1) // bs, 1)   # [S]
    jcap = live_blocks - 1

    def kv_index(s, h, j, bt, seen_ref, qlen_ref, jcap_ref):
        jc = jnp.minimum(j, jcap_ref[s])
        return (bt[s, jc], h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, rep * Q, Dh),
                     lambda s, h, j, bt, sn, ql, jc: (s, h, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, bs, Dh), kv_index, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, bs, Dh), kv_index, memory_space=pltpu.VMEM),
    ]
    inputs = [qt, k_pool, v_pool]
    if quantized:
        # scale pools [NB, KV, 1, bs]: the [1, bs] tile rides the same
        # block-table index map as its page, one lane row per grid step
        in_specs += [pl.BlockSpec((1, 1, 1, bs), kv_index,
                                  memory_space=pltpu.VMEM)] * 2
        inputs += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S, KV, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rep * Q, Dh),
                               lambda s, h, j, bt, sn, ql, jc: (s, h, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((rep * Q, LANES), jnp.float32),
            pltpu.VMEM((rep * Q, LANES), jnp.float32),
            pltpu.VMEM((rep * Q, Dh), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, nb_grid=MB, rep=rep,
                               q_tokens=Q, scale=scale,
                               window=int(window) if window else None,
                               quantized=quantized)
    # qt reshaped so kv-head is a real leading dim for the spec: [S*KV, rep*Q, Dh]
    with jax.named_scope("paged_attention"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, KV, rep * Q, Dh), q.dtype),
            name="paged_attention",
            interpret=interpret,
        )(block_tables.astype(jnp.int32), seen, q_len, jcap, *inputs)
    return out.reshape(S, KV, rep, Q, Dh).transpose(0, 3, 1, 2, 4) \
              .reshape(S, Q, H, Dh)


def is_supported(q_shape, pool_shape):
    S, Q, H, Dh = q_shape
    NB, KV, bs, _ = pool_shape
    return H % KV == 0 and Dh <= 256 and bs % 8 == 0
