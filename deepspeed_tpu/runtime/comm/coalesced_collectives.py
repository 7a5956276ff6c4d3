"""Coalesced + quantized collectives — ZeRO++ comm kernels.

Reference ``runtime/comm/coalesced_collectives.py``:
- ``reduce_scatter_coalesced`` (:31): one fused reduce-scatter over many
  tensors.
- ``all_to_all_quant_reduce`` (:81, qgZ): gradients are int4-quantized,
  exchanged all-to-all *within* the node, reduced locally, int8-quantized and
  exchanged across nodes, reduced again — 4x less cross-node traffic.

TPU mapping: these run inside ``shard_map`` over mesh axes. The hierarchy is
``dp`` (intra-slice ICI, the reference's intra-node NVLink) and ``dpr``
(cross-slice DCN, the reference's inter-node IB) — see
``parallel/topology.py``. qwZ (``zero_quantized_weights``) is
``quantized_all_gather``: the wire format is int8 + per-group scales.

The quantize / dequantize halves are the ``ops/pallas/quant_collective``
kernel pair (``block_quantize`` / ``block_dequantize_reduce``, jnp fallback
off-TPU): the dequant+sum of the exchange is fused into one VMEM pass, and
nothing wider than the wire payload is ever materialized per peer. Every
exchange records trace-time comm telemetry with both the logical fp32 bytes
(comparable with the unquantized path) and the true ``wire_bytes``
(packed ints + fp32 group scales) per mesh axis.
"""

import jax.numpy as jnp
from jax import lax


from deepspeed_tpu.ops.pallas.quant_collective import (
    block_dequantize,
    block_dequantize_reduce,
    block_quantize,
    wire_nbytes,
)


def _record_wire(op, axis, logical_numel, wire):
    """Trace-time comm record: logical fp32 bytes + true wire bytes."""
    from deepspeed_tpu import telemetry
    if telemetry.enabled():
        telemetry.record_comm(op, int(logical_numel) * 4, 0.0, axis=axis,
                              traced=True, wire_bytes=int(wire))


def reduce_scatter_coalesced(tensors, axis_name="dp"):
    """Fused reduce-scatter of a list of tensors over ``axis_name``
    (reference :31). Each tensor is flattened; every rank gets back its
    1/world shard of each (padded to divide evenly)."""
    world = lax.axis_size(axis_name)
    out = []
    for t in tensors:
        flat = t.reshape(-1)
        pad = (-flat.shape[0]) % world
        if pad:
            flat = jnp.pad(flat, (0, pad))
        out.append(lax.psum_scatter(flat.reshape(world, -1), axis_name,
                                    scatter_dimension=0, tiled=False))
    return out


def quantized_all_gather(x, axis_name="dp", num_bits=8, group_size=2048,
                         dtype=jnp.float32):
    """qwZ: all-gather with an int8 wire format (reference qwZ quantized
    all-gather: ``partition_parameters.py:728`` CUDAQuantizer +
    ``csrc/quantization/swizzled_quantize.cu``). Gathers ``x`` (this rank's
    shard) from every rank along ``axis_name``; only int8 values + fp32
    group scales cross the wire, and each gathered shard row dequantizes
    straight into its output slot — no fp32 ``[world, *shape]`` staging
    pass."""
    world = lax.axis_size(axis_name)
    flat = x.reshape(-1)
    q, scale = block_quantize(flat, num_bits=num_bits, group_size=group_size,
                              local=True)
    _record_wire("all_gather_quant", axis_name, flat.shape[0],
                 wire_nbytes(flat.shape[0], num_bits, group_size))
    qg = lax.all_gather(q, axis_name)        # [world, wire]
    sg = lax.all_gather(scale, axis_name)    # [world, groups]
    full = block_dequantize(qg, sg, num_bits=num_bits, group_size=group_size,
                            out_len=flat.shape[0], dtype=dtype, local=True)
    return full.reshape((world * x.shape[0],) + x.shape[1:])


def exchange_reduce(blocks, axis, bits, group_size=2048, return_error=False):
    """Quantized all-to-all + fused dequant-reduce: the qgZ exchange
    primitive.

    ``blocks``: [peers, m] — row j is this rank's payload destined for peer j.
    Each row is groupwise-quantized to ``bits``, exchanged over ``axis``
    (row j -> peer j), and dequant-summed in one kernel pass: returns this
    rank's [m] partial sum over the ``axis`` group.

    ``return_error=True`` additionally returns the local quantization
    residual ``blocks - dequantize(quantize(blocks))`` ([peers, m], computed
    from this rank's own outgoing wire payload, no extra comm) — the
    error-feedback carry for the next step."""
    P, m = blocks.shape
    q, s = block_quantize(blocks, num_bits=bits, group_size=group_size,
                          local=True)
    _record_wire("all_to_all_quant", axis, blocks.size,
                 P * wire_nbytes(m, bits, group_size))
    qx = lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=False)
    sx = lax.all_to_all(s, axis, split_axis=0, concat_axis=0, tiled=False)
    out = block_dequantize_reduce(qx, sx, num_bits=bits,
                                  group_size=group_size, out_len=m,
                                  local=True)
    if return_error:
        err = blocks - block_dequantize(q, s, num_bits=bits,
                                        group_size=group_size, out_len=m,
                                        local=True)
        return out, err
    return out


def expert_all_to_all(x, axis, bits=None, group_size=2048,
                      op="a2a_dispatch"):
    """MoE expert dispatch/combine all-to-all of per-peer payload blocks.

    ``x``: [peers, ...] — block j is this rank's payload for peer j along
    ``axis``; returns [peers, ...] where block j is what peer j sent here.

    ``bits`` None keeps the payload's own dtype on the wire (the ICI
    default: wire bytes == payload bytes). ``bits`` set routes each peer
    block through the qwZ/qgZ kernel pair — only packed ints + fp32 group
    scales cross the link (the DCN leg). Either way telemetry records the
    exchange under ``op`` ("a2a_dispatch" / "a2a_combine" — the overlap
    scheduler's MoE stream classes) with logical fp32 bytes and true wire
    bytes.

    The quantized leg is forward-only (round-to-nearest has no useful VJP);
    training paths keep ``bits=None`` unless they carry their own error
    feedback like ``exchange_reduce`` callers do."""
    P = x.shape[0]
    if bits is None:
        _record_wire(op, axis, x.size, x.size * x.dtype.itemsize)
        return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                              tiled=False)
    blocks = x.reshape(P, -1).astype(jnp.float32)
    m = blocks.shape[1]
    q, s = block_quantize(blocks, num_bits=bits, group_size=group_size,
                          local=True)
    _record_wire(op, axis, x.size, P * wire_nbytes(m, bits, group_size))
    qx = lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=False)
    sx = lax.all_to_all(s, axis, split_axis=0, concat_axis=0, tiled=False)
    out = block_dequantize(qx, sx, num_bits=bits, group_size=group_size,
                           out_len=m, local=True)
    return out.reshape(x.shape).astype(x.dtype)


def moe_hierarchical_a2a(x, intra_axis="ep", inter_axis="dpr", inter_bits=8,
                         group_size=2048, op="a2a_dispatch"):
    """hpZ-split expert all-to-all over a two-level expert world.

    ``x``: [inter, intra, ...] — block (a, b) is this rank's payload for the
    peer at inter index ``a`` (DCN) and intra index ``b`` (ICI). Returns
    [inter, intra, ...] where block (a, b) holds what THAT peer sent here.

    Stage 1 exchanges full precision over ``intra_axis`` (ICI — bytes are
    nearly free); stage 2 exchanges ``inter_bits`` over ``inter_axis`` (DCN
    — the leg ``perf_gate check_moe_wire`` caps at ≤ 0.5x fp32). Same
    hierarchy split as qgZ/hpZ in :func:`all_to_all_quant_reduce`, but
    payload-preserving (no reduce) — expert tokens must arrive intact."""
    # stage 1 (ICI, fp): lead with the intra destination. Result is
    # [intra_src, inter_dest, ...]: each intra peer now holds the slab its
    # group routed to this intra index, still grouped by inter destination.
    y = expert_all_to_all(jnp.swapaxes(x, 0, 1), intra_axis, bits=None,
                          group_size=group_size, op=op)
    # stage 2 (DCN, quantized): lead with the inter destination. Result is
    # [inter_src, intra_src, ...] — payload from every (a, b) peer.
    return expert_all_to_all(jnp.swapaxes(y, 0, 1), inter_axis,
                             bits=inter_bits, group_size=group_size, op=op)


def all_to_all_quant_reduce(x, intra_axis="dp", inter_axis=None,
                            intra_bits=4, inter_bits=8, group_size=2048,
                            dtype=jnp.float32):
    """qgZ: hierarchical quantized gradient reduction (reference :81).

    ``x`` is this rank's full-size gradient; the result is this rank's
    1/world flat shard of the *sum* over all ranks (world = intra × inter).
    Stage 1 int4-quantizes per destination block and all-to-alls within
    ``intra_axis`` (ICI), then dequant-reduces; stage 2 (when ``inter_axis``
    is given) repeats with int8 across ``inter_axis`` (DCN). Cross-DCN bytes
    are inter_bits/32 of an fp32 reduce-scatter."""

    intra = lax.axis_size(intra_axis)
    inter = lax.axis_size(inter_axis) if inter_axis else 1
    world = intra * inter
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % world
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shard = flat.shape[0] // world

    # stage 1 (ICI): each intra-peer block carries all its inter-shards
    partial = exchange_reduce(flat.reshape(intra, inter * shard),
                              intra_axis, intra_bits, group_size)
    if inter == 1:
        return partial.astype(dtype)
    # stage 2 (DCN): exchange the partial sums' inter-blocks
    return exchange_reduce(partial.reshape(inter, shard),
                           inter_axis, inter_bits, group_size).astype(dtype)
