"""Fused quantize / dequantize-reduce Pallas kernels — the ZeRO++ wire ops.

Capability analog of the reference's ``csrc/quantization/{swizzled_quantize,
quant_reduce}.cu``: the hot halves of qwZ/qgZ (``runtime/comm/
coalesced_collectives.py``). The pure-jnp ``ops/quantizer`` path leaves XLA a
chain of pad/reshape/reduce/select ops per leaf; these kernels produce the
int8/int4 wire payload (and consume it, fused with the cross-peer sum) in one
VMEM pass per group block.

Layout: callers hand rows of payload (one row per peer / per gathered shard);
each row is split into ``group_size`` groups with one fp32 scale per group.
Wire formats (shared by the kernels and the jnp twins in this module — the
only consumers are ``block_dequantize``/``block_dequantize_reduce``):

- 8-bit: int8, one byte per element.
- 4-bit: uint8, two elements per byte, **half-split** packed per group —
  byte ``j`` of a group carries element ``j`` (low nibble) and element
  ``j + group_size//2`` (high nibble). Half-split keeps the pack/unpack
  slices contiguous and 128-lane aligned inside the kernel; the even/odd
  interleave of ``ops/quantizer.quantize`` would need a strided lane
  gather Mosaic cannot vectorize.

Dispatch follows the other five kernels: env (``DS_TPU_QUANT_BG``) > tuning
table > ladder, through ``registry.resolve_block_config``; invocation goes
through ``registry.sharded_kernel_call`` (``local=True`` callers — inside a
qgZ/qwZ ``shard_map`` body — pin every role to None so no nested shard_map is
attempted, and the dispatch is still counted). Shapes the kernel cannot tile
(tiny leaves, odd groups) fall back to the jnp twins, recorded with a
``fallback`` reason code.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_GROUP = 2048

BG = 64  # ladder default: group-rows per block; the tuning table overrides

#: fp32 scale output is lane-padded to the TPU lane width and sliced to one
#: column outside the kernel (a [rows, 1] store would still occupy a full
#: lane tile — this just makes the padding explicit).
_SCALE_LANES = 128


def _env_bg(rows):
    """DS_TPU_QUANT_BG override (0/unset = off); must tile ``rows``."""
    import os
    raw = os.environ.get("DS_TPU_QUANT_BG", "")
    if not raw:
        return None
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(f"DS_TPU_QUANT_BG={raw!r} is not an integer")
    if v == 0:
        return None
    if v < 0:
        raise ValueError(f"DS_TPU_QUANT_BG={v} must be positive")
    if rows > v and rows % v != 0:
        raise ValueError(f"DS_TPU_QUANT_BG={v} does not tile {rows} "
                         f"group-rows")
    return v


def _blocks_fit(bg, rows, group_size):
    """Whether a block_g choice tiles ``rows`` group-rows of ``group_size``."""
    return (bg >= 8 and bg % 8 == 0
            and group_size % 256 == 0 and group_size >= 256
            and rows % 8 == 0 and (rows <= bg or rows % bg == 0))


def is_supported(rows, group_size, num_bits):
    """Group-row counts the kernels tile cleanly; callers fall back to the
    jnp twins otherwise (``rows`` = total groups = payload / group_size)."""
    return num_bits in (8, 4) and _blocks_fit(BG, rows, group_size)


def _resolve_blocks(kernel, dims, dtype):
    """env > tuning table > ladder (module BG default)."""
    from deepspeed_tpu.autotuning.kernel_table import BlockConfig
    from deepspeed_tpu.ops import registry

    forced = _env_bg(dims["rows"])
    if forced is not None:
        cfg = BlockConfig.make(kernel, source="env", block_g=forced)
        return registry.note_block_config(kernel, cfg)

    def validate(blocks, exact):
        return _blocks_fit(blocks["block_g"], exact["rows"], exact["g"])

    def ladder():
        return {"block_g": BG}

    return registry.resolve_block_config(kernel, dims, dtype,
                                         validate=validate, ladder=ladder)


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def _quant_kernel(x_ref, q_ref, s_ref, *, bits):
    x = x_ref[...].astype(jnp.float32)                 # [bg, gs]
    qmax = jnp.float32(127.0 if bits == 8 else 7.0)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)  # [bg, 1]
    scale = jnp.where(amax > 0, amax / qmax, jnp.float32(1.0))
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32)
    if bits == 4:
        h = x.shape[1] // 2
        # half-split pack: contiguous 128-aligned lane slices (see module doc)
        q_ref[...] = ((q[:, :h] & 0xF) | ((q[:, h:] & 0xF) << 4)) \
            .astype(jnp.uint8)
    else:
        q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape)


def _unpack(q, bits):
    """Wire block [bg, gsw] -> values [bg, gs] (int32), in-kernel or jnp."""
    if bits == 8:
        return q.astype(jnp.int32)
    qi = q.astype(jnp.int32)
    lo = qi & 0xF
    hi = (qi >> 4) & 0xF
    lo = jnp.where(lo > 7, lo - 16, lo)    # sign-extend 4-bit two's complement
    hi = jnp.where(hi > 7, hi - 16, hi)
    return jnp.concatenate([lo, hi], axis=-1)


def _deq_reduce_kernel(q_ref, s_ref, o_ref, acc, *, bits, npeers):
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    vals = _unpack(q_ref[0], bits).astype(jnp.float32)   # [bg, gs]
    scale = s_ref[0][:, :1]                              # [bg, 1]
    acc[...] += vals * scale

    @pl.when(p == npeers - 1)
    def _done():
        o_ref[...] = acc[...].astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# jnp twins — same wire format, pure-XLA (the off-TPU / odd-shape path)
# ---------------------------------------------------------------------------

def _quantize_rows_ref(rows, num_bits):
    """rows [N, group_size] f32 (one group per row) -> (q_rows, scale [N])."""
    qmax = jnp.float32(127.0 if num_bits == 8 else 7.0)
    amax = jnp.max(jnp.abs(rows), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, jnp.float32(1.0))
    q = jnp.clip(jnp.round(rows / scale), -qmax, qmax).astype(jnp.int32)
    if num_bits == 4:
        h = rows.shape[1] // 2
        q = ((q[:, :h] & 0xF) | ((q[:, h:] & 0xF) << 4)).astype(jnp.uint8)
    else:
        q = q.astype(jnp.int8)
    return q, scale[:, 0]


def _dequantize_rows_ref(q_rows, scale, num_bits):
    """q_rows [N, gsw] + scale [N] -> [N, group_size] f32."""
    vals = _unpack(q_rows, num_bits)
    return vals.astype(jnp.float32) * scale[:, None]


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _prep_rows(x, group_size):
    """[R, M] -> padded group-rows [R*G, group_size] (+ layout ints)."""
    R, M = x.shape
    G = max(1, -(-M // group_size))
    Mp = G * group_size
    xf = x.astype(jnp.float32)
    if Mp != M:
        xf = jnp.pad(xf, ((0, 0), (0, Mp - M)))
    return xf.reshape(R * G, group_size), R, G, Mp


def _interp(interpret):
    from deepspeed_tpu.ops import registry
    return registry.pallas_interpret() if interpret is None else interpret


def block_quantize(x, num_bits=8, group_size=DEFAULT_GROUP, interpret=None,
                   block_config=None, local=False):
    """Groupwise symmetric quantization of payload rows — the wire producer.

    ``x`` [R, M] (or 1D [M], treated as one row): each row is split into
    ``G = ceil(M / group_size)`` groups (zero-padded). Returns ``(q, scale)``
    where ``q`` is [R, G*group_size] int8 (8-bit) or [R, G*group_size//2]
    half-split-packed uint8 (4-bit) and ``scale`` is [R, G] fp32. 1D input
    gives 1D outputs.

    ``local=True`` marks a call from inside a ``shard_map`` body (qgZ/qwZ):
    every sharding role is pinned to None so ``sharded_kernel_call`` degrades
    to a direct call instead of tracing a nested shard_map.
    """
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.autotuning.kernel_table import BlockConfig
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.registry import sharded_kernel_call

    if num_bits == 4 and group_size % 2:
        raise ValueError(f"4-bit packing needs an even group_size, "
                         f"got {group_size}")
    squeeze = (x.ndim == 1)
    if squeeze:
        x = x[None]
    rows, R, G, Mp = _prep_rows(x, group_size)
    n = rows.shape[0]

    interpret = _interp(interpret)
    if (interpret or registry.pallas_enabled()) \
            and is_supported(n, group_size, num_bits):
        if block_config is not None:
            if not isinstance(block_config, BlockConfig):
                block_config = BlockConfig.make("block_quantize",
                                                source="sweep",
                                                **dict(block_config))
            bg = block_config.get("block_g")
            if not _blocks_fit(bg, n, group_size):
                raise ValueError(f"block_quantize: pinned block_g={bg} does "
                                 f"not tile rows={n}, group={group_size}")
            registry.note_block_config("block_quantize", block_config,
                                       reason=block_config.source)
        else:
            block_config = _resolve_blocks(
                "block_quantize",
                {"rows": n, "g": group_size, "bits": num_bits}, rows.dtype)
        bg = block_config.get("block_g")

        def call(r):
            return _quantize_rows_local(r, num_bits, bg, interpret)

        def accept(shard_shapes):
            (ns, _), = shard_shapes
            return _blocks_fit(bg, ns, group_size)

        role = None if local else "data"
        q_rows, s_pad = sharded_kernel_call(
            call, [rows], [(role, None)], [(role, None), (role, None)],
            accept=accept, name="block_quantize", block_config=block_config)
        scale = s_pad[:, 0]
    else:
        telemetry.record_dispatch("block_quantize", "fallback",
                                  "no_tpu" if not (interpret or
                                                   registry.pallas_enabled())
                                  else "unsupported_shape")
        q_rows, scale = _quantize_rows_ref(rows, num_bits)

    q = q_rows.reshape(R, -1)
    scale = scale.reshape(R, G)
    if squeeze:
        return q[0], scale[0]
    return q, scale


def _quantize_rows_local(rows, num_bits, bg, interpret):
    n, gs = rows.shape
    bg = min(bg, n)
    gsw = gs if num_bits == 8 else gs // 2
    qdt = jnp.int8 if num_bits == 8 else jnp.uint8
    with jax.named_scope("block_quantize"):
        return pl.pallas_call(
            functools.partial(_quant_kernel, bits=num_bits),
            grid=(n // bg,),
            in_specs=[pl.BlockSpec((bg, gs), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((bg, gsw), lambda i: (i, 0)),
                       pl.BlockSpec((bg, _SCALE_LANES), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((n, gsw), qdt),
                       jax.ShapeDtypeStruct((n, _SCALE_LANES), jnp.float32)],
            name="block_quantize",
            interpret=interpret,
        )(rows)


def _dequantize_reduce_impl(q3, s2, num_bits, group_size, interpret,
                            block_config, local, name):
    """q3 [P, N, gsw] + s2 [P, N] -> [N, group_size] f32, summed over P."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.autotuning.kernel_table import BlockConfig
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.registry import sharded_kernel_call

    P_, N, gsw = q3.shape
    interpret = _interp(interpret)
    if (interpret or registry.pallas_enabled()) \
            and is_supported(N, group_size, num_bits):
        if block_config is not None:
            if not isinstance(block_config, BlockConfig):
                block_config = BlockConfig.make("block_dequantize_reduce",
                                                source="sweep",
                                                **dict(block_config))
            bg = block_config.get("block_g")
            if not _blocks_fit(bg, N, group_size):
                raise ValueError(f"{name}: pinned block_g={bg} does not tile "
                                 f"rows={N}, group={group_size}")
            registry.note_block_config("block_dequantize_reduce", block_config,
                                       reason=block_config.source)
        else:
            block_config = _resolve_blocks(
                "block_dequantize_reduce",
                {"peers": P_, "rows": N, "g": group_size, "bits": num_bits},
                q3.dtype)
        bg = block_config.get("block_g")
        # scales ride into VMEM lane-broadcast (tiny: N * 512 bytes per peer)
        sb = jnp.broadcast_to(s2[:, :, None].astype(jnp.float32),
                              (P_, N, _SCALE_LANES))

        def call(qv, sv):
            return _deq_reduce_local(qv, sv, num_bits, bg, interpret)

        def accept(shard_shapes):
            (_, ns, _), _ = shard_shapes
            return _blocks_fit(bg, ns, group_size)

        role = None if local else "data"
        return sharded_kernel_call(
            call, [q3, sb], [(None, role, None), (None, role, None)],
            (role, None), accept=accept, name=name,
            block_config=block_config)

    telemetry.record_dispatch(name, "fallback",
                              "no_tpu" if not (interpret or
                                               registry.pallas_enabled())
                              else "unsupported_shape")
    deq = _dequantize_rows_ref(q3.reshape(P_ * N, gsw),
                               s2.reshape(P_ * N), num_bits)
    return deq.reshape(P_, N, group_size).sum(axis=0)


def _deq_reduce_local(q3, sb, num_bits, bg, interpret):
    P_, N, gsw = q3.shape
    gs = gsw if num_bits == 8 else gsw * 2
    bg = min(bg, N)
    with jax.named_scope("block_dequantize_reduce"):
        return pl.pallas_call(
            functools.partial(_deq_reduce_kernel, bits=num_bits, npeers=P_),
            grid=(N // bg, P_),   # peers innermost: VMEM-resident accumulation
            in_specs=[pl.BlockSpec((1, bg, gsw), lambda i, p: (p, i, 0)),
                      pl.BlockSpec((1, bg, _SCALE_LANES), lambda i, p: (p, i, 0))],
            out_specs=pl.BlockSpec((bg, gs), lambda i, p: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((N, gs), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bg, gs), jnp.float32)],
            name="block_dequantize_reduce",
            interpret=interpret,
        )(q3, sb)


def block_dequantize_reduce(q, scale, num_bits=8, group_size=DEFAULT_GROUP,
                            out_len=None, dtype=jnp.float32, interpret=None,
                            block_config=None, local=False):
    """Fused dequantize + cross-peer sum — the exchange-reduce consumer.

    ``q`` [P, wire] and ``scale`` [P, G] as produced by :func:`block_quantize`
    (one row per peer, exchanged over the collective); returns the [out_len]
    f32 sum over the P peers (``out_len`` defaults to the full padded
    G*group_size). The peer dimension is the reduction and never sharded.
    """
    P_, G = scale.shape
    gsw = q.shape[1] // G
    out = _dequantize_reduce_impl(q.reshape(P_, G, gsw), scale, num_bits,
                                  group_size, interpret, block_config, local,
                                  name="block_dequantize_reduce")
    flat = out.reshape(G * group_size)
    if out_len is not None:
        flat = flat[:out_len]
    return flat.astype(dtype)


def block_dequantize(q, scale, num_bits=8, group_size=DEFAULT_GROUP,
                     out_len=None, dtype=jnp.float32, interpret=None,
                     block_config=None, local=False):
    """Row-wise dequantization (no reduction) — the all-gather consumer.

    ``q`` [R, wire] + ``scale`` [R, G] -> [R, out_len]. Runs the reduce
    kernel with a single peer, so shard rows dequantize straight into their
    output slots without a [world, *shape] fp32 staging buffer.
    """
    R, G = scale.shape
    gsw = q.shape[1] // G
    out = _dequantize_reduce_impl(q.reshape(1, R * G, gsw),
                                  scale.reshape(1, R * G), num_bits,
                                  group_size, interpret, block_config, local,
                                  name="block_dequantize_reduce")
    out = out.reshape(R, G * group_size)
    if out_len is not None:
        out = out[:, :out_len]
    return out.astype(dtype)


def wire_nbytes(numel, num_bits, group_size=DEFAULT_GROUP):
    """True wire footprint of ``numel`` payload elements: packed ints plus
    fp32 group scales (telemetry's ``wire_bytes``; logical bytes stay the
    fp32 ``numel * 4``)."""
    groups = max(1, -(-numel // group_size))
    payload = groups * group_size if num_bits == 8 \
        else groups * (group_size // 2)
    return payload + groups * 4
