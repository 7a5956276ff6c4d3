"""Error-feedback sign-compressed allreduce.

The capability analog of the reference's compressed-communication backends
(``deepspeed/runtime/comm/nccl.py:51`` ``NcclBackend.compressed_allreduce``,
``runtime/comm/mpi.py``, ``runtime/comm/hccl.py``): a two-phase allreduce that
transmits one sign bit per element plus one fp32 scale per tensor, with
worker- and server-side error feedback so compression noise averages out over
steps (the 1-bit Adam family relies on this).

TPU-native shape: the reference packs sign bits with cupy and issues NCCL
alltoall/allgather by hand; here the same algorithm is a pure function over
``jax.lax`` collectives, meant to run inside ``shard_map`` over a mesh axis —
typically the DCN-crossing axis, where 32x wire compression actually matters
(ICI-local reductions are better served by plain ``psum``).

Wire format: signs bit-packed to uint8 (``jnp.packbits``) + a single fp32
scale, so the all_to_all/all_gather really move 1 bit per element.
"""

import jax
import jax.numpy as jnp
from jax import lax



def sign_compress(x, error, mask=None):
    """Error-feedback sign compression core, shared by the wire-level
    collective below and the 1-bit optimizer family (``ops/onebit.py``).

    Returns ``(decompressed, new_error, scale, bits)`` where ``decompressed =
    scale * sign(x + error)`` and ``new_error`` is the residual actually left
    unapplied. The scale preserves the l2 norm (reference nccl.py:
    ``norm/sqrt(numel)``); zeros compress to +1 like
    torch.sign-with-bit-packing does.

    ``mask`` zeroes coordinates that must not receive compressed magnitude
    (e.g. coordinates whose frozen Adam variance is exactly 0 — dead ReLU
    units — where ``1/(sqrt(0)+eps)`` would blow the update up); the residual
    stays consistent with what was actually applied.
    """
    corrected = x + error
    scale = jnp.linalg.norm(corrected.reshape(-1)) / jnp.sqrt(jnp.float32(corrected.size))
    bits = (corrected >= 0)
    decompressed = scale * jnp.where(bits, 1.0, -1.0).astype(x.dtype)
    if mask is not None:
        decompressed = jnp.where(mask, decompressed, 0.0)
    return decompressed, corrected - decompressed, scale, bits


def _compress(flat, error):
    """Wire form: sign-compress → (packed_bits, scale, new_error)."""
    decompressed, new_error, scale, bits = sign_compress(flat, error)
    return jnp.packbits(bits), scale, new_error


def _decompress(packed, scale, n, dtype):
    bits = jnp.unpackbits(packed)[:n]
    return scale * jnp.where(bits, 1.0, -1.0).astype(dtype)


def compressed_allreduce(tensor, worker_error, server_error, axis_name="dp"):
    """Average ``tensor`` over ``axis_name`` using 1-bit compression.

    Must be called inside ``shard_map``/``pmap`` with ``axis_name`` bound.
    ``worker_error`` has ``tensor.size`` elements (padded size — see
    ``error_shapes``); ``server_error`` has ``tensor.size // world`` elements.
    Both are device-local state the caller threads between steps (the reference
    stores them on the optimizer, e.g. ``fp16/onebit/adam.py``).

    Returns ``(averaged, new_worker_error, new_server_error)``.
    """
    world = lax.axis_size(axis_name)
    flat = tensor.reshape(-1).astype(jnp.float32)
    n = flat.size
    # pad so each of the `world` chunks is a whole number of packed bytes
    chunk = -(-n // world)
    chunk = -(-chunk // 8) * 8
    padded = chunk * world
    flat = jnp.pad(flat, (0, padded - n))
    assert worker_error.size == padded and server_error.size == chunk, (
        f"error buffers must be sized by error_shapes(): need ({padded},)/({chunk},), "
        f"got ({worker_error.size},)/({server_error.size},)")

    # phase 1 — worker compression + all_to_all of packed chunks
    packed, scale, new_worker_error = _compress(flat, worker_error.reshape(-1))
    # (world, chunk/8) uint8 — each rank receives its chunk from every rank
    recv = lax.all_to_all(packed.reshape(world, chunk // 8), axis_name,
                          split_axis=0, concat_axis=0, tiled=False)
    scales = lax.all_gather(scale, axis_name)  # (world,)

    # server-side average of this rank's chunk over all workers
    bits = jnp.unpackbits(recv, axis=1)  # (world, chunk)
    signs = jnp.where(bits, 1.0, -1.0).astype(jnp.float32)
    server_chunk = (signs * scales[:, None]).mean(axis=0)

    # phase 2 — server compression + all_gather of packed server chunks
    packed_s, scale_s, new_server_error = _compress(server_chunk, server_error.reshape(-1))
    gathered = lax.all_gather(packed_s, axis_name, axis=0, tiled=True)
    scales_s = lax.all_gather(scale_s, axis_name)  # (world,)
    bits_g = jnp.unpackbits(gathered).reshape(world, chunk)
    out = (jnp.where(bits_g, 1.0, -1.0) * scales_s[:, None]).reshape(-1)[:n]
    return out.reshape(tensor.shape).astype(tensor.dtype), new_worker_error, new_server_error


def error_shapes(n, world):
    """Shapes of (worker_error, server_error) buffers for an n-element tensor:
    per-rank chunk rounded up to whole packed bytes."""
    chunk = (-(-n // world) + 7) // 8 * 8
    return (chunk * world,), (chunk,)


def init_error_buffers(n, world, dtype=jnp.float32):
    w, s = error_shapes(n, world)
    return jnp.zeros(w, dtype), jnp.zeros(s, dtype)
