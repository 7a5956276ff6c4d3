"""Fused dequantize-matmul Pallas kernel (W8A16-style).

Capability analog of the reference's quantized GEMMs
(``inference/v2/kernels/core_ops/cuda_linear`` FP6 GEMM and
``cutlass_ops/mixed_gemm`` W4/W8A16): the XLA path dequantizes the whole
weight to bf16 in HBM before the matmul, doubling weight traffic; this
kernel DMAs the int8 blocks and their group scales into VMEM and
dequantizes right before the MXU dot — HBM reads stay int8-sized.

Layout matches ``inference/quantization``'s ``quantize_lastdim``: weight
q [K, N] int8 with per-(row, N-group) scales [K, N // group_size] f32.
Activations x [M, K] (bf16/f32). Grid (M/bm, N/bn, K/bk), k innermost with
an f32 VMEM accumulator.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BM, BN, BK = 256, 256, 512  # ladder defaults; the tuning table overrides


def _blocks_fit(bm, bn, bk, m, k, n, group_size):
    """Whether a (bm, bn, bk) choice tiles these exact dims cleanly. The
    scale block is [bk, bn/G]: Mosaic takes a lane dim only when it is a
    multiple of 128 or the whole array's (the chip's compiler refuses the
    rest — interpret mode does not notice)."""
    return (m % 8 == 0 and (m <= bm or m % bm == 0)
            and k % bk == 0 and n % bn == 0
            and bn % group_size == 0 and group_size <= bn
            and (bn == n or (bn // group_size) % 128 == 0))


def _ladder_blocks(n, group_size):
    """The ladder's (bm, bn, bk) for an N-wide weight: the module defaults
    where their scale block is legal, else the whole width in one block with
    bk cut so the dequantized f32 block stays within VMEM (compiled for the
    v5e at 512 x 4096 x 4096, tests/test_chip_compile.py). Widths past
    ``MAX_WHOLE_N`` have no legal ladder entry and take the XLA path."""
    bn, bk = BN, BK
    if bn != n and (bn // group_size) % 128 != 0:
        bn = n
        while bk > 128 and bk * bn > 512 * 1024:
            bk //= 2
    return BM, bn, bk


MAX_WHOLE_N = 4096


def is_supported(m, k, n, group_size, num_bits):
    """Shapes the kernel tiles cleanly; callers fall back to XLA dequant."""
    bm, bn, bk = _ladder_blocks(n, group_size)
    return (num_bits == 8 and bn <= MAX_WHOLE_N
            and _blocks_fit(bm, bn, bk, m, k, n, group_size))


def _resolve_blocks(m, k, n, group_size, dtype):
    """Tuning-table-first block resolution (ladder = module defaults)."""
    from deepspeed_tpu.ops import registry

    def validate(blocks, dims):
        return _blocks_fit(blocks["block_m"], blocks["block_n"],
                           blocks["block_k"], dims["m"], dims["k"],
                           dims["n"], dims["g"])

    def ladder():
        bm, bn, bk = _ladder_blocks(n, group_size)
        return {"block_m": bm, "block_n": bn, "block_k": bk}

    return registry.resolve_block_config(
        "quantized_matmul", {"m": m, "k": k, "n": n, "g": group_size}, dtype,
        validate=validate, ladder=ladder)


def _kernel(x_ref, q_ref, s_ref, o_ref, acc, *, nk, bn, group_size):
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]                                    # [bm, bk]
    w8 = q_ref[...].astype(jnp.float32)               # [bk, bn]
    s = s_ref[...]                                    # [bk, bn/G] (BlockSpec
    # already DMA'd this j-block: an in-kernel lane-dim dynamic slice is a
    # vector.load Mosaic cannot prove 128-aligned — it must not appear here)
    ng = bn // group_size
    # expand group scales to lanes with a one-hot matmul: [bk,ng] @ [ng,bn].
    # A [bk, ng, G] reshape+broadcast would be a 3D relayout; iota + dot
    # keeps every op 2D and MXU-shaped.
    col_group = jax.lax.broadcasted_iota(jnp.int32, (ng, bn), 1) // group_size
    row_id = jax.lax.broadcasted_iota(jnp.int32, (ng, bn), 0)
    expand = (col_group == row_id).astype(jnp.float32)
    s_lanes = jax.lax.dot_general(s, expand, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    w = (w8 * s_lanes).astype(x.dtype)
    acc[...] += jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(kstep == nk - 1)
    def _done():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def quantized_matmul(x, q, scale, group_size, out_dtype=None,
                     interpret=False, block_config=None):
    """x [M, K] @ dequant(q [K, N] int8, scale [K, N//G]) -> [M, N].

    Blocks resolve tuning table > ladder (module BM/BN/BK defaults);
    ``block_config`` (a ``BlockConfig`` or ``{"block_m": .., "block_n": ..,
    "block_k": ..}`` dict) pins them outright — the tuner sweep path.

    SPMD: rows (``M``) shard over the active mesh's data axes and output
    features (``N``, with the matching ``N//G`` scale columns) over the TP
    axis — the classic column-parallel layout, K replicated so no cross-shard
    reduction is needed. Sharding is vetoed unless the per-shard dims still
    satisfy the kernel's block constraints (``is_supported``'s rules).
    """
    from deepspeed_tpu.autotuning.kernel_table import BlockConfig
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.registry import sharded_kernel_call

    M, K = x.shape
    N = q.shape[1]
    if block_config is not None:
        if not isinstance(block_config, BlockConfig):
            block_config = BlockConfig.make("quantized_matmul",
                                            source="sweep",
                                            **dict(block_config))
        bm, bn, bk = (block_config.get("block_m"), block_config.get("block_n"),
                      block_config.get("block_k"))
        if not _blocks_fit(bm, bn, bk, M, K, N, group_size):
            raise ValueError(
                f"quantized_matmul: pinned blocks (bm={bm}, bn={bn}, bk={bk})"
                f" do not tile M={M}, K={K}, N={N}, group={group_size}")
        registry.note_block_config("quantized_matmul", block_config,
                                   reason=block_config.source)
    else:
        block_config = _resolve_blocks(M, K, N, group_size, x.dtype)
    blocks = (block_config.get("block_m"), block_config.get("block_n"),
              block_config.get("block_k"))

    def call(x_, q_, s_):
        return _quantized_matmul_local(x_, q_, s_, group_size,
                                       out_dtype=out_dtype,
                                       interpret=interpret, blocks=blocks)

    def accept(shard_shapes):
        (m, k), (_, n), _ = shard_shapes
        return _blocks_fit(blocks[0], min(blocks[1], n), blocks[2], m, k, n,
                           group_size)

    return sharded_kernel_call(
        call, [x, q, scale],
        [("data", None), (None, "head"), (None, "head")],
        ("data", "head"), accept=accept, name="quantized_matmul",
        block_config=block_config)


def _quantized_matmul_local(x, q, scale, group_size, out_dtype=None,
                            interpret=False, blocks=None):
    M, K = x.shape
    _, N = q.shape
    out_dtype = out_dtype or x.dtype
    BM_, BN_, BK_ = blocks if blocks is not None else (BM, BN, BK)
    bm = min(BM_, M)
    BN_ = min(BN_, N)   # a whole-width block, seen from a tp shard of N
    nm, nn, nk = M // bm, N // BN_, K // BK_

    with jax.named_scope("quantized_matmul"):
        out = pl.pallas_call(
            functools.partial(_kernel, nk=nk, bn=BN_, group_size=group_size),
            grid=(nm, nn, nk),
            in_specs=[
                pl.BlockSpec((bm, BK_), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((BK_, BN_), lambda i, j, kk: (kk, j)),
                # per-j scale block [bk, bn//G]: sliced by the DMA machinery
                # here, never by an in-kernel lane-dim dynamic slice
                pl.BlockSpec((BK_, BN_ // group_size), lambda i, j, kk: (kk, j)),
            ],
            out_specs=pl.BlockSpec((bm, BN_), lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, BN_), jnp.float32)],
            name="quantized_matmul",
            interpret=interpret,
        )(x, q, scale.astype(jnp.float32))
    return out
