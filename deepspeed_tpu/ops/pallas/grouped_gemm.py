"""Ragged grouped-GEMM MoE FFN over the Pallas ``megablox`` kernel.

Capability analog of the reference's CUTLASS grouped expert GEMMs +
moe_scatter/moe_gather (``inference/v2/kernels/cutlass_ops/moe_gemm``,
``kernels/ragged_ops/{moe_scatter,moe_gather}``): tokens are sorted by
assigned expert (moe_scatter), each expert's contiguous row-group hits the
MXU through ``jax.experimental.pallas.ops.tpu.megablox.gmm`` — no capacity
dimension, no [T, E, C] dispatch tensors — and the weighted results unsort
back (moe_gather).

vs the GShard einsum path (`inference/v2/model_implementations/mixtral.py`):
that one is O(T^2 E) in dispatch memory/FLOPs at lossless capacity; this one
is O(T k) rows regardless of routing skew. The einsum path remains the
numerics oracle and CPU fallback.

Tiling. The FFN is three grouped GEMMs of two shapes: ``x @ w1`` and
``x @ w3`` contract D and emit F, ``h @ w2`` contracts F and emits D. Each
shape gets its own ``(tile_m, tile_k, tile_n)`` from :func:`gmm_tiling`: the
divisors of ITS ``k`` and ``n`` (multiples of 128) that take the fewest grid
steps a visited group while the kernel's blocks stay under ``VMEM_BUDGET``.
A grid step costs ~0.35 us whatever it moves, and megablox takes
``k/tile_k x n/tile_n`` of them for every group it visits
(:func:`gmm_grid_steps`), so a tiling that had to divide both D and F (their
common divisor is often 128) made the grid, not the weights' read, the
kernel's time. The five knobs of the FFN's ``BlockConfig`` (``tile_m``, and
``tile_k`` / ``tile_n`` of the up and of the down GEMM) resolve tuning table >
that rule, or are pinned by the tuner's sweep.
"""

import functools
import importlib

import jax
import jax.numpy as jnp

# The MXU's edge and a vreg's lanes: every tile edge is a multiple of it, and
# the supported feature widths are. ``tile_m`` IS it: megablox multiplies
# tile_m rows for every group it visits however few are the group's own, and
# under 128 rows the MXU's weight load, not its rows, sets a visit's time
ROW_ALIGN = 128

# What a call's blocks may take of v5e's 16 MiB of scoped VMEM (megablox's
# pallas_call does not raise the limit); the rest is Mosaic's own scratch
VMEM_BUDGET = 12 * 1024 * 1024

KNOBS_UP = ("tile_m", "up_tile_k", "up_tile_n")
KNOBS_DOWN = ("tile_m", "down_tile_k", "down_tile_n")


def is_supported(d_model, d_ff):
    # every GEMM tiles its own k and n in multiples of 128, down to 128
    # itself; ragged m is handled by padding below
    return (d_model is not None and d_ff is not None
            and d_model % ROW_ALIGN == 0 and d_ff % ROW_ALIGN == 0)


def _tile_widths(width):
    """The multiples of 128 that divide ``width``, largest first."""
    units = width // ROW_ALIGN
    return [u * ROW_ALIGN for u in range(units, 0, -1) if units % u == 0]


def gmm_vmem_bytes(tiling, itemsize):
    """VMEM of one megablox ``gmm`` grid step: the weights' block and the row
    tile, each double-buffered; the float32 output tile, double-buffered, and
    the accumulator. Mosaic's own count for (512, 2304, 896) in bfloat16 is
    0.8 MiB over this one."""
    tm, tk, tn = tiling
    return 2 * (tk * tn + tm * tk) * itemsize + 3 * tm * tn * 4


def gmm_tilings(k, n, itemsize):
    """Every ``(tile_m, tile_k, tile_n)`` of one grouped GEMM ``[rows, k] @
    [E, k, n]`` that fits ``VMEM_BUDGET``, best first: the fewest grid steps a
    visited group; among equals ``k`` whole (the weights' block then stays
    put while a group's row tiles pass, and no partial sum is carried from
    step to step), then the widest ``tile_n`` (one DMA row is ``tile_n``
    contiguous elements of the weights). The order is the chip's: v5e at
    Mellum2's and Mixtral's widths, PERF.md PR 35."""
    fits = [(ROW_ALIGN, tk, tn)
            for tk in _tile_widths(k) for tn in _tile_widths(n)
            if gmm_vmem_bytes((ROW_ALIGN, tk, tn), itemsize) <= VMEM_BUDGET]
    return sorted(fits, key=lambda t: ((k // t[1]) * (n // t[2]),
                                       t[1] != k, -t[2]))


def gmm_tiling(k, n, itemsize):
    """The rule: the first of :func:`gmm_tilings`."""
    return gmm_tilings(k, n, itemsize)[0]


def gmm_grid_steps(rows, groups, k, n, tiling):
    """Grid steps of one ``gmm`` call at most: megablox visits every row tile
    once and once more for each group that starts inside one (row tiles +
    groups - 1 visits when no group is empty), and steps through ``k / tile_k
    x n / tile_n`` blocks of the group's weights on every visit."""
    tm, tk, tn = tiling
    return (-(-rows // tm) + groups - 1) * (k // tk) * (n // tn)


def ffn_tilings(blocks):
    """``(up, down)`` triples out of the FFN's five knobs."""
    return (tuple(blocks[name] for name in KNOBS_UP),
            tuple(blocks[name] for name in KNOBS_DOWN))


def ffn_blocks(up, down):
    """The FFN's five knobs out of its two GEMM shapes' triples."""
    assert up[0] == down[0], "the three GEMMs share their rows' padding"
    return {**dict(zip(KNOBS_UP, up)), **dict(zip(KNOBS_DOWN, down))}


def _tiling_fits(blocks, d, f):
    """Whether the five knobs tile the FFN's GEMMs: the up GEMMs contract D
    and emit F, the down GEMM contracts F and emits D, so each triple has to
    divide its own GEMM's widths and no other's. ``tile_m`` only pads rows."""
    up, down = ffn_tilings(blocks)
    return all(t % ROW_ALIGN == 0 for t in up + down) and all(
        k % tk == 0 and n % tn == 0
        for (_, tk, tn), (k, n) in ((up, (d, f)), (down, (f, d))))


def _resolve_tiling(rows, d, f, dtype):
    """Tuning-table-first tiling of the FFN (ladder = :func:`gmm_tiling` for
    each GEMM shape); the registry keeps it as the kernel's active config."""
    from deepspeed_tpu.ops import registry
    itemsize = jnp.dtype(dtype).itemsize

    def validate(blocks, dims):
        return _tiling_fits(blocks, dims["d"], dims["f"])

    def ladder():
        return ffn_blocks(gmm_tiling(d, f, itemsize),
                          gmm_tiling(f, d, itemsize))

    return registry.resolve_block_config(
        "moe_ffn_gmm", {"rows": rows, "d": d, "f": f}, dtype,
        validate=validate, ladder=ladder)


def topk_router(x, gate_wg, k):
    """Mixtral top-k softmax router with renormalized gate weights.

    THE routing implementation — both the megablox and the einsum dispatch
    paths consume its (top_vals [T, k], top_idx [T, k]) so gating numerics
    can never diverge between backends."""
    logits = jnp.dot(x, gate_wg, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, k)
    return top_vals / jnp.sum(top_vals, axis=-1, keepdims=True), top_idx


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, group_sizes, tiling, interpret):
    """``lhs[group's rows] @ rhs[group]`` in float32, tiled for THIS GEMM's
    (k, n). megablox's own VJP hands the forward's triple to the backward's
    GEMMs, where k and n change places and a triple fit to one GEMM need not
    divide; this one runs them at 128^3, which divides every supported width
    and is what they ran at before (no cell or tuner times the backward)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    return gmm(lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
               tiling=tiling, interpret=interpret)


def _gmm_fwd(lhs, rhs, group_sizes, tiling, interpret):
    return (_gmm(lhs, rhs, group_sizes, tiling, interpret),
            (lhs, rhs, group_sizes))


def _gmm_bwd(tiling, interpret, residual, grad):
    del tiling
    backend = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    lhs, rhs, group_sizes = residual
    tiles = (ROW_ALIGN, ROW_ALIGN, ROW_ALIGN)
    grad_lhs = backend.gmm(grad, rhs, group_sizes, lhs.dtype, tiles,
                           transpose_rhs=True, interpret=interpret)
    grad_rhs = backend.tgmm(lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
                            tiles, interpret=interpret)
    return grad_lhs, grad_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def moe_ffn_gmm(x, top_vals, top_idx, w1, w2, w3, *, n_experts, dtype,
                valid=None, interpret=False, block_config=None):
    """Mixtral-style expert FFN: silu(x@w1) * (x@w3) @ w2 per expert, routed
    by precomputed (top_vals, top_idx) from :func:`topk_router`.

    x [T, D]; w1/w3 [E, D, F]; w2 [E, F, D] -> [T, D]. ``valid`` [T] bool
    (None: all): a token that is not valid takes no expert rows (its rows
    sort past every expert's group, which the grouped GEMM never visits) and
    gets zeros. So does a single row whose ``top_idx`` is ``n_experts``: an
    expert the caller does not hold (``moe_layer.moe_ffn``'s
    ``experts_held``).

    SPMD: tokens shard over the active mesh's data axes (dp AND ep — under
    expert parallelism the token batch is split across the expert world, the
    reference's expert groups carved out of DP); the scatter→gmm→gather chain
    is per-token exact, so each shard grouping only its own tokens gives
    bitwise-identical rows. Expert weights stay replicated in the spec — if
    the caller holds them ep-sharded, GSPMD all-gathers at entry.

    The tiling resolves tuning table > :func:`gmm_tiling` a GEMM shape;
    ``block_config`` (a ``BlockConfig`` or a dict of the five knobs) pins it
    — the tuner sweep path — and has to divide each GEMM's own widths.
    """
    from deepspeed_tpu.autotuning.kernel_table import BlockConfig
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.registry import sharded_kernel_call

    T, D = x.shape
    F = w1.shape[-1]
    rows = T * top_idx.shape[-1]
    if block_config is not None:
        if not isinstance(block_config, BlockConfig):
            block_config = BlockConfig.make("moe_ffn_gmm", source="sweep",
                                            **dict(block_config))
        if not _tiling_fits(block_config.as_dict(), D, F):
            raise ValueError(f"moe_ffn_gmm: pinned tiling {block_config!r}"
                             f" does not tile D={D}, F={F}")
        registry.note_block_config("moe_ffn_gmm", block_config,
                                   reason=block_config.source)
    else:
        block_config = _resolve_tiling(rows, D, F, x.dtype)
    tilings = ffn_tilings(block_config.as_dict())

    if valid is None:
        valid = jnp.ones((T,), bool)

    def call(x_, tv_, ti_, ok_, w1_, w2_, w3_):
        return _moe_ffn_gmm_local(x_, tv_, ti_, ok_, w1_, w2_, w3_,
                                  n_experts=n_experts, dtype=dtype,
                                  interpret=interpret, tilings=tilings)

    wr = (None, None, None)
    return sharded_kernel_call(
        call, [x, top_vals, top_idx, valid, w1, w2, w3],
        [("data", None), ("data", None), ("data", None), ("data",),
         wr, wr, wr],
        ("data", None), name="moe_ffn_gmm", block_config=block_config)


def moe_ffn_gmm_rows(x_rows, row_experts, w1, w2, w3, *, n_experts, dtype,
                     interpret=False):
    """Per-row grouped expert FFN: row ``i`` runs through expert
    ``row_experts[i]`` — silu(x@w1) * (x@w3) @ w2, outputs in input row
    order. No gate weighting and no k-slot combine: the expert-parallel
    all-to-all path calls this on the RECEIVING shard and weights rows back
    on the sender, so the per-row result is the unit of exchange.

    Direct call, no ``sharded_kernel_call``: the caller sits inside a
    manual-axes ``shard_map`` body where every mesh axis is already bound,
    so the registry could only fall back ("no_live_role") anyway.

    x_rows [R, D]; row_experts [R] int32 in [0, n_experts); w1/w3
    [E, D, F]; w2 [E, F, D] -> [R, D].
    """
    R, D = x_rows.shape
    E = n_experts
    up, down = ffn_tilings(
        _resolve_tiling(R, D, w1.shape[-1], x_rows.dtype).as_dict())

    order = jnp.argsort(row_experts, stable=True)
    xs = jnp.take(x_rows, order, axis=0)                 # [R, D] grouped
    group_sizes = jnp.zeros((E,), jnp.int32).at[row_experts].add(1)
    pad = (-R) % up[0]
    if pad:
        xs = jnp.concatenate([xs, jnp.zeros((pad, D), xs.dtype)], axis=0)
        group_sizes = group_sizes.at[E - 1].add(pad)

    def grouped(lhs, rhs, tiling):
        return _gmm(lhs, rhs, group_sizes, tiling, interpret).astype(dtype)

    h = jax.nn.silu(grouped(xs, w1, up)) * grouped(xs, w3, up)  # [R+pad, F]
    y = grouped(h, w2, down)[:R]                         # [R, D]
    inv = jnp.argsort(order, stable=True)
    return jnp.take(y, inv, axis=0)


def _moe_ffn_gmm_local(x, top_vals, top_idx, valid, w1, w2, w3, *, n_experts,
                       dtype, interpret=False, tilings=None):
    T, D = x.shape
    E = n_experts
    k = top_idx.shape[-1]
    rows = T * k
    if tilings is None:
        tilings = ffn_tilings(
            _resolve_tiling(rows, D, w1.shape[-1], x.dtype).as_dict())
    up, down = tilings
    pad = (-rows) % up[0]  # rows padded to the m-tile; no group holds the pad

    # moe_scatter: stable sort of the T*k (token, expert) rows by expert. A
    # token that is not valid sorts as expert E, past every group: the group
    # sizes then sum to the valid rows only and gmm never visits the rest
    # (nor the pad), whose output rows stay unwritten and are masked below
    with jax.named_scope("moe_sort"):
        flat_e = jnp.where(jnp.repeat(valid, k), top_idx.reshape(-1), E)
        order = jnp.argsort(flat_e, stable=True)
        token_of = jnp.arange(rows, dtype=jnp.int32) // k
        xs = jnp.take(x, token_of[order], axis=0)        # [T*k, D] grouped
        group_sizes = jnp.zeros((E,), jnp.int32).at[flat_e].add(
            1, mode="drop")
        if pad:
            xs = jnp.concatenate(
                [xs, jnp.zeros((pad, D), xs.dtype)], axis=0)

    def grouped(lhs, rhs, tiling):
        with jax.named_scope("moe_ffn_gmm"):
            return _gmm(lhs, rhs, group_sizes, tiling,
                        interpret).astype(dtype)

    h = jax.nn.silu(grouped(xs, w1, up)) * grouped(xs, w3, up)  # [rows+pad, F]
    y = grouped(h, w2, down)[:rows]                      # [rows, D]

    # moe_gather: unsort, weight by gate, combine the k slots
    with jax.named_scope("moe_unsort"):
        inv = jnp.argsort(order, stable=True)
        y = jnp.take(y, inv, axis=0).reshape(T, k, D)
        # rows no group held (a padded slot's; an expert index of E) were
        # never written
        written = valid[:, None] & (top_idx < E)
        y = jnp.where(written[..., None], y.astype(jnp.float32), 0.0)
        return jnp.sum(y * top_vals[..., None], axis=1).astype(dtype)
