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
"""

import jax
import jax.numpy as jnp

ROW_ALIGN = 128  # gmm's default m-dimension tile (ladder tiling fallback)


def is_supported(d_model, d_ff):
    # gmm tiles k/n at 128; ragged m is handled by padding below
    return (d_model is not None and d_ff is not None
            and d_model % ROW_ALIGN == 0 and d_ff % ROW_ALIGN == 0)


def _tiling_fits(tm, tk, tn, d, f):
    """Whether a gmm (tile_m, tile_k, tile_n) triple tiles both GEMMs of the
    FFN — x@w1/w3 contracts D and emits F, h@w2 contracts F and emits D, so
    every tile dim must divide both feature dims. tile_m only pads rows
    (handled below), but keep it lane-aligned for the MXU."""
    return (tm % ROW_ALIGN == 0
            and d % tk == 0 and f % tk == 0
            and d % tn == 0 and f % tn == 0)


def _resolve_tiling(rows, d, f, dtype):
    """Tuning-table-first gmm tiling (ladder = megablox default 128^3)."""
    from deepspeed_tpu.ops import registry

    def validate(blocks, dims):
        return _tiling_fits(blocks["tile_m"], blocks["tile_k"],
                            blocks["tile_n"], dims["d"], dims["f"])

    def ladder():
        return {"tile_m": ROW_ALIGN, "tile_k": 128, "tile_n": 128}

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


def moe_ffn_gmm(x, top_vals, top_idx, w1, w2, w3, *, n_experts, dtype,
                valid=None, interpret=False, block_config=None):
    """Mixtral-style expert FFN: silu(x@w1) * (x@w3) @ w2 per expert, routed
    by precomputed (top_vals, top_idx) from :func:`topk_router`.

    x [T, D]; w1/w3 [E, D, F]; w2 [E, F, D] -> [T, D]. ``valid`` [T] bool
    (None: all): a token that is not valid takes no expert rows (its rows
    sort past every expert's group, which the grouped GEMM never visits) and
    gets zeros.

    SPMD: tokens shard over the active mesh's data axes (dp AND ep — under
    expert parallelism the token batch is split across the expert world, the
    reference's expert groups carved out of DP); the scatter→gmm→gather chain
    is per-token exact, so each shard grouping only its own tokens gives
    bitwise-identical rows. Expert weights stay replicated in the spec — if
    the caller holds them ep-sharded, GSPMD all-gathers at entry.

    The gmm ``tiling`` triple resolves tuning table > ladder (megablox's
    128^3 default); ``block_config`` (a ``BlockConfig`` or ``{"tile_m": ..,
    "tile_k": .., "tile_n": ..}`` dict) pins it — the tuner sweep path.
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
        tm, tk, tn = (block_config.get("tile_m"), block_config.get("tile_k"),
                      block_config.get("tile_n"))
        if not _tiling_fits(tm, tk, tn, D, F):
            raise ValueError(f"moe_ffn_gmm: pinned tiling ({tm}, {tk}, {tn})"
                             f" does not tile D={D}, F={F}")
        registry.note_block_config("moe_ffn_gmm", block_config,
                                   reason=block_config.source)
    else:
        block_config = _resolve_tiling(rows, D, F, x.dtype)
    tiling = (block_config.get("tile_m"), block_config.get("tile_k"),
              block_config.get("tile_n"))

    if valid is None:
        valid = jnp.ones((T,), bool)

    def call(x_, tv_, ti_, ok_, w1_, w2_, w3_):
        return _moe_ffn_gmm_local(x_, tv_, ti_, ok_, w1_, w2_, w3_,
                                  n_experts=n_experts, dtype=dtype,
                                  interpret=interpret, tiling=tiling)

    wr = (None, None, None)
    return sharded_kernel_call(
        call, [x, top_vals, top_idx, valid, w1, w2, w3],
        [("data", None), ("data", None), ("data", None), ("data",),
         wr, wr, wr],
        ("data", None), name="moe_ffn_gmm", block_config=block_config)


def moe_ffn_gmm_rows(x_rows, row_experts, w1, w2, w3, *, n_experts, dtype,
                     interpret=False, tiling=None):
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
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    R, D = x_rows.shape
    E = n_experts
    tm, tk, tn = tiling if tiling is not None else (ROW_ALIGN, 128, 128)

    order = jnp.argsort(row_experts, stable=True)
    xs = jnp.take(x_rows, order, axis=0)                 # [R, D] grouped
    group_sizes = jnp.zeros((E,), jnp.int32).at[row_experts].add(1)
    pad = (-R) % tm
    if pad:
        xs = jnp.concatenate([xs, jnp.zeros((pad, D), xs.dtype)], axis=0)
        group_sizes = group_sizes.at[E - 1].add(pad)

    def grouped(lhs, rhs):
        return gmm(lhs, rhs, group_sizes,
                   preferred_element_type=jnp.float32,
                   tiling=(tm, tk, tn),
                   interpret=interpret).astype(dtype)

    h = jax.nn.silu(grouped(xs, w1)) * grouped(xs, w3)   # [R+pad, F]
    y = grouped(h, w2)[:R]                               # [R, D]
    inv = jnp.argsort(order, stable=True)
    return jnp.take(y, inv, axis=0)


def _moe_ffn_gmm_local(x, top_vals, top_idx, valid, w1, w2, w3, *, n_experts,
                       dtype, interpret=False, tiling=None):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    T, D = x.shape
    E = n_experts
    k = top_idx.shape[-1]
    tm, tk, tn = tiling if tiling is not None else (ROW_ALIGN, 128, 128)
    rows = T * k
    pad = (-rows) % tm  # rows padded to the m-tile; no group holds the pad

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

    def grouped(lhs, rhs):
        with jax.named_scope("moe_ffn_gmm"):
            return gmm(lhs, rhs, group_sizes,
                       preferred_element_type=jnp.float32,
                       tiling=(tm, tk, tn),
                       interpret=interpret).astype(dtype)

    h = jax.nn.silu(grouped(xs, w1)) * grouped(xs, w3)   # [rows+pad, F]
    y = grouped(h, w2)[:rows]                            # [rows, D]

    # moe_gather: unsort, weight by gate, combine the k slots
    with jax.named_scope("moe_unsort"):
        inv = jnp.argsort(order, stable=True)
        y = jnp.take(y, inv, axis=0).reshape(T, k, D)
        y = jnp.where(valid[:, None, None], y.astype(jnp.float32), 0.0)
        return jnp.sum(y * top_vals[..., None], axis=1).astype(dtype)
