"""Pallas kernels of learned sparse attention's indexer over paged keys: the
index scores of a query on every cached token of its row, and the selection
of the ``topk`` largest as a threshold. The read under that threshold is a
mode of the paged walk (``paged_attention.paged_mha``'s ``select``).

``paged_index_scores`` (its name in a trace). The index pool ``[NB, 1, bs,
W]`` stays in HBM and holds one key a token, ``W`` a whole number of lane
tiles. The block table, ``seen`` and ``q_len`` are scalar-prefetched; the grid
is ``(seqs, table width / pages)`` and a step copies the LIVE pages of its
``pages`` by ``make_async_copy`` into one half of a double-buffered scratch
while the step before it is multiplied (the copies of a step are started by
its predecessor, across sequences too), so bytes are O(live pages) and a step
whose pages are all dead writes ``-inf`` and copies nothing. A step computes
``sum_j w[t, j] ReLU(q[t, j] . key[n])`` for its ``pages * bs`` keys: a
``[D, 1]`` decode dispatch as ONE ``[heads, W] x [W, keys]`` product a row
(the heads are the rows), a ``[1, C]`` chunk as ``heads`` products ``[C, W] x
[W, keys]`` accumulated in float32. Keys behind a query (``n > seen + t``)
read ``-inf``.

``topk_threshold``. The ``topk``-th largest of each row of scores without a
sort: float32 scores map to int32 keys of the same order, and the threshold's
32 bits are settled from the top, one pass a bit, each pass counting the keys
at or above the candidate. A grid step holds 8 rows' keys in VMEM (one copy
from HBM) and counts lane-wise, so a pass is compare, select and add a vector
register; it counts only up to the tile's longest live context. A row with
fewer than ``topk`` finite scores gets ``-inf``: it reads all it sees.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROWS = 8                         # float32 rows of a vector register

_PAGE_BYTES = 2 << 20            # one half of the page buffer
_TILE_BYTES = 1 << 20            # a step's float32 score tile
_VMEM_LIMIT_BYTES = 48 << 20
_COUNT_LANES = 1024              # columns a trip of a counting pass takes

_INT_MIN = np.int32(-2 ** 31)
#: the int32 key of float32 ``-inf``: no score's key lies below it
_KEY_NEG_INF = np.float32(-np.inf).view(np.int32) ^ np.int32(0x7FFFFFFF)


def _pages_a_step(table_width, bs, width, itemsize, chunk):
    """Pages a grid step of ``paged_index_scores`` takes: the largest divisor
    of the table's width whose pages fit half the buffer and whose score tile
    fits its budget."""
    most = max(1, min(_PAGE_BYTES // (bs * width * itemsize),
                      _TILE_BYTES // (4 * chunk * bs)))
    return max(p for p in range(1, min(most, table_width) + 1)
               if table_width % p == 0)


def scores_is_supported(q_shape, pool_shape):
    S, Q, Hi, Di = q_shape
    NB, heads, bs, width = pool_shape
    return (heads == 1 and width % LANES == 0 and Di <= width and bs % 8 == 0
            and (Q == 1 or Q % 8 == 0))


def _scores_kernel(bt_ref, seen_ref, qlen_ref, q_ref, w_ref, pool_hbm, o_ref,
                   buf, sems, *, bs, pages, heads, chunk):
    s, t = pl.program_id(0), pl.program_id(1)
    n_t = pl.num_programs(1)
    step = s * n_t + t
    keys = pages * bs

    def live_here(seq, trip):
        """Live pages among the ``pages`` of step ``(seq, trip)``."""
        live = jnp.maximum(pl.cdiv(seen_ref[seq] + qlen_ref[seq], bs), 1)
        return jnp.clip(live - trip * pages, 0, pages)

    def each_copy(seq, trip, slot, act):
        def one(p, _):
            act(pltpu.make_async_copy(
                pool_hbm.at[bt_ref[seq, trip * pages + p]],
                buf.at[slot, p], sems.at[slot]))
            return 0
        jax.lax.fori_loop(0, live_here(seq, trip), one, 0)

    slot = jax.lax.rem(step, 2)

    @pl.when(step == 0)
    def _first():
        # a page of a step that no copy filled is multiplied all the same:
        # its keys lie behind every query, but have to be finite
        buf[...] = jnp.zeros_like(buf)
        each_copy(0, 0, 0, lambda c: c.start())

    @pl.when(step + 1 < pl.num_programs(0) * n_t)
    def _prefetch():
        wraps = t + 1 == n_t
        each_copy(jnp.where(wraps, s + 1, s), jnp.where(wraps, 0, t + 1),
                  1 - slot, lambda c: c.start())

    each_copy(s, t, slot, lambda c: c.wait())

    @pl.when(live_here(s, t) == 0)
    def _dead():
        o_ref[...] = jnp.full_like(o_ref, -jnp.inf)

    @pl.when(live_here(s, t) > 0)
    def _live():
        k = buf[slot].reshape(keys, buf.shape[-1])
        dot = lambda q: jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if chunk == 1:
            # the heads are the rows: [heads, W] x [W, keys]
            scores = jnp.sum(jax.nn.relu(dot(q_ref[0])) * w_ref[0], axis=0,
                             keepdims=True)
        else:
            w = w_ref[0]                                   # [chunk, heads]
            scores = jnp.zeros((chunk, keys), jnp.float32)
            for j in range(heads):
                scores += jax.nn.relu(dot(q_ref[0, j])) * w[:, j:j + 1]
        kpos = t * keys + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        qi = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        o_ref[0] = jnp.where(kpos <= seen_ref[s] + qi, scores, -jnp.inf)


def paged_index_scores(q_idx, w_idx, pool, block_tables, seen, q_len, *,
                       interpret=False):
    """q_idx [S, Q, Hi, Di], w_idx [S, Q, Hi] float32, pool [NB, 1, bs, W],
    block_tables [S, MB] -> scores [S, Q, MB * bs] float32 (module
    docstring). Sequences shard over the mesh's data axes; the pool stays
    whole on each shard."""
    from deepspeed_tpu.ops.registry import sharded_kernel_call

    def call(q_, w_, bt_, sn_, ql_, pool_):
        return _index_scores_local(q_, w_, pool_, bt_, sn_, ql_,
                                   interpret=interpret)

    return sharded_kernel_call(
        call, [q_idx, w_idx, block_tables, seen, q_len, pool],
        [("data", None, None, None), ("data", None, None), ("data", None),
         ("data",), ("data",), (None, None, None, None)],
        ("data", None, None), name="paged_index_scores")


def _index_scores_local(q_idx, w_idx, pool, block_tables, seen, q_len, *,
                        interpret=False):
    S, Q, Hi, Di = q_idx.shape
    _, _, bs, W = pool.shape
    MB = block_tables.shape[1]
    pages = _pages_a_step(MB, bs, W, pool.dtype.itemsize, Q)
    keys = pages * bs
    q = jnp.pad(q_idx.astype(pool.dtype), ((0, 0),) * 3 + ((0, W - Di),))
    w = w_idx.astype(jnp.float32)
    if Q == 1:
        q, w = q[:, 0], w[:, 0, :, None]                  # [S,Hi,W], [S,Hi,1]
        q_spec = pl.BlockSpec((1, Hi, W), lambda s, t, bt, sn, ql: (s, 0, 0))
        w_spec = pl.BlockSpec((1, Hi, 1), lambda s, t, bt, sn, ql: (s, 0, 0))
    else:
        q = q.transpose(0, 2, 1, 3)                       # [S, Hi, Q, W]
        q_spec = pl.BlockSpec((1, Hi, Q, W),
                              lambda s, t, bt, sn, ql: (s, 0, 0, 0))
        w_spec = pl.BlockSpec((1, Q, Hi), lambda s, t, bt, sn, ql: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, MB // pages),
        in_specs=[q_spec, w_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, Q, keys),
                               lambda s, t, bt, sn, ql: (s, 0, t)),
        scratch_shapes=[pltpu.VMEM((2, pages, 1, bs, W), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    kernel = functools.partial(_scores_kernel, bs=bs, pages=pages, heads=Hi,
                               chunk=Q)
    with jax.named_scope("paged_index_scores"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, Q, MB * bs), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            name="paged_index_scores",
            interpret=interpret,
        )(block_tables.astype(jnp.int32), seen.astype(jnp.int32),
          q_len.astype(jnp.int32), q, w, pool)


# -- the selection as a threshold ---------------------------------------------

def _to_key(x):
    """float32 -> int32 of the same order (``-0.0`` below ``0.0``)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


def _from_key(key):
    return jax.lax.bitcast_convert_type(
        key ^ ((key >> 31) & jnp.int32(0x7FFFFFFF)), jnp.float32)


def threshold_is_supported(scores_shape):
    return scores_shape[-1] % LANES == 0


def _threshold_kernel(trips_ref, x_ref, o_ref, key_scr, *, topk, width):
    """8 rows of scores -> their ``topk``-th largest, on every lane of the
    output's rows. ``trips_ref``: trips of ``width`` columns that reach the
    tile's longest live context."""
    trips = trips_ref[pl.program_id(0)]
    cols = lambda c: pl.ds(pl.multiple_of(c * width, LANES), width)

    def load(c, _):
        key_scr[:, cols(c)] = _to_key(x_ref[:, cols(c)])
        return 0

    jax.lax.fori_loop(0, trips, load, 0)

    def settle(i, t):
        # bit 31 first: INT_MIN + 2^31 wraps to 0, the keys' midpoint
        cand = t + jnp.left_shift(jnp.int32(1), 31 - i)
        at = jnp.broadcast_to(cand, (ROWS, LANES))

        def count(c, n):
            tile = key_scr[:, cols(c)]
            for j in range(width // LANES):
                n = n + jnp.where(tile[:, j * LANES:(j + 1) * LANES] >= at, 1, 0)
            return n

        n = jax.lax.fori_loop(0, trips, count,
                              jnp.zeros((ROWS, LANES), jnp.int32))
        # a row's count is below 2^24: exact in float32
        n = jnp.sum(n.astype(jnp.float32), axis=1, keepdims=True)
        return jnp.where(n >= topk, cand, t)

    t = jax.lax.fori_loop(0, 32, settle, jnp.full((ROWS, 1), _INT_MIN))
    tau = _from_key(jnp.maximum(t, _KEY_NEG_INF))
    o_ref[...] = jnp.broadcast_to(tau, o_ref.shape)


def topk_threshold(scores, visible, topk, *, interpret=False):
    """scores [S, Q, N] float32 -> tau [S, Q]: each row's ``topk``-th largest
    score, ``-inf`` for a row with fewer than ``topk`` scores above ``-inf``
    (module docstring). ``visible`` [S, Q] int32: the leading columns of a
    row that may hold a score above ``-inf`` (the tokens its query sees).
    Rows shard over the mesh's data axes."""
    from deepspeed_tpu.ops.registry import sharded_kernel_call

    def call(x, vis):
        return _threshold_local(x, vis, topk, interpret=interpret)

    return sharded_kernel_call(call, [scores, visible],
                               [("data", None, None), ("data", None)],
                               ("data", None), name="topk_threshold")


def _threshold_local(scores, visible, topk, *, interpret=False):
    S, Q, N = scores.shape
    rows = S * Q
    x = scores.reshape(rows, N)
    live = jnp.minimum(visible.reshape(rows), N)
    pad = -rows % ROWS
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)), constant_values=-jnp.inf)
        live = jnp.pad(live, (0, pad))
    tiles = (rows + pad) // ROWS
    width = max(w for w in range(LANES, min(_COUNT_LANES, N) + 1, LANES)
                if N % w == 0)
    # trips of ``width`` columns that reach a tile's longest live context
    trips = (-(-live.reshape(tiles, ROWS).max(axis=1) // width)).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles,),
        in_specs=[pl.BlockSpec((ROWS, N), lambda i, trips: (i, 0))],
        out_specs=pl.BlockSpec((ROWS, LANES), lambda i, trips: (i, 0)),
        scratch_shapes=[pltpu.VMEM((ROWS, N), jnp.int32)],
    )
    with jax.named_scope("topk_threshold"):
        out = pl.pallas_call(
            functools.partial(_threshold_kernel, topk=topk, width=width),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((tiles * ROWS, LANES), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            name="topk_threshold",
            interpret=interpret,
        )(trips, x)
    return out[:rows, 0].reshape(S, Q)
