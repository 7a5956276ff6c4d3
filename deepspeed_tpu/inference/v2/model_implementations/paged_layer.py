"""How a layer of a ragged forward touches its paged cache: the one place
that knows how the stacked pools are laid out for the layer loop, how a
round's K and V rows are written into them (page by page through the block
table, or row by row: "The write" below), how they are read and which
kernel reads them (the reference's ragged kernel set
``inference/v2/kernels/ragged_ops``: linear_blocked_kv_rotary -> scatter into
the paged cache, blocked_flash -> paged attention, logits_gather ->
last-token logits). Every family's forward calls these and nothing else of
the cache.

Two kinds of page. A K and V pair (``_scatter_kv``, ``_paged_attention``),
and a page of ONE leaf, a latent row a token (``_scatter_latent``;
``ragged/cache_groups.py`` ``leaves=1``), which is read once for the scores
and the values: absorbed (``_latent_attention``) or, for a prompt chunk, with
the trip's keys and values up-projected for one head in VMEM
(``_latent_attention_up``); ``up_projects_in_walk`` is the one rule between
them, by static shapes.

A K and V pair may keep an indexer's key beside it (``index_dim``): learned
sparse attention (``dsa_attention``) writes it (``_scatter_index``), scores a
query against every cached key of its row (``_index_scores``), finds the
score of its ``topk``-th largest (``_select``: a threshold, never a sort) and
reads K and V at the tokens at or above it (``_sparse_attention``).

The layout. The state manager hands a forward stacked pools ``[L, NB+1,
KV, bs, Dh]`` (an ``(int8, scale)`` pair when ``kv_dtype="int8"``), the last
page of every layer being that layer's trash page, which absorbs the writes
of padded rows (``ragged/kv_cache.py`` allocates it). To the layer loop they
are ONE pool of ``L * (NB+1)`` pages (a free reshape, ``merge_layers``):
layer ``i`` owns pages ``[i * (NB+1), (i+1) * (NB+1))``, reached by
offsetting the block tables (``layer_rows``), its trash page among them
(``layer_trash``). The merged pools ride the CARRY of the layer loop, the
write updates them in place and the paged kernel reads pages through the
tables: no layer's pool is ever sliced out or written back. As scan inputs
and outputs the pools were two buffers each (the whole KV pool again as
scratch) and every round moved them through HBM ~13x. Pools of slots (a
recurrent state a sequence) are merged and offset the same way.

The write (``_write``, under the device scope ``paged_write``; ``_scatter_kv``,
``_scatter_latent`` and ``_scatter_index`` are its entry points by leaf). A
row's Q new tokens fill CONSECUTIVE slots of the pages its table names from
``seen // bs`` on, so the write goes through the block table as the reader
reads: PAGE-WISE (``_write_pages``), the at most ``pages_a_row(Q, bs)`` pages
a row touches read, their new slots taken from the chunk laid out as pages,
and written whole; a prompt chunk's 512 tokens are 9 page updates a pool and
layer. Or ROW-WISE (``_write_rows``), one ``[W]`` row a (token, head), where
that is fewer and cheaper updates: a decode row's one token. ``writes_pages``
is the one rule between them, by static shapes, with the chip's timings that
set it. Either way a real slot takes the same bytes, a slot that is not real
keeps what it held, a page another sequence shares is never written (a row
writes from ``seen`` on, in pages it alone holds) and what is padding lands
in the layer's trash page, which may hold any value.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.flash_attention import NEG_INF
from deepspeed_tpu.ops.registry import pallas_interpret, takes_kernel


# -- the layout ---------------------------------------------------------------

def merge_layers(pools):
    """Every leaf ``[L, n, ...]`` of ``pools`` (any pytree of stacked pools)
    as ``[L * n, ...]``."""
    return jax.tree.map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]), pools)


def split_layers(pools, layers):
    """Undo ``merge_layers`` for pools of ``layers`` layers."""
    return jax.tree.map(
        lambda a: a.reshape((layers, a.shape[0] // layers) + a.shape[1:]),
        pools)


def layer_rows(tables, i, nb):
    """``tables`` (page or slot ids within a layer) as ids of layer ``i`` in
    the merged pool of ``nb`` rows a layer. ``i``: a Python int or a traced
    scalar."""
    return tables + i * nb


def layer_trash(i, nb):
    """Layer ``i``'s trash page in the merged pool: the last of its ``nb``."""
    return i * nb + nb - 1


def _pool_parts(pool):
    """A KV pool is either an array (fp) or an ``(int8, scale)`` pair
    (``state_manager.kv_dtype="int8"``) — split without probing."""
    return pool if isinstance(pool, tuple) else (pool, None)


def pool_pages_per_layer(pool):
    """Pages a layer (trash included) of a possibly-quantized STACKED pool
    [L, NB+1, KV, bs, Dh]."""
    return _pool_parts(pool)[0].shape[1]


def _pool_block_size(pool):
    """Block size from a possibly-quantized STACKED pool [L, NB, KV, bs, Dh]."""
    return _pool_parts(pool)[0].shape[3]


def real_slots(q_len, chunk):
    """Which of a dispatch's ``[S, chunk]`` token slots hold a real token:
    the first ``q_len`` of each row. ``[S, chunk]`` bool."""
    return jnp.arange(chunk)[None, :] < q_len[:, None]


# -- the write ----------------------------------------------------------------

def _quantize_kv_rows(x):
    """[..., Dh] fp -> (int8 [..., Dh], fp32 scale [...]) — the per-row
    symmetric wire format of ``quant_collective`` applied per token row.
    Uses the module's jnp twin (the Pallas producer kernel needs
    group_size >= 256; KV rows are Dh wide), fused into the jitted forward."""
    from deepspeed_tpu.ops.pallas.quant_collective import _quantize_rows_ref
    q, scale = _quantize_rows_ref(
        x.astype(jnp.float32).reshape(-1, x.shape[-1]), 8)
    return q.reshape(x.shape), scale.reshape(x.shape[:-1])


#: row updates that cost the chip what ONE whole-page update costs
ROWS_A_PAGE = 16


def pages_a_row(Q, block_size):
    """The most pages ``Q`` consecutive token slots touch, wherever in a page
    the first lies."""
    return (Q + block_size - 2) // block_size + 1


def writes_pages(Q, KV, block_size, quantized=False):
    """THE rule of the write's form, from a dispatch's static shapes alone:
    page-wise (``_write_pages``) where a row's ``Q x KV`` row updates are at
    least ``ROWS_A_PAGE`` for each of the ``pages_a_row`` page updates that
    replace them, row-wise (``_write_rows``) below that and for an ``(int8,
    scale)`` pair, whose scale side pool ``[NB, KV, 1, bs]`` keeps the slot
    in its LAST dimension (no cell serves int8 pages; the pair keeps the
    scatter whole).

    The chip's timings that set it (one TPU v5 lite, PR 53, the write alone
    in a scan over the layers on the merged pool; PERF.md section 6 has the
    table by pool and shape): a ROW update costs 67-79 ns at rows of 256-512 B and
    ~120 ns at a latent row's 1,280 B, whatever the dispatch's shape: XLA
    runs them one after the other. A PAGE update (the page read, chosen by
    slot, written) costs 0.3-1.0 us at ``[1, C]`` and 0.8-1.5 us at ``[64,
    Q]`` for pages of 64-256 KB (0.2-0.3 us at an index leaf's 16 KB). So a
    page costs 4 to 21 rows, and at 16 rows a page no measured shape loses
    more than the timing's floor: Mistral's ``[1, 512]`` (455 rows a page)
    9.32 -> 0.56 ms both pools over 16 layers, ``[1, 16]`` (64) 0.48 -> 0.28,
    ``[4, 8]`` (32) 0.75 -> 0.36, ``[64, 8]`` (32) 9.24 -> 3.59; a latent
    ``[1, 512]`` (57) 0.89 -> 0.16 over 12 layers; while ``[64, 1]`` (8 rows
    a page at Mistral's 8 heads, 1 at a latent row) stays row-wise at 1.45 ms
    where pages take 2.70, as does a latent ``[1, 16]`` (8)."""
    return not quantized and \
        Q * KV >= ROWS_A_PAGE * pages_a_row(Q, block_size)


def _write_slots(block_tables, seen, q_len, Q, block_size, trash):
    """(page, slot in the page), each [S*Q, 1], of a dispatch's ``[S, Q]``
    token slots; a padded slot goes to slot 0 of the ``trash`` page."""
    pos = seen[:, None] + jnp.arange(Q)[None, :]              # [S, Q]
    valid = jnp.arange(Q)[None, :] < q_len[:, None]
    blk = jnp.take_along_axis(block_tables, pos // block_size, axis=1,
                              mode="clip")
    # every leading dim is indexed — (block, head, slot) per [Dh] row, values
    # [S*Q, KV, Dh] — so the scatter writes whole rows in the pool's own
    # layout. Leaving the head dim a slice between two indexed dims made the
    # chip's compiler re-lay the WHOLE pool out around the scatter.
    bi = jnp.where(valid, blk, trash).reshape(-1, 1)          # [S*Q, 1]
    si = jnp.where(valid, pos % block_size, 0).reshape(-1, 1)
    return bi, si


def _write_rows(pools, rows, block_tables, seen, q_len, block_size, trash):
    """The row-wise form: every pool [NB, KV, bs, W] of ``pools`` takes its
    [S, Q, KV, W] of ``rows`` as S x Q x KV updates of one [W] row each."""
    S, Q, KV = rows[0].shape[:3]
    bi, si = _write_slots(block_tables, seen, q_len, Q, block_size, trash)
    hi = jnp.arange(KV)[None, :]                              # [1, KV]
    return tuple(
        pool.at[bi, hi, si].set(x.reshape(S * Q, KV, -1).astype(pool.dtype))
        for pool, x in zip(pools, rows))


def _write_pages(pools, rows, block_tables, seen, q_len, block_size, trash):
    """The page-wise form: a row's Q new tokens fill consecutive slots of at
    most ``P = pages_a_row(Q, bs)`` pages its table names from ``seen // bs``
    on, so every pool [NB, KV, bs, W] of ``pools`` takes its [S, Q, KV, W] of
    ``rows`` as S x P updates of one whole page each: the new rows laid out
    as pages at offset ``seen % bs`` (a slice of the padded chunk at a MAJOR
    dimension, then one transpose of bs against KV), the slots outside
    ``[seen, seen + q_len)`` keeping what the page held (the P pages read,
    chosen by slot, written whole). A page none of whose slots is real is the
    ``trash`` page, which may hold any value. Every real slot takes the bytes
    the row-wise form gives it."""
    S, Q, KV = rows[0].shape[:3]
    bs, P = block_size, pages_a_row(Q, block_size)
    first, shift = seen // bs, seen % bs                      # [S]
    pos = first[:, None] * bs + jnp.arange(P * bs)[None, :]   # [S, P * bs]
    real = (pos >= seen[:, None]) & (pos < (seen + q_len)[:, None])
    real = real.reshape(S * P, bs)
    entries = jnp.take_along_axis(
        block_tables, first[:, None] + jnp.arange(P)[None, :], axis=1,
        mode="clip").reshape(S * P)
    pages = jnp.where(real.any(-1), entries, trash)           # [S * P]

    def as_pages(x):
        # slot j of the P pages holds the chunk's token j - shift
        x = jnp.pad(x, ((0, 0), (bs, P * bs - Q), (0, 0), (0, 0)))
        x = jax.vmap(lambda r, s: jax.lax.dynamic_slice_in_dim(
            r, bs - s, P * bs, 0))(x, shift)                  # [S, P*bs, KV, W]
        return x.reshape(S * P, bs, KV, -1).swapaxes(1, 2)    # [S*P, KV, bs, W]

    return tuple(
        pool.at[pages].set(jnp.where(real[:, None, :, None],
                                     as_pages(x).astype(pool.dtype),
                                     pool[pages]))
        for pool, x in zip(pools, rows))


def _write(pools, rows, block_tables, seen, q_len, block_size, trash):
    """``rows`` [S, Q, KV, W] each into ``pools`` [NB, KV, bs, W] each, in the
    form ``writes_pages`` picks for their shapes; the device scope
    ``paged_write``."""
    _, Q, KV, _ = rows[0].shape
    form = _write_pages if writes_pages(Q, KV, block_size) else _write_rows
    with jax.named_scope("paged_write"):
        return form(pools, rows, block_tables, seen, q_len, block_size, trash)


def _scatter_latent(pool, rows, block_tables, seen, q_len, block_size, trash):
    """Write [S, Q, W] new latent rows into the one-leaf pool [NB, 1, bs, W]
    via block tables: one whole row a token, padded slots to the ``trash``
    page (``_write``)."""
    return _write((pool,), (rows[:, :, None],), block_tables, seen, q_len,
                  block_size, trash)[0]


def _scatter_kv(k_pool, v_pool, k, v, block_tables, seen, q_len, block_size,
                trash):
    """Write [S, Q, KV, Dh] new KVs into the [NB, KV, bs, Dh] pool via block
    tables (``_write``).

    Padded token slots are routed to the ``trash`` page.
    Analog of the reference's linear_blocked_kv_copy kernel. Quantized pools
    (``(int8, scale)`` pairs) quantize on-write: each token's row quantizes
    per (token, kv head) over Dh, and the fp32 scale scatters into the side
    pool [NB, KV, 1, bs] under the same block/slot indices, row by row.
    """
    k_pool, k_scale = _pool_parts(k_pool)
    v_pool, v_scale = _pool_parts(v_pool)
    if k_scale is None:
        return _write((k_pool, v_pool), (k, v), block_tables, seen, q_len,
                      block_size, trash)
    S, Q = k.shape[:2]
    bi, si = _write_slots(block_tables, seen, q_len, Q, block_size, trash)
    hi = jnp.arange(k.shape[2])[None, :]                      # [1, KV]
    k, ks = _quantize_kv_rows(k)              # int8 [S,Q,KV,Dh], f32 [S,Q,KV]
    v, vs = _quantize_kv_rows(v)
    k_scale = k_scale.at[bi, hi, 0, si].set(ks.reshape(S * Q, -1))
    v_scale = v_scale.at[bi, hi, 0, si].set(vs.reshape(S * Q, -1))
    with jax.named_scope("paged_write"):
        k_pool, v_pool = _write_rows((k_pool, v_pool), (k, v), block_tables,
                                     seen, q_len, block_size, trash)
    return (k_pool, k_scale), (v_pool, v_scale)


# -- the read -----------------------------------------------------------------

def _paged_attention(q, k_pool, v_pool, block_tables, seen, block_size,
                     q_len, window=None, softmax_scale=None):
    """Grouped-query attention over per-sequence paged KV: the Pallas
    blocked-flash kernel (ops/pallas/paged_attention.py — O(seen) HBM reads)
    when Pallas is on and the shapes tile, the dense gather twin elsewhere.
    ``window``: Mistral-style sliding window. ``softmax_scale``: None is
    ``1/sqrt(Dh)``. q: [S,Q,H,Dh] -> [S,Q,H,Dh]."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    kp, ks = _pool_parts(k_pool)
    # the batch dims are left out of the warning: one a model, not one a shape
    if takes_kernel("paged_mha", pa.is_supported(q.shape, kp.shape),
                    f"q heads {tuple(q.shape[2:])} over pages "
                    f"{tuple(kp.shape[1:])} violate the kernel's tiling "
                    f"(need H%KV==0, Dh<=256, block_size%8==0; a walk of "
                    f"live pages for Dh%128==0, else a grid over the "
                    f"table's width), O(max_context) reads"):
        vp, vs = _pool_parts(v_pool)
        return pa.paged_mha(q, kp, vp, block_tables, seen, q_len,
                            k_scale=ks, v_scale=vs,
                            softmax_scale=softmax_scale, window=window,
                            interpret=pallas_interpret())
    return _paged_attention_dense(q, k_pool, v_pool, block_tables, seen,
                                  block_size, window=window,
                                  softmax_scale=softmax_scale)


def _paged_attention_dense(q, k_pool, v_pool, block_tables, seen, block_size,
                           window=None, softmax_scale=None, keep=None):
    """Pure-XLA reference path (gathers the full table; numerics twin of the
    Pallas kernel — including the fused-dequant int8 path, which it
    reproduces as gather-then-dequantize with broadcast scales). ``keep``
    [S, Q, MB * bs] bool: the keys a query may read beside the causal rule
    (``_sparse_attention``)."""
    k_pool, k_scale = _pool_parts(k_pool)
    v_pool, v_scale = _pool_parts(v_pool)
    S, Q, H, Dh = q.shape
    KV = k_pool.shape[1]
    rep = H // KV
    scale = 1.0 / (Dh ** 0.5) if softmax_scale is None else softmax_scale
    MB = block_tables.shape[1]

    def one_seq(q_s, bt_s, seen_s, keep_s=None):
        keys, vals = k_pool[bt_s], v_pool[bt_s]       # [MB, KV, bs, Dh]
        if k_scale is not None:
            # scale rows [MB, KV, 1, bs] -> per-token column [MB, KV, bs, 1]
            keys = keys.astype(jnp.float32) * \
                jnp.swapaxes(k_scale[bt_s], -1, -2)
            vals = vals.astype(jnp.float32) * \
                jnp.swapaxes(v_scale[bt_s], -1, -2)
        # [MB, KV, bs, Dh] -> token-major [MB*bs, KV, Dh]
        keys = (keys.transpose(0, 2, 1, 3)
                .reshape(MB * block_size, KV, Dh).astype(q_s.dtype))
        vals = (vals.transpose(0, 2, 1, 3)
                .reshape(MB * block_size, KV, Dh).astype(q_s.dtype))
        qg = q_s.reshape(Q, KV, rep, Dh)
        logits = jnp.einsum("qkrd,skd->krqs", qg, keys).astype(jnp.float32) * scale
        key_pos = jnp.arange(MB * block_size)[None, :]
        qry_pos = (seen_s + jnp.arange(Q))[:, None]
        visible = key_pos <= qry_pos
        if window:
            visible = visible & (key_pos > qry_pos - window)
        if keep_s is not None:
            visible = visible & keep_s
        logits = jnp.where(visible, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(q_s.dtype)
        return jnp.einsum("krqs,skd->qkrd", probs, vals).reshape(Q, H, Dh)

    if keep is not None:
        return jax.vmap(one_seq)(q, block_tables, seen, keep)
    return jax.vmap(one_seq)(q, block_tables, seen)


# -- the read of a page of one leaf (a latent row a token) ---------------------

def _latent_attention(q, pool, block_tables, seen, block_size, q_len,
                      value_dim, softmax_scale):
    """The absorbed read: every query head of q [S, Q, H, W] on the ONE
    latent row a token of ``pool`` [NB, 1, bs, W], scores by the row's whole
    width, values its first ``value_dim`` columns -> [S, Q, H, value_dim].
    The Pallas walk ``paged_mla`` (a page crosses HBM once) when Pallas is on
    and the shapes tile, the dense gather twin elsewhere."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    if takes_kernel("paged_mla",
                    pa.mla_is_supported(q.shape, pool.shape, value_dim),
                    f"q heads {tuple(q.shape[2:])} over latent pages "
                    f"{tuple(pool.shape[1:])} violate the kernel's tiling "
                    f"(need row width and value_dim%128==0, "
                    f"block_size%8==0), O(max_context) reads"):
        return pa.paged_mla(q, pool, block_tables, seen, q_len,
                            value_dim=value_dim, softmax_scale=softmax_scale,
                            interpret=pallas_interpret())
    return _latent_attention_dense(q, pool, block_tables, seen, block_size,
                                   value_dim, softmax_scale)


def _latent_attention_dense(q, pool, block_tables, seen, block_size,
                            value_dim, softmax_scale):
    """Pure-XLA twin of ``paged_mla`` (gathers the full table)."""
    S, Q, H, W = q.shape
    MB = block_tables.shape[1]

    def one_seq(q_s, bt_s, seen_s):
        rows = pool[bt_s][:, 0].reshape(MB * block_size, W).astype(q_s.dtype)
        logits = jnp.einsum("qhw,sw->hqs", q_s, rows).astype(jnp.float32) \
            * softmax_scale
        key_pos = jnp.arange(MB * block_size)[None, :]
        qry_pos = (seen_s + jnp.arange(Q))[:, None]
        logits = jnp.where(key_pos <= qry_pos, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(q_s.dtype)
        return jnp.einsum("hqs,sv->qhv", probs, rows[:, :value_dim])

    return jax.vmap(one_seq)(q, block_tables, seen)


def up_projects_in_walk(Q, kv_lora_rank, qk_nope_head_dim, v_head_dim):
    """THE rule of the latent read's form, from a dispatch's static shapes
    alone (``Q`` token slots a row and the model's widths ``r``, ``dn``,
    ``dv``), as ``writes_pages`` is the write's: True where the walk
    up-projects a trip's keys and values for the tile's head in VMEM
    (``_latent_attention_up``), False where it stays absorbed
    (``_latent_attention``).

    By count, a (query, key) pair of one head costs the absorbed walk ``2 r
    + dr`` multiply-adds (scores over the row, values over the latent) and
    the up-projecting walk ``dn + dr + dv``, plus the trip's up-projection
    ``r (dn + dv)`` a key shared by the ``T`` queries of the head's tile
    (``T = query_row_tile(Q)``: at most 512): the lesser is the second where
    ``T (2 r - dn - dv) > r (dn + dv)``, from 171 queries at the three latent
    families' widths (512, 128, 128). A decode row and a verify round's 8
    stay absorbed by three orders and by one.

    The chip's timings that set it (one TPU v5 lite, PR 58, the kernel alone,
    ms at a context of 2 k / 8 k / 16 k, absorbed -> up-projecting with the
    trips ``paged_attention._up_pages`` gives it; PERF.md section 6 has the
    table): 32 heads ``[1, 512]`` 0.62 / 2.11 / 4.11 -> 0.41 / 1.34 / 2.58,
    ``[1, 256]`` 0.32 / 1.07 / 2.07 -> 0.27 / 0.90 / 1.74, ``[1, 128]`` 0.21 /
    0.55 / 1.05 -> 0.30 / 0.85 / 1.54; 64 heads twice each. The chip puts the
    crossing where the count does, between 128 and 256 tokens, and ``[1,
    128]`` loses by half (a tile of four heads' queries would up-project the
    trip four times)."""
    from deepspeed_tpu.ops.pallas.paged_attention import query_row_tile
    r, up = kv_lora_rank, qk_nope_head_dim + v_head_dim
    return query_row_tile(Q) * (2 * r - up) > r * up


def _latent_attention_up(q, w_uk, w_uv, pool, block_tables, seen, block_size,
                         q_len, softmax_scale):
    """The read that up-projects in the walk: q [S, Q, H, dn + dr] as
    projected (position part rotated) on the ONE latent row a token of
    ``pool`` [NB, 1, bs, W] through ``w_uk`` [r, H, dn] and ``w_uv`` [r, H,
    dv] -> [S, Q, H, dv], the heads' values. ``paged_mla`` with ``up`` (a
    page crosses HBM once, nothing up-projected does) when Pallas is on and
    the shapes tile, the dense gather twin elsewhere."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    r, _, dn = w_uk.shape
    # the position part against the row's columns behind the latent, the
    # padding's among them
    q_row = jnp.pad(q, ((0, 0),) * 3 + ((0, dn + pool.shape[-1] - r - q.shape[-1]),))
    if takes_kernel("paged_mla",
                    pa.mla_is_supported(q_row.shape, pool.shape, r,
                                        up_dims=(dn, w_uv.shape[-1])),
                    f"q heads {tuple(q.shape[2:])} over latent pages "
                    f"{tuple(pool.shape[1:])} violate the up-projecting "
                    f"walk's tiling (need row width, latent, nope and value "
                    f"widths%128==0, chunk and block_size%8==0), "
                    f"O(max_context) reads"):
        return pa.paged_mla(q_row, pool, block_tables, seen, q_len,
                            value_dim=r, softmax_scale=softmax_scale,
                            up=(w_uk, w_uv), interpret=pallas_interpret())
    return _latent_attention_up_dense(q, w_uk, w_uv, pool, block_tables, seen,
                                      block_size, softmax_scale)


def _latent_attention_up_dense(q, w_uk, w_uv, pool, block_tables, seen,
                               block_size, softmax_scale):
    """Pure-XLA twin of ``paged_mla``'s ``up`` (gathers the full table, and
    up-projects it for every head)."""
    Q, MB = q.shape[1], block_tables.shape[1]
    r, _, dn = w_uk.shape
    dr = q.shape[-1] - dn

    def one_seq(q_s, bt_s, seen_s):
        rows = pool[bt_s][:, 0].reshape(MB * block_size, -1).astype(q_s.dtype)
        c, k_pe = rows[:, :r], rows[:, r:r + dr]
        k = jnp.einsum("sc,chd->shd", c, w_uk.astype(q_s.dtype))
        v = jnp.einsum("sc,chd->shd", c, w_uv.astype(q_s.dtype))
        logits = (jnp.einsum("qhd,shd->hqs", q_s[..., :dn], k,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("qhd,sd->hqs", q_s[..., dn:], k_pe,
                               preferred_element_type=jnp.float32)) \
            * softmax_scale
        key_pos = jnp.arange(MB * block_size)[None, :]
        qry_pos = (seen_s + jnp.arange(Q))[:, None]
        logits = jnp.where(key_pos <= qry_pos, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(q_s.dtype)
        return jnp.einsum("hqs,shv->qhv", probs, v)

    return jax.vmap(one_seq)(q, block_tables, seen)


# -- learned sparse attention: a K and V pair with an indexer's key ------------

def _scatter_index(pool, keys, block_tables, seen, q_len, block_size, trash):
    """Write [S, Q, Di] new index keys into the index pool [NB, 1, bs, W]
    (``W`` the key's width padded to whole lane tiles, zeros behind the key),
    one row a token under the page and slot its K and V take."""
    pad = pool.shape[-1] - keys.shape[-1]
    rows = jnp.pad(keys, ((0, 0), (0, 0), (0, pad))) if pad else keys
    return _scatter_latent(pool, rows, block_tables, seen, q_len, block_size,
                           trash)


def _index_scores(q_idx, w_idx, pool, block_tables, seen, block_size, q_len):
    """The index score of every query on every cached token of its row:
    ``I[s, t, n] = sum_j w_idx[s, t, j] ReLU(q_idx[s, t, j] . key[s, n])`` for
    q_idx [S, Q, Hi, Di], w_idx [S, Q, Hi] float32 and the index pool [NB, 1,
    bs, W] -> [S, Q, MB * bs] float32, ``-inf`` where token ``n`` lies behind
    query ``t`` (``n > seen + t``). Products in the pool's dtype with float32
    accumulation, the sum over heads in float32. The Pallas walk
    ``paged_index_scores`` (a live page crosses HBM once) when Pallas is on
    and the shapes tile, the dense gather twin elsewhere."""
    from deepspeed_tpu.ops.pallas import sparse_index as si
    if takes_kernel("paged_index_scores",
                    si.scores_is_supported(q_idx.shape, pool.shape),
                    f"indexer heads {tuple(q_idx.shape[2:])} over index pages "
                    f"{tuple(pool.shape[1:])} violate the kernel's tiling "
                    f"(need row width%128==0, block_size%8==0, a chunk of 1 "
                    f"or a multiple of 8), O(max_context) reads"):
        return si.paged_index_scores(q_idx, w_idx, pool, block_tables, seen,
                                     q_len, interpret=pallas_interpret())
    return _index_scores_dense(q_idx, w_idx, pool, block_tables, seen,
                               block_size)


def _index_scores_dense(q_idx, w_idx, pool, block_tables, seen, block_size):
    """Pure-XLA twin of ``paged_index_scores`` (gathers the full table)."""
    S, Q, Hi, Di = q_idx.shape
    MB = block_tables.shape[1]

    def one_seq(q_s, w_s, bt_s, seen_s):
        keys = pool[bt_s][:, 0].reshape(MB * block_size, -1)[:, :Di]
        dots = jnp.einsum("qhd,nd->qhn", q_s.astype(keys.dtype), keys,
                          preferred_element_type=jnp.float32)
        scores = jnp.sum(jax.nn.relu(dots) * w_s[:, :, None], axis=1)
        key_pos = jnp.arange(MB * block_size)[None, :]
        qry_pos = (seen_s + jnp.arange(Q))[:, None]
        return jnp.where(key_pos <= qry_pos, scores, -jnp.inf)

    return jax.vmap(one_seq)(q_idx, w_idx.astype(jnp.float32), block_tables,
                             seen)


def _select(scores, topk, seen):
    """The selection as a threshold: ``tau`` [S, Q] float32, the ``topk``-th
    largest of each query's ``scores`` [S, Q, N] (``-inf`` where the query
    sees fewer than ``topk`` tokens: it then reads them all), so that the
    tokens a query reads are those with ``scores >= tau``: exactly the
    ``topk`` of largest score unless scores tie at ``tau``. Never a sort: the
    Pallas kernel ``topk_threshold`` settles the threshold's 32 bits one at a
    time by counting in VMEM; the twin asks ``jax.lax.top_k``."""
    from deepspeed_tpu.ops.pallas import sparse_index as si
    if takes_kernel("topk_threshold", si.threshold_is_supported(scores.shape),
                    f"scores {tuple(scores.shape[1:])} violate the kernel's "
                    f"tiling (need a context%128==0)"):
        visible = seen[:, None] + jnp.arange(scores.shape[1])[None, :] + 1
        return si.topk_threshold(scores, visible.astype(jnp.int32), topk,
                                 interpret=pallas_interpret())
    return _select_dense(scores, topk)


def _select_dense(scores, topk):
    """Pure-XLA twin of ``topk_threshold``: the last of ``jax.lax.top_k``."""
    return jax.lax.top_k(scores, topk)[0][..., -1]


def _sparse_attention(q, k_pool, v_pool, scores, tau, block_tables, seen,
                      block_size, q_len):
    """Grouped-query attention of q [S, Q, H, Dh] over the cached tokens of
    its row whose index score is at or above the query's threshold (``scores``
    [S, Q, MB * bs], ``tau`` [S, Q]: one set a query token, shared by every
    head): the paged walk over every live page under the per-query mask
    ``scores >= tau`` (``paged_mha``'s ``select``), else the dense twin."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    if takes_kernel("paged_mha",
                    pa.is_supported(q.shape, k_pool.shape)
                    and pa.select_is_supported(q.shape, k_pool.shape),
                    f"q heads {tuple(q.shape[2:])} over pages "
                    f"{tuple(k_pool.shape[1:])} violate the masked walk's "
                    f"tiling (need Dh%128==0, a chunk of 1 or a multiple of "
                    f"8), O(max_context) reads"):
        return pa.paged_mha(q, k_pool, v_pool, block_tables, seen, q_len,
                            select=(scores, tau),
                            interpret=pallas_interpret())
    return _paged_attention_dense(q, k_pool, v_pool, block_tables, seen,
                                  block_size, keep=scores >= tau[..., None])


def dsa_attention(q, q_idx, w_idx, k_pool, v_pool, i_pool, block_tables, seen,
                  block_size, q_len, topk):
    """Learned sparse attention's read, after the write: q [S, Q, H, Dh] on
    the ``topk`` cached tokens of its row that the indexer's scores pick
    (``q_idx`` [S, Q, Hi, Di] and ``w_idx`` [S, Q, Hi] against the index
    keys of ``i_pool``). One rule by shape: a table that holds at most
    ``topk`` tokens, or a dispatch none of whose rows passes ``topk``
    (``seen + new <= topk``: the selection is the identity), takes
    ``_paged_attention`` and computes no score; any other dispatch scores,
    selects and reads sparsely, its short rows reading all they see."""
    def dense():
        with jax.named_scope("dsa_read"):
            return _paged_attention(q, k_pool, v_pool, block_tables, seen,
                                    block_size, q_len)

    def sparse():
        with jax.named_scope("dsa_index"):
            scores = _index_scores(q_idx, w_idx, i_pool, block_tables, seen,
                                   block_size, q_len)
        with jax.named_scope("dsa_select"):
            tau = _select(scores, topk, seen)
        with jax.named_scope("dsa_read"):
            return _sparse_attention(q, k_pool, v_pool, scores, tau,
                                     block_tables, seen, block_size, q_len)

    if block_tables.shape[1] * block_size <= topk:
        return dense()
    return jax.lax.cond(jnp.all(seen + q_len <= topk), dense, sparse)


# -- the logits gather --------------------------------------------------------

def token_at(x, idx):
    """``x`` [S, Q, D] at chunk position ``idx`` [S] of each row -> [S, D]."""
    return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]


def last_token(x, q_len):
    """logits_gather analog: ``x`` [S, Q, D] at the last real token of each
    row's chunk -> [S, D]. A row of no tokens (``q_len`` 0) reads position
    0."""
    return token_at(x, jnp.maximum(q_len - 1, 0))
