"""Pallas paged (blocked-flash) attention for the ragged inference engine.

Capability analog of the reference's blocked_flash kernel family
(``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash/``), designed for
the TPU's DMA engines rather than translated:

- the K and V pools stay in HBM; the block table, ``seen`` and ``q_len`` are
  **scalar-prefetched**, and the kernel walks each sequence's *live* pages
  (``ceil((seen + q_len) / bs)``, never the table's width): steps and HBM
  bytes are both O(live pages);
- a trip of the walk copies ``pages`` pages by ``make_async_copy`` from the
  indices in the table into one half of a double-buffered VMEM scratch while
  the other half is multiplied; the last trip of a grid step starts the first
  copies of the next step, so the pipe stays full across sequences. A page is
  contiguous over its KV heads, so one copy a page serves every head of the
  step. Table entries past the live count are never dereferenced;
- the online softmax (m, l, acc in VMEM scratch) updates once a trip on a
  lane-dense ``[rows, pages * bs]`` score tile; slots past the live count in
  the last trip hold finite leftovers (the buffers start zeroed) that the
  position mask ``kpos <= seen + qi`` zeroes out of the sums;
- the grid is ``(seqs, kv_heads // heads)``. ``heads`` (KV heads a step),
  the query-row tile and ``pages`` follow from the shapes so that the
  resident query state, the page buffers and the score tile fit VMEM
  (``_walk_plan``): a ``[D, 1]`` decode dispatch (and a verify round's
  ``[D, 8]``) takes all its KV heads in one step, a ``[1, 512]`` chunk one
  head a step in row tiles of 512.

A pool whose rows do not fill a lane tile (``Dh`` of 64, 80, 96) cannot be
copied by hand: Mosaic slices such an HBM array only for the grid's own
pipeline. It keeps ``_grid_kernel``: a grid ``(seqs, kv_heads, max_blocks)``
whose K/V index maps read the block table, blocks past the live length
clamped to the last live one so that they are not fetched again. Bytes
O(live pages), steps O(table width), one ``[rows, bs]`` update a step.

Layouts: q [S, Q, H, Dh] (Q = new-token budget, 1 for pure decode);
k/v pools [NB, KV, bs, Dh]; block_tables [S, MB]; seen [S]. Output matches q.
GQA runs natively: each KV head attends its whole ``rep = H // KV``
query-head group (``rep * Q`` rows) against the trip's keys.

Latent pages (``paged_mla``, the kernel's name in a trace): a group of one
leaf keeps ONE row a token and layer (``[NB, 1, bs, W]``: the normalised
latent's ``value_dim`` columns, then the rotated position part), read for the
scores by its whole width and for the values by its first ``value_dim``
columns; every query head sits on that one row (``rep = H``). It is a mode of
the same walk: a page crosses HBM once into ONE buffer, the values are a
lane-aligned slice of the keys in VMEM, and the second grid axis steps over
tiles of the ``H * Q`` query rows (a ``[1, 512]`` chunk has 16,384 of them,
more than VMEM holds beside their accumulator), each tile walking the row's
live pages anew. ``W`` is any multiple of 128.

The latent walk that UP-PROJECTS (``paged_mla(up=(w_uk, w_uv))``, in a trace
``paged_mla_up``; a prompt chunk's read where ``paged_layer.
up_projects_in_walk`` says so): a further sub-mode of the latent mode, the
same copies, buffers, ``live_pages`` and mask. A tile is ONE head's queries
(all of them up to 512, tiles of them past that), q comes as projected
(``dn`` columns, then the position part in the columns the row keeps behind
its latent), and the step holds that head's ``W_UK_h`` and ``W_UV_h`` (blocks
of ``dn`` and ``dv`` columns of ``w_uk`` / ``w_uv`` seen ``[r, H * d]``: the
pipeline fetches a head's pair once, 256 KB). A trip's buffer ``c [keys, r]``
becomes ``k_h = c W_UK_h`` and ``v_h = c W_UV_h`` in VMEM, rounded to the
pool's dtype where the published forward rounds ``kv_b_proj``'s output, the
scores are ``q . [k_h | row's columns behind the latent]`` and the values
``p @ v_h`` into a ``[rows, dv]`` accumulator: a trip of 512 keys against 512
queries is 335 MFLOP where the absorbed trip is 604, nothing up-projected
touches HBM, and a page crosses HBM as before, once a head. Its trips are
wider than the plan's (``_up_pages``: a score tile of 2 MB, 16 pages of 64 at
512 rows; what a trip costs beside its keys is shared by more of them), and
a row's LAST trip takes a half-size update where no page of its second half
is live, so that at most half a trip is work on masked keys.

A selection (``select=(scores, tau)``: learned sparse attention). A query
reads only the keys whose index score is at or above its threshold
(``scores`` [S, Q, MB * bs] float32, ``tau`` [S, Q]; one set a query token,
shared by every head). It is a mode of the same walk over every live page:
the trip's ``[Q, pages * bs]`` slab of scores rides the trip's buffers (a
copy by hand beside the pages for a chunk; a row's whole scores through the
pipeline for a ``[D, 1]`` dispatch), becomes a bias of 0 or ``NEG_INF`` once a
trip and is added to every head's score tile. ``pages`` is cut to a divisor
of the table's width, so that no slab overruns the scores.

int8 KV (``k_scale``/``v_scale`` given): pools are int8 with per-token fp32
scales in side pools [NB, KV, 1, bs]. The pages take the same walk, so their
HBM reads stay int8-sized and the dequant fuses into the flash loop in VMEM.
The scales cannot: Mosaic refuses a DMA out of an HBM array narrower than a
lane tile (``bs`` < 128), so for the walk XLA gathers each sequence's scale
rows through its table, clamped to the live pages, into lane-dense
``[trips, pages * bs]`` rows that the pipeline hands the kernel a grid step
at a time — 1/32 of a page's bytes for each slot of the table (the grid
kernel's pipeline fetches a page's ``[1, bs]`` row beside the page). No
transposes: ``k``'s per-token
scale folds into the score *columns* after the QK dot (``sij * ks``), ``v``'s
folds into ``p``'s columns before the PV dot (``(p * vs) @ v``) — both are
lane-broadcast multiplies.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9

LANES = 128

# VMEM the walk plans for, of the 128 MiB a v5e core has: the query state
# resident over a grid step, both halves of the page buffers, one score tile
_RESIDENT_BYTES = 6 << 20
_STREAM_BYTES = 8 << 20
_SCORE_BYTES = 1 << 20
_VMEM_LIMIT_BYTES = 48 << 20
_MAX_ROW_TILE = 512
# the score tile of a trip of the walk that up-projects: see ``_up_pages``
_UP_SCORE_BYTES = 2 << 20


def query_row_tile(rows):
    """The query rows a tile of the walk holds, of ``rows`` that belong
    together: all of them up to ``_MAX_ROW_TILE``, else the largest tile of
    whole sublane tiles that divides them."""
    if rows <= _MAX_ROW_TILE:
        return rows
    return max((t for t in range(8, _MAX_ROW_TILE + 1, 8) if rows % t == 0),
               default=rows)


def _walk_plan(rows, kv, bs, dh, q_itemsize, pool_itemsize, table_width):
    """(KV heads a grid step, query rows a tile, pages a trip) for one call,
    from what the kernel can see of it."""
    # a head's q and out blocks (double-buffered by the pipeline), acc, m, l
    resident = rows * (4 * dh * q_itemsize + 4 * dh + 2 * 4 * LANES)
    heads = max(g for g in range(1, kv + 1)
                if kv % g == 0 and (g == 1 or g * resident <= _RESIDENT_BYTES))
    row_tile = query_row_tile(rows)
    pages = min(_STREAM_BYTES // (4 * heads * bs * dh * pool_itemsize),
                _SCORE_BYTES // (4 * row_tile * bs), table_width)
    lane_pages = max(LANES // bs, 1)          # pages a full lane tile of keys
    if pages > lane_pages:
        pages -= pages % lane_pages
    return heads, row_tile, max(pages, 1)


def _up_pages(row_tile, bs, row_bytes, table_width):
    """Pages a trip of the walk that up-projects: an even count (a row's last
    trip may take half), as many as a score tile of ``_UP_SCORE_BYTES`` and
    both halves of the ONE pool's buffer in ``_STREAM_BYTES`` hold. Wider
    than ``_walk_plan``'s, by what the chip showed of this form (one TPU v5
    lite, PR 58, ``[1, 512]`` at 32 heads and 8 k): 4 / 8 / 16 / 32 pages a
    trip 2.98 / 1.88 / 1.33 / 1.25 ms, a trip of P pages ~2.15 us + 0.19 us x
    P, so that what a trip costs beside its keys is shared by more of them
    (the mask's form and a mask left out moved nothing, the up-projection
    left out 0.34 ms; the ABSORBED walk gains 4 % from 16 pages and is left
    the plan's). With the half-size last trip 16 pages read 0.52 / 1.03 /
    1.34 / 2.58 ms at 2.3 k / 6 k / 8 k / 16 k where the absorbed walk reads
    0.74 / 1.61 / 2.12 / 4.10."""
    pages = min(_UP_SCORE_BYTES // (4 * row_tile * bs),
                _STREAM_BYTES // (2 * bs * row_bytes), table_width)
    return max(pages - pages % 2, 2)


def _flash_update(q, k, v, ks, vs, m_ref, l_ref, acc_ref, *, key0, row0,
                  seen_s, q_tokens, scale, window, bias=None):
    """One online-softmax update of the rows ``[row0, row0 + len(q))`` of a
    KV head's query group against the keys ``[key0, key0 + len(k))``.
    ``ks`` / ``vs``: the keys' ``[1, len(k)]`` scale rows for int8 pages.
    ``bias``: 0 or ``NEG_INF`` a (row, key), a selection's mask."""
    if ks is not None:
        # int8 page tiles dequantize HERE, in VMEM — fp KV never exists in
        # HBM. The QK dot runs on the raw int8 values (widened to the q
        # dtype; +-127 is exact in bf16) and each key's scale folds into its
        # score column afterwards.
        k = k.astype(q.dtype)
    sij = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32) * scale
    if ks is not None:
        sij = sij * ks
    # causal over the ragged sequence: key pos <= seen + qi
    kpos = key0 + jax.lax.broadcasted_iota(jnp.int32, sij.shape, 1)
    qi = (row0 + jax.lax.broadcasted_iota(jnp.int32, sij.shape, 0)) % q_tokens
    visible = kpos <= seen_s + qi
    if window is not None:  # Mistral-style sliding window
        visible = jnp.logical_and(visible, kpos > seen_s + qi - window)
    sij = jnp.where(visible, sij, NEG_INF)
    if bias is not None:
        sij = sij + bias

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.maximum(m_prev, jnp.max(sij, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(sij - m_cur)
    l_cur = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)
    if vs is not None:
        # per-token v scale folds into p's columns before the PV dot:
        # (p * vs) @ v_int == p @ (v_int * vs^T) without the transpose
        pv = jax.lax.dot_general(p * vs, v.astype(jnp.float32),
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    else:
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv


def _finish(l_ref, acc_ref, dtype):
    l = l_ref[...][..., :1]
    return (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(dtype)


def _walk_kernel(bt_ref, seen_ref, qlen_ref, *refs, bs, pages, heads,
                 row_tile, latent=None, select=False, **mask):
    sel_hbm = sel_buf = None
    up = ()
    if select:
        # ``sc_ref``: the scores, a row's whole [trips, keys] in VMEM for a
        # dispatch of one token a row, else in HBM with slabs copied by hand
        (q_ref, k_hbm, v_hbm, sc_ref, tau_ref, o_ref, k_buf, v_buf, sems,
         slot_ref, m_scr, l_scr, acc_scr, *slab) = refs
        scales = []
        sources = ((k_hbm, k_buf), (v_hbm, v_buf))
        if slab:
            sel_hbm, (sel_buf, sel_sems) = sc_ref, slab
    elif latent is None:
        # ``scales``: a sequence's (ks, vs) rows when the pages are int8
        (q_ref, k_hbm, v_hbm, *scales, o_ref, k_buf, v_buf, sems, slot_ref,
         m_scr, l_scr, acc_scr) = refs
        sources = ((k_hbm, k_buf), (v_hbm, v_buf))
    else:
        # ``latent``: the row's value columns. One row a token in ONE pool;
        # the second grid axis is a tile of the query rows. ``up``: the
        # tile's head's ``(W_UK, W_UV)`` where the walk up-projects
        (q_ref, *up, k_hbm, o_ref, k_buf, sems, slot_ref, m_scr, l_scr,
         acc_scr) = refs
        sources = ((k_hbm, k_buf),)
    s, hg = pl.program_id(0), pl.program_id(1)
    n_hg = pl.num_programs(1)
    step = s * n_hg + hg
    rows, dh = q_ref.shape[2], q_ref.shape[3]
    keys = pages * bs                             # key positions a trip

    def live_pages(seq):
        # a padded row (seen = q_len = 0) still reads one page, the trash block
        return jnp.maximum(pl.cdiv(seen_ref[seq] + qlen_ref[seq], bs), 1)

    def each_copy(seq, head_group, trip, slot, act):
        """``act`` on the copy of every LIVE page of one trip into ``slot``:
        ``start`` and ``wait`` see the same pages, and a table entry past the
        live count is never read."""
        first = trip * pages

        def one(p, _):
            page = bt_ref[seq, first + p]
            # a latent page has one "head", whatever row tile the step is at
            head0 = head_group * heads if latent is None else 0
            for t, (hbm, buf) in enumerate(sources):
                act(pltpu.make_async_copy(
                    hbm.at[page, pl.ds(head0, heads)],
                    buf.at[slot, p], sems.at[slot, t]))
            return 0

        jax.lax.fori_loop(0, jnp.minimum(live_pages(seq) - first, pages),
                          one, 0)
        if sel_hbm is not None:
            # the trip's slab of scores: [Q, keys] at the trip's first key
            act(pltpu.make_async_copy(
                sel_hbm.at[seq, :, pl.ds(pl.multiple_of(first * bs, keys), keys)],
                sel_buf.at[slot], sel_sems.at[slot]))

    start = lambda copy: copy.start()
    wait = lambda copy: copy.wait()

    @pl.when(step == 0)
    def _first():
        # leftovers in a slot that no copy of a trip filled are multiplied by
        # a masked (zero) weight: they have to be finite from the start
        for _, buf in sources:
            buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        each_copy(0, 0, 0, 0, start)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    n_trips = pl.cdiv(live_pages(s), pages)
    first_slot = slot_ref[0]

    def walk(trip, _):
        slot = jax.lax.rem(first_slot + trip, 2)

        # the next trip of this step, else the first of the next step: the
        # pipe stays full across sequences
        more = trip + 1 < n_trips
        wraps = jnp.logical_and(jnp.logical_not(more), hg + 1 == n_hg)

        @pl.when(jnp.logical_or(more, step + 1 < pl.num_programs(0) * n_hg))
        def _prefetch():
            each_copy(jnp.where(wraps, s + 1, s),
                      jnp.where(more, hg, jnp.where(wraps, 0, hg + 1)),
                      jnp.where(more, trip + 1, 0), 1 - slot, start)

        each_copy(s, hg, trip, slot, wait)

        if latent is not None and not up:
            k = k_buf[slot, :, 0].reshape(keys, k_buf.shape[-1])
            _flash_update(q_ref[0, 0], k, k[:, :latent], None, None,
                          m_scr.at[0], l_scr.at[0], acc_scr.at[0],
                          key0=trip * keys, row0=hg * row_tile,
                          seen_s=seen_ref[s], **mask)
            return 0

        if up:
            def up_project(n):
                # the update against the trip's first ``n`` pages: their keys
                # and values of the tile's ONE head, rounded where
                # ``kv_b_proj``'s output is; the row's other columns (the
                # shared position part) stay the keys' last
                k = k_buf[slot, :n, 0].reshape(n * bs, k_buf.shape[-1])
                k_h, v = (jnp.dot(k[:, :latent], w[...],
                                  preferred_element_type=jnp.float32)
                          .astype(k.dtype) for w in up)
                _flash_update(q_ref[0, 0],
                              jnp.concatenate([k_h, k[:, latent:]], axis=1),
                              v, None, None,
                              m_scr.at[0], l_scr.at[0], acc_scr.at[0],
                              key0=trip * keys, row0=hg * row_tile,
                              seen_s=seen_ref[s], **mask)

            # a row's last trip is on average half live: it takes the
            # half-size update where no page of its second half is
            left = live_pages(s) - trip * pages
            pl.when(left > pages // 2)(functools.partial(up_project, pages))
            pl.when(left <= pages // 2)(
                functools.partial(up_project, pages // 2))
            return 0

        bias = None
        if select:
            q_tokens = mask["q_tokens"]
            sel = sc_ref[0, pl.ds(trip, 1)] if sel_hbm is None \
                else sel_buf[slot]                       # [q_tokens, keys]
            bias = jnp.where(sel >= tau_ref[0], 0.0, NEG_INF)
            if q_tokens > 1 and row_tile > q_tokens:
                # a tile's rows are whole heads' queries: row % q_tokens
                bias = jnp.concatenate([bias] * (row_tile // q_tokens), 0)

        def head(h, _):
            k = k_buf[slot, :, h].reshape(keys, dh)
            v = v_buf[slot, :, h].reshape(keys, dh)
            ks, vs = (ref[0, h, pl.ds(trip, 1)] for ref in scales) \
                if scales else (None, None)
            for row0 in range(0, rows, row_tile):
                tile = pl.ds(row0, row_tile)
                more = {}
                if bias is not None:
                    # a tile inside one head's queries takes their rows
                    first = row0 % bias.shape[0]
                    more["bias"] = bias[first:first + row_tile]
                _flash_update(q_ref[0, h, tile], k, v, ks, vs,
                              m_scr.at[h, tile], l_scr.at[h, tile],
                              acc_scr.at[h, tile], key0=trip * keys,
                              row0=row0, seen_s=seen_ref[s], **mask, **more)
            return 0

        # traced once, laid out ``heads`` times: the heads' multiplications
        # and softmax updates are independent and overlap
        jax.lax.fori_loop(0, heads, head, 0, unroll=True)
        return 0

    jax.lax.fori_loop(0, n_trips, walk, 0)
    slot_ref[0] = jax.lax.rem(first_slot + n_trips, 2)
    o_ref[0] = _finish(l_scr, acc_scr, o_ref.dtype)


def _grid_kernel(bt_ref, seen_ref, qlen_ref, jcap_ref, *refs, bs, nb_grid,
                 **mask):
    q_ref, k_ref, v_ref, *scales, o_ref, m_scr, l_scr, acc_scr = refs
    s, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block j holds key positions [j*bs, (j+1)*bs); run while any are live
    @pl.when(j * bs < seen_ref[s] + qlen_ref[s])
    def _body():
        ks, vs = (ref[0, 0] for ref in scales) if scales else (None, None)
        _flash_update(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], ks, vs,
                      m_scr, l_scr, acc_scr, key0=j * bs, row0=0,
                      seen_s=seen_ref[s], **mask)

    @pl.when(j == nb_grid - 1)
    def _finish_():
        o_ref[0, 0] = _finish(l_scr, acc_scr, o_ref.dtype)


def paged_mha(q, k_pool, v_pool, block_tables, seen, q_len, *,
              k_scale=None, v_scale=None, softmax_scale=None, window=None,
              select=None, interpret=False):
    """Blocked-flash attention over paged KV. See module docstring for shapes.
    ``select``: ``(scores [S, Q, MB * bs] float32, tau [S, Q])``, a query
    reading only the keys with ``scores >= tau`` (fp pages, no window).

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

    def call(q_, kp_, vp_, bt_, sn_, ql_, *more):
        ks_, vs_ = more if quantized else (None, None)
        return _paged_mha_local(q_, kp_, vp_, bt_, sn_, ql_,
                                k_scale=ks_, v_scale=vs_,
                                softmax_scale=softmax_scale, window=window,
                                interpret=interpret,
                                **({"select": more} if select else {}))

    def accept(shard_shapes):
        (_, _, h, _), (_, kv, _, _) = shard_shapes[0], shard_shapes[1]
        return kv >= 1 and h % kv == 0

    inputs = [q, k_pool, v_pool, block_tables, seen, q_len]
    roles = [("data", None, "head", None), (None, "head", None, None),
             (None, "head", None, None), ("data", None), ("data",), ("data",)]
    if quantized:
        inputs += [k_scale, v_scale]
        roles += [(None, "head", None, None), (None, "head", None, None)]
    if select:
        assert not quantized and not window, \
            "a selection reads fp pages and has no window"
        inputs += list(select)
        roles += [("data", None, None), ("data", None)]
    return sharded_kernel_call(
        call, inputs, roles,
        ("data", None, "head", None), accept=accept, name="paged_mha",
        block_config=block_config)


def _paged_mha_local(q, k_pool, v_pool, block_tables, seen, q_len, *,
                     k_scale=None, v_scale=None, softmax_scale=None,
                     window=None, select=None, interpret=False):
    S, Q, H, Dh = q.shape
    KV, bs = k_pool.shape[1:3]
    rep = H // KV
    rows = rep * Q
    # [S, Q, H, Dh] -> [S, KV, rep*Q, Dh]: rows grouped by kv head
    qt = q.reshape(S, Q, KV, rep, Dh).transpose(0, 2, 3, 1, 4) \
         .reshape(S, KV, rows, Dh)
    mask = dict(q_tokens=Q, window=int(window) if window else None,
                scale=softmax_scale if softmax_scale is not None
                else Dh ** -0.5)
    # Mosaic copies by hand only out of arrays whose rows fill a lane tile
    call = _walk_call if Dh % LANES == 0 else _grid_call
    more = {"select": select} if select else {}
    with jax.named_scope("paged_attention"):
        out = call(qt, k_pool, v_pool, block_tables.astype(jnp.int32),
                   seen.astype(jnp.int32), q_len.astype(jnp.int32),
                   k_scale, v_scale, mask, interpret, **more)
    return out.reshape(S, KV, rep, Q, Dh).transpose(0, 3, 1, 2, 4) \
              .reshape(S, Q, H, Dh)


def _walk_call(qt, k_pool, v_pool, block_tables, seen, q_len, k_scale,
               v_scale, mask, interpret, select=None):
    S, KV, rows, Dh = qt.shape
    bs = k_pool.shape[2]
    heads, row_tile, pages = _walk_plan(
        rows, KV, bs, Dh, qt.dtype.itemsize, k_pool.dtype.itemsize,
        block_tables.shape[1])
    if select:
        # a trip's slab of scores must lie inside the table's width, and a
        # tile's rows be whole heads' queries or a part of one head's
        Q = mask["q_tokens"]
        pages = max(p for p in range(1, pages + 1)
                    if block_tables.shape[1] % p == 0)
        row_tile = max(t for t in range(8 if Q > 1 else 1, row_tile + 1)
                       if rows % t == 0 and (t % Q == 0 or Q % t == 0))
    q_spec = pl.BlockSpec((1, heads, rows, Dh),
                          lambda s, h, bt, sn, ql: (s, h, 0, 0),
                          memory_space=pltpu.VMEM)
    in_specs = [q_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
    inputs = [qt, k_pool, v_pool]
    if k_scale is not None:
        # a sequence's scale rows, a trip a row: [S, KV, trips, pages * bs].
        # Slots past the live count repeat the last live page's (finite)
        trips = pl.cdiv(block_tables.shape[1], pages)
        live = jnp.maximum(pl.cdiv(seen + q_len, bs), 1)
        slots = jnp.minimum(jnp.arange(trips * pages), live[:, None] - 1)
        walked = jnp.take_along_axis(block_tables, slots, axis=1)
        rows_of = lambda pool: pool[walked][:, :, :, 0].transpose(0, 2, 1, 3) \
            .reshape(S, KV, trips, pages * bs)
        inputs += [rows_of(k_scale), rows_of(v_scale)]
        in_specs += [pl.BlockSpec((1, heads, trips, pages * bs),
                                  lambda s, h, bt, sn, ql: (s, h, 0, 0),
                                  memory_space=pltpu.VMEM)] * 2

    slab = []
    if select:
        scores, tau = select
        Q, keys = mask["q_tokens"], pages * bs
        whole = lambda *block: pl.BlockSpec(
            block, lambda s, h, bt, sn, ql: (s,) + (0,) * (len(block) - 1),
            memory_space=pltpu.VMEM)
        if Q == 1:
            # a row's whole scores, a trip a row
            inputs.append(scores.reshape(S, -1, keys))
            in_specs.append(whole(1, scores.shape[-1] // keys, keys))
        else:
            inputs.append(scores)
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            slab = [pltpu.VMEM((2, Q, keys), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,))]
        inputs.append(tau.astype(jnp.float32)[..., None])
        in_specs.append(whole(1, Q, 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, KV // heads),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            # a half of a buffer holds a trip: [pages, heads, bs, Dh]
            pltpu.VMEM((2, pages, heads, bs, Dh), k_pool.dtype),
            pltpu.VMEM((2, pages, heads, bs, Dh), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),    # the half the next step starts in
            pltpu.VMEM((heads, rows, LANES), jnp.float32),
            pltpu.VMEM((heads, rows, LANES), jnp.float32),
            pltpu.VMEM((heads, rows, Dh), jnp.float32),
        ] + slab,
    )
    kernel = functools.partial(_walk_kernel, bs=bs, pages=pages, heads=heads,
                               row_tile=row_tile, **mask,
                               **({"select": True} if select else {}))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="paged_attention",
        interpret=interpret,
    )(block_tables, seen, q_len, *inputs)


def _grid_call(qt, k_pool, v_pool, block_tables, seen, q_len, k_scale,
               v_scale, mask, interpret):
    S, KV, rows, Dh = qt.shape
    bs = k_pool.shape[2]
    MB = block_tables.shape[1]
    # clamp dead blocks to the last live one -> identical index -> no re-fetch
    jcap = jnp.maximum(pl.cdiv(seen + q_len, bs), 1) - 1          # [S]

    def kv_index(s, h, j, bt, seen_ref, qlen_ref, jcap_ref):
        return (bt[s, jnp.minimum(j, jcap_ref[s])], h, 0, 0)

    q_spec = pl.BlockSpec((1, 1, rows, Dh),
                          lambda s, h, j, bt, sn, ql, jc: (s, h, 0, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, 1, bs, Dh), kv_index, memory_space=pltpu.VMEM)
    in_specs = [q_spec, kv_spec, kv_spec]
    inputs = [qt, k_pool, v_pool]
    if k_scale is not None:
        # scale pools [NB, KV, 1, bs]: the [1, bs] tile rides the same
        # block-table index map as its page, one lane row per grid step
        in_specs += [pl.BlockSpec((1, 1, 1, bs), kv_index,
                                  memory_space=pltpu.VMEM)] * 2
        inputs += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S, KV, MB),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, Dh), jnp.float32),
        ],
    )
    kernel = functools.partial(_grid_kernel, bs=bs, nb_grid=MB, **mask)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        name="paged_attention",
        interpret=interpret,
    )(block_tables, seen, q_len, jcap, *inputs)


def paged_mla(q, pool, block_tables, seen, q_len, *, value_dim,
              softmax_scale, up=None, interpret=False):
    """Attention of every query head over ONE latent row a token.

    ABSORBED (``up`` None). q [S, Q, H, W]: a head's query in the row's own
    columns (the latent part absorbed through ``W_UK``, then the rotated
    position part, zeros in any padding); ``pool`` [NB, 1, bs, W]: the one
    pool of a group of one leaf. Returns [S, Q, H, value_dim]: ``softmax(q .
    row) @ row[:value_dim]``, for the caller to take through ``W_UV``.

    UP-PROJECTED IN THE WALK (``up=(w_uk [value_dim, H, dn], w_uv [value_dim,
    H, dv])``). q [S, Q, H, dn + W - value_dim]: a head's query as projected
    (its ``dn`` columns against ``k_h``, then the position part against the
    row's columns behind the latent, zeros in any padding). A grid step holds
    ONE head's queries (tiles of them past ``_MAX_ROW_TILE``) and that
    head's ``W_UK_h`` and ``W_UV_h``; a trip's page buffer becomes ``k_h =
    c W_UK_h`` and ``v_h = c W_UV_h`` in VMEM, rounded to the pool's dtype
    where ``kv_b_proj``'s output is, and nothing up-projected touches HBM.
    Returns [S, Q, H, dv]: the head's values."""
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.registry import sharded_kernel_call

    # a mode of the paged walk: the tuning table and the dispatch counters
    # know it as ``paged_mha``, the trace as ``paged_mla``
    block_config = registry.resolve_block_config(
        "paged_mha", {"bs": pool.shape[2], "dh": q.shape[-1]}, q.dtype)

    def call(q_, bt_, sn_, ql_, pool_, *up_):
        return _paged_mla_local(q_, pool_, bt_, sn_, ql_,
                                value_dim=value_dim,
                                softmax_scale=softmax_scale,
                                up=up_ or None, interpret=interpret)

    # sequences shard over the data axes; the one latent row is every
    # head's, so the pool (and a head's up-projections) stays whole on each
    # shard
    up = tuple(up or ())
    return sharded_kernel_call(
        call, [q, block_tables, seen, q_len, pool, *up],
        [("data", None, None, None), ("data", None), ("data",), ("data",),
         (None, None, None, None)] + [(None, None, None)] * len(up),
        ("data", None, None, None), name="paged_mha",
        block_config=block_config)


def _paged_mla_local(q, pool, block_tables, seen, q_len, *, value_dim,
                     softmax_scale, up=None, interpret=False):
    S, Q, H, W = q.shape
    bs, width = pool.shape[2:]
    rows = H * Q
    # [S, Q, H, W] -> [S, 1, H*Q, W]: a head's queries together, so that a
    # row's chunk position is ``row % Q``
    qt = q.transpose(0, 2, 1, 3).reshape(S, 1, rows, W)
    if up:
        # a tile lies inside ONE head's queries, and brings that head's
        # columns of ``w [r, H, d]`` seen ``[r, H * d]``
        row_tile, out_dim = query_row_tile(Q), up[1].shape[-1]
        pages = _up_pages(row_tile, bs, width * pool.dtype.itemsize,
                          block_tables.shape[1])
        up = [w.reshape(w.shape[0], -1) for w in up]
        up_specs = [pl.BlockSpec(
            (w.shape[0], w.shape[1] // H),
            lambda s, r, bt, sn, ql: (0, r // (Q // row_tile)),
            memory_space=pltpu.VMEM) for w in up]
    else:
        _, row_tile, pages = _walk_plan(
            rows, 1, bs, width, qt.dtype.itemsize, pool.dtype.itemsize,
            block_tables.shape[1])
        out_dim, up, up_specs = value_dim, [], []
    tile = lambda width: pl.BlockSpec(
        (1, 1, row_tile, width), lambda s, r, bt, sn, ql: (s, 0, r, 0),
        memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, rows // row_tile),
        in_specs=[tile(W), *up_specs, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile(out_dim),
        scratch_shapes=[
            pltpu.VMEM((2, pages, 1, bs, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2, 1)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((1, row_tile, LANES), jnp.float32),
            pltpu.VMEM((1, row_tile, LANES), jnp.float32),
            pltpu.VMEM((1, row_tile, out_dim), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _walk_kernel, bs=bs, pages=pages, heads=1, row_tile=row_tile,
        latent=value_dim, q_tokens=Q, window=None,
        scale=softmax_scale)
    with jax.named_scope("paged_mla"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((S, 1, rows, out_dim), qt.dtype),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT_BYTES),
            name="paged_mla_up" if up else "paged_mla",
            interpret=interpret,
        )(block_tables.astype(jnp.int32), seen.astype(jnp.int32),
          q_len.astype(jnp.int32), qt, *up, pool)
    return out.reshape(S, H, Q, out_dim).transpose(0, 2, 1, 3)


def mla_is_supported(q_shape, pool_shape, value_dim, up_dims=None):
    """The latent walk copies pages by hand: the pool's rows fill lane
    tiles, the values are a lane-aligned run of their first columns, and
    more than ``_MAX_ROW_TILE`` query rows divide into tiles of 8s.
    ``up_dims``: ``(dn, dv)`` of the walk that up-projects, whose q is ``dn``
    columns and the row's columns behind the latent: both whole lane tiles,
    and a head's queries whole sublane tiles."""
    S, Q, H, W = q_shape
    rows = H * Q
    NB, heads, bs, width = pool_shape
    if up_dims is not None:
        dn, dv = up_dims
        return (W == dn + width - value_dim and Q % 8 == 0
                and dn % LANES == 0 and dv % LANES == 0
                and mla_is_supported((S, Q, 1, width), pool_shape, value_dim))
    return (W == width and width % LANES == 0 and heads == 1
            and value_dim % LANES == 0 and value_dim <= width
            and bs % 8 == 0
            and (rows <= _MAX_ROW_TILE or rows % 8 == 0))


def select_is_supported(q_shape, pool_shape):
    """The walk under a selection copies pages and slabs of scores by hand:
    rows that fill lane tiles, and a chunk whose score slab has whole
    sublane tiles (or one token a row)."""
    S, Q, H, Dh = q_shape
    return Dh % LANES == 0 and (Q == 1 or Q % 8 == 0)


def is_supported(q_shape, pool_shape):
    S, Q, H, Dh = q_shape
    NB, KV, bs, _ = pool_shape
    return H % KV == 0 and Dh <= 256 and bs % 8 == 0
