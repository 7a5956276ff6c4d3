"""Pallas TPU flash attention (fwd + bwd), the framework's core fast kernel.

Capability analog of the reference's fused attention kernels
(``csrc/transformer/inference/csrc/softmax.cu`` and the blocked_flash family
under ``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash/``), designed
TPU-first rather than translated: a 4D grid ``(batch, head, q_block, k_block)``
with the k dimension innermost so Mosaic double-buffers K/V block DMAs while
the MXU works, online-softmax state (running max / sum / accumulator) carried
in VMEM scratch across the k iterations, and causal blocks above the diagonal
skipped entirely.

Features: causal masking, additive bias (broadcast over batch/head dims),
grouped-query attention (q heads share k/v heads in-kernel — no HBM-side
``jnp.repeat``), softmax scale, sliding-window masking (Mistral-style local
attention — blocks left of the window are skipped like the causal block-skip,
with their K/V block indices clamped onto the visible range so Mosaic elides
the DMAs too: both MXU time and HBM traffic are O(T·W), not O(T²)),
packed-sequence segment-id masking
(cross-segment logits masked in-kernel — no [Tq,Tk] bias materialization),
custom VJP with flash backward kernels.

Layout: q [B, Tq, H, Dh], k/v [B, Tk, KV, Dh] with H % KV == 0; output
[B, Tq, H, Dh] (same as ``ops.flash_attention.mha_reference``). Segment ids
are int32 [B, Tq] / [B, Tk]; attention is masked where they differ.
"""

import functools

import jax
import jax.ad_checkpoint  # jax 0.9 removed the lazy `jax.ad_checkpoint` attr
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9  # finite: -inf poisons fully-masked softmax rows

LANES = 128     # TPU lane width; m/l scratch rows are broadcast across lanes
SUBLANES = 8    # TPU sublane count; kv segment-id rows are sublane-replicated

#: ``checkpoint_name``s of the custom_vjp's residuals that only the forward
#: kernel can make (output and log-sum-exp; q, k, v are slices of a saved dot)
RESIDUAL_NAMES = ("flash_attn_res_out", "flash_attn_res_lse")


def _largest_divisor(n, candidates):
    for c in candidates:
        if n % c == 0:
            return c
    return None


_LADDER = (512, 256, 128)


def _env_block(var, seq_len, which):
    """Parse a DS_FLASH_BQ/BK override. Returns the forced block or None
    (unset / "0" = off). A value that is not an integer or does not divide
    the sequence raises a ValueError naming the variable — a silently
    ignored override cost real tuning sessions (docs/AUTOTUNING.md)."""
    import os
    raw = os.environ.get(var, "")
    if not raw:
        return None
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(f"{var}={raw!r} is not an integer block size")
    if v == 0:
        return None
    if v < 0:
        raise ValueError(f"{var}={v} must be a positive block size")
    if seq_len % v != 0:
        raise ValueError(f"{var}={v} does not divide the {which} sequence "
                         f"length {seq_len}")
    return v


def _pick_blocks(tq, tk):
    """Hardcoded block ladder with the DS_FLASH_BQ / DS_FLASH_BK env
    override on top (a documented escape hatch over the tuning table —
    see :func:`_resolve_blocks` for the full table-first resolution)."""
    force_q = _env_block("DS_FLASH_BQ", tq, "query")
    force_k = _env_block("DS_FLASH_BK", tk, "key")
    bq = force_q if force_q else _largest_divisor(tq, _LADDER)
    bk = force_k if force_k else _largest_divisor(tk, _LADDER)
    return bq, bk


def _resolve_blocks(tq, tk, dh, dtype):
    """Resolution order for one dispatch: env override > tuning table >
    ladder. Returns the ``BlockConfig`` and records the decision (source +
    a tuned|ladder_fallback|env_override telemetry reason) in the registry."""
    from deepspeed_tpu.autotuning.kernel_table import BlockConfig
    from deepspeed_tpu.ops import registry

    force_q = _env_block("DS_FLASH_BQ", tq, "query")
    force_k = _env_block("DS_FLASH_BK", tk, "key")
    if force_q or force_k:
        bq = force_q if force_q else _largest_divisor(tq, _LADDER)
        bk = force_k if force_k else _largest_divisor(tk, _LADDER)
        cfg = BlockConfig.make("flash_mha", source="env",
                               block_q=bq, block_k=bk)
        return registry.note_block_config("flash_mha", cfg)

    def validate(blocks, dims):
        return (dims["tq"] % blocks["block_q"] == 0
                and dims["tk"] % blocks["block_k"] == 0)

    def ladder():
        return {"block_q": _largest_divisor(tq, _LADDER),
                "block_k": _largest_divisor(tk, _LADDER)}

    return registry.resolve_block_config(
        "flash_mha", {"tq": tq, "tk": tk, "dh": dh}, dtype,
        validate=validate, ladder=ladder)


def unsupported_reason(q_shape, k_shape, bias_shape=None, window=None,
                       segment_ids_shape=None):
    """None if the kernel can handle these shapes, else a human reason."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return f"expected 4D [B,T,H,Dh] tensors, got q={q_shape} k={k_shape}"
    B, tq, H, dh = q_shape
    _, tk, kv, _ = k_shape
    if kv == 0 or H % kv != 0:
        return f"q heads {H} not a multiple of kv heads {kv}"
    if dh > 256:
        return f"head dim {dh} > 256"
    bq, bk = _pick_blocks(tq, tk)
    if bq is None or bk is None:
        return f"seq lens (q={tq}, k={tk}) not multiples of 128"
    if window is not None and int(window) <= 0:
        return f"sliding window must be positive, got {window}"
    if bias_shape is not None:
        if len(bias_shape) != 4:
            return f"bias must be 4D [B|1, H|1, Tq, Tk], got {bias_shape}"
        bb, bh, btq, btk = bias_shape
        if (btq, btk) != (tq, tk) or bb not in (1, B) or bh not in (1, H):
            return (f"bias {bias_shape} not broadcastable to "
                    f"[{B}|1, {H}|1, {tq}, {tk}]")
    if segment_ids_shape is not None:
        qs, ks = segment_ids_shape
        if tuple(qs) != (B, tq) or tuple(ks) != (B, tk):
            return (f"segment ids {qs}/{ks} must be [B={B}, Tq={tq}] and "
                    f"[B={B}, Tk={tk}]")
    return None


def is_supported(q_shape, k_shape, bias_shape=None, window=None,
                 segment_ids_shape=None):
    """Whether the kernel can handle these shapes (else callers fall back)."""
    return unsupported_reason(q_shape, k_shape, bias_shape, window,
                              segment_ids_shape) is None


# ---------------------------------------------------------------------------
# shared masking helpers
# ---------------------------------------------------------------------------

def _block_visible(iq, ik, *, causal, window, bq, bk, off):
    """Whether block (iq, ik) can contain any visible (query, key) pair.

    Causal skips blocks fully above the diagonal; a sliding window also skips
    blocks fully LEFT of the window (key j visible iff j > i + off - window),
    making MXU cost O(Tq·window/bk) blocks per row instead of O(Tk/bk)."""
    run = (iq * bq + bq - 1 + off >= ik * bk) if causal else (ik >= 0)
    if window is not None:
        run = run & (ik * bk + bk - 1 + window > iq * bq + off)
    return run


def _k_bounds(iq, *, causal, window, bq, bk, nk, off):
    """[lo, hi] k-block range visible from q-block iq (inclusive)."""
    lo = jnp.int32(0)
    hi = jnp.int32(nk - 1)
    if window is not None:
        lo = jnp.maximum(lo, (iq * bq + off - window + 1) // bk)
    if causal:
        hi = jnp.clip((iq * bq + bq - 1 + off) // bk, 0, nk - 1)
    return lo, jnp.maximum(hi, lo)


def _q_bounds(ik, *, causal, window, bq, bk, nq, off):
    """[lo, hi] q-block range that can see k-block ik (inclusive)."""
    lo = jnp.int32(0)
    hi = jnp.int32(nq - 1)
    if causal:
        lo = jnp.maximum(lo, (ik * bk - off) // bq)
    if window is not None:
        hi = jnp.clip((ik * bk + bk - 2 + window - off) // bq, 0, nq - 1)
    return jnp.minimum(lo, hi), hi


def _clamp_k(ik, iq, **kw):
    """Clamp a skipped k-block index onto the visible range so Mosaic sees the
    same block index as the previous grid step and elides the K/V DMA —
    ``pl.when`` alone only gates MXU compute, the pipeline would still fetch
    every block and HBM traffic would stay O(Tq·Tk)."""
    lo, hi = _k_bounds(iq, **kw)
    return jnp.clip(ik, lo, hi)


def _clamp_q(iq, ik, **kw):
    """Same as :func:`_clamp_k` for the dkv grid (q innermost)."""
    lo, hi = _q_bounds(ik, **kw)
    return jnp.clip(iq, lo, hi)


# ---------------------------------------------------------------------------
# the walk inside a block
#
# A grid step holds a [bq, bk] block, as large as the chip's timing wants
# (the whole sequence at gpt2's 1024). Where the block IS the sequence and an
# edge crosses it, the kernels walk it in [sq, sk] sub-tiles: for a tile of
# query rows only the key tiles a (query, key) pair can be visible in, the
# position mask only on the tiles an edge crosses. Every bound is then a
# Python int and the walk unrolls. With several blocks a sequence a block is
# one tile, skipped or run whole as ``_block_visible`` says: a walk whose
# bounds follow ``program_id`` (loops of a traced trip count) took the v5e
# twice the whole block's time (docs/AUTOTUNING.md).
# ---------------------------------------------------------------------------

#: preferred (query rows, keys) sub-tile of each kernel's walk, as timed on
#: the v5e at gpt2's [12, 16, 1024, 64] (docs/AUTOTUNING.md); a block the
#: size does not divide is walked whole
_TILES = {"fwd": (128, 256), "dq": (256, 256), "dkv": (128, 256)}


def _tiles(kernel, tq, tk, bq, bk, causal, window):
    """A kernel's tile at these blocks: the block itself unless it is the
    whole sequence and an edge crosses it."""
    if (bq, bk) != (tq, tk) or not (causal or window is not None):
        return bq, bk
    sq, sk = _TILES[kernel]
    return (sq if bq % sq == 0 else bq), (sk if bk % sk == 0 else bk)


def _ordered(a, b, c, d, n):
    clip = lambda x, lo, hi: max(lo, min(x, hi))
    a = clip(a, 0, n)
    d = clip(d, a, n)
    b = clip(b, a, d)
    return a, b, clip(c, b, d), d


def _k_ranges(row0, col0, n, *, sq, sk, causal, window):
    """Key tiles ``u`` (keys ``col0 + u * sk ..``, ``n`` of them) that query
    rows ``row0 .. row0 + sq`` (``off`` added) can see, as ``(a, b, c, d)``:
    ``[a, b)`` the window's left edge crosses, ``[b, c)`` are visible whole,
    ``[c, d)`` the diagonal crosses; outside ``[a, d)`` nothing is visible.
    Where both edges cross one tile it lies in a masked range. Python ints."""
    x = row0 - col0
    a = b = 0
    c = d = n
    if window is not None:
        a = (x - window + 1) // sk
        b = (x - window + sq - 1) // sk + 1
    if causal:
        c = (x + 1) // sk
        d = (x + sq - 1) // sk + 1
    return _ordered(a, b, c, d, n)


def _q_ranges(col0, row0, n, *, sq, sk, causal, window):
    """:func:`_k_ranges` seen from keys ``col0 .. col0 + sk``: query tiles
    ``t`` (rows ``row0 + t * sq ..``, ``off`` added): ``[a, b)`` the diagonal
    crosses, ``[b, c)`` whole, ``[c, d)`` the window's edge crosses."""
    y = col0 - row0
    a = b = 0
    c = d = n
    if causal:
        a = y // sq
        b = (y + sk + sq - 2) // sq
    if window is not None:
        c = (y + window) // sq
        d = (y + sk + window + sq - 2) // sq
    return _ordered(a, b, c, d, n)


def visible_share(tq, tk, bq, bk, causal, window):
    """Share of the [tq, tk] square whose logits the forward computes at
    these blocks (static, from shapes; the backward kernels' share is the
    same while every kernel's rows divide one keys' tile, as ``_TILES``'
    do): 1.0 without an edge, 0.625 for a causal 1024 x 1024 in one block
    walked in tiles of 256 keys."""
    sq, sk = _tiles("fwd", tq, tk, bq, bk, causal, window)
    tiles = 0
    for iq in range(tq // bq):
        for ik in range(tk // bk):
            for t in range(bq // sq):
                a, _, _, d = _k_ranges(iq * bq + t * sq + tk - tq, ik * bk,
                                       bk // sk, sq=sq, sk=sk, causal=causal,
                                       window=window)
                tiles += d - a
    return tiles * sq * sk / (tq * tk)


def _walk(ranges, step, carry):
    """``step(i, carry, edge)`` over the tiles of a walk, unrolled; ``None``
    for a block that is one tile, run whole (the grid step's
    ``_block_visible`` was its bound)."""
    if ranges is None:
        return step(0, carry, edge=True)
    a, b, c, d = ranges
    for i in range(a, d):
        carry = step(i, carry, edge=not b <= i < c)
    return carry


def _when(cond, fn):
    """``pl.when`` that a static condition decides at trace time."""
    if isinstance(cond, bool):
        if cond:
            fn()
    else:
        pl.when(cond)(fn)


def _tile_logits(q, k, rows, cols, thr, diff, bias_ref, qseg_ref, kseg_ref, *,
                 scale, edge, causal, window, sk):
    """Masked, scaled logits of one [sq, sk] tile. ``thr`` is the tile's first
    key minus its first query row (``off`` added), ``diff`` the tile's
    ``row - column`` iota: a pair is causal-visible iff ``diff >= thr`` and
    inside the window iff ``diff < thr + window``; only a tile an edge
    crosses (``edge``) builds that mask. Segment ids arrive lane-replicated
    (q: [bq, LANES]) and sublane-replicated (kv: [SUBLANES, bk]) so the
    comparison lowers to cheap VPU broadcasts."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if bias_ref is not None:
        s = s + bias_ref[0, 0, rows, cols].astype(jnp.float32)
    mask = None
    if qseg_ref is not None:
        # pltpu.repeat, not jnp.tile: tile lowers through a shape cast that
        # older Mosaic rejects ("unsupported shape cast")
        qs = pltpu.repeat(qseg_ref[0, rows, :], sk // LANES, 1)   # [sq, sk]
        mask = qs == kseg_ref[0, :1, cols]                        # [1, sk]
    if edge:
        if causal:
            pm = diff >= thr
            mask = pm if mask is None else mask & pm
        if window is not None:
            wm = diff < thr + window
            mask = wm if mask is None else mask & wm
    return s if mask is None else jnp.where(mask, s, NEG_INF)


def _diff_iota(sq, sk, causal, window):
    if not causal and window is None:
        return None
    return (jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1))


def _block_ids(nq, nk, q_axis, k_axis):
    """A block's place; 0 as a Python int on an axis of one block, so that
    the walk's bounds are static there."""
    return (0 if nq == 1 else pl.program_id(q_axis),
            0 if nk == 1 else pl.program_id(k_axis))


def _unpack_refs(refs, n_fixed, has_bias, has_seg):
    """Split a kernel's positional refs into (fixed..., bias, qseg, kseg,
    rest...) honoring the optional-input layout used by every kernel here."""
    fixed = refs[:n_fixed]
    i = n_fixed
    bias_ref = refs[i] if has_bias else None
    i += 1 if has_bias else 0
    qseg_ref = refs[i] if has_seg else None
    kseg_ref = refs[i + 1] if has_seg else None
    i += 2 if has_seg else 0
    return fixed, bias_ref, qseg_ref, kseg_ref, refs[i:]


def _seg_inputs(segment_ids, B, tq, tk):
    """Replicate [B,T] segment ids into Mosaic-friendly layouts: q ids across
    LANES (minor), kv ids across SUBLANES (second minor)."""
    q_seg, kv_seg = segment_ids
    q_rep = jnp.broadcast_to(q_seg.astype(jnp.int32)[:, :, None],
                             (B, tq, LANES))
    kv_rep = jnp.broadcast_to(kv_seg.astype(jnp.int32)[:, None, :],
                              (B, SUBLANES, tk))
    return q_rep, kv_rep


def _seg_specs(bq, bk, order="qk", clamp=None):
    def qindex(b, h, i, j):
        iq, ik = (i, j) if order == "qk" else (j, i)
        if clamp is not None and order == "kq":
            iq = clamp(iq, ik)
        return (b, iq, 0)

    def kindex(b, h, i, j):
        iq, ik = (i, j) if order == "qk" else (j, i)
        if clamp is not None and order == "qk":
            ik = clamp(ik, iq)
        return (b, 0, ik)

    return (pl.BlockSpec((1, bq, LANES), qindex),
            pl.BlockSpec((1, SUBLANES, bk), kindex))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, causal, scale, window, bq, bk, nq, nk, off, tiles,
                has_bias, has_seg):
    (q_ref, k_ref, v_ref), bias_ref, qseg_ref, kseg_ref, rest = _unpack_refs(
        refs, 3, has_bias, has_seg)
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    iq, ik = _block_ids(nq, nk, 2, 3)
    sq, sk = tiles
    dh = q_ref.shape[-1]

    def finish(rows, m, l, acc):
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, rows, :] = (acc / l_safe).astype(o_ref.dtype)
        # LSE rows are replicated across the LANES minor dim: Mosaic requires
        # the last two block dims be (8k, 128m)-aligned, so a [bq] vector
        # output is stored as [bq, LANES] (same layout as jax's own kernel).
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        lse_ref[0, 0, rows, :] = jnp.broadcast_to(lse, (lse.shape[0], LANES))

    if nk > 1:
        @pl.when(ik == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        diff = _diff_iota(sq, sk, causal, window)
        for t in range(bq // sq):
            rows = pl.ds(t * sq, sq)
            row0 = iq * bq + t * sq + off
            q = q_ref[0, 0, rows, :]                      # [sq, dh]

            def step(u, carry, edge):
                m_prev, l_prev, acc = carry
                cols = pl.ds(u * sk, sk)
                s = _tile_logits(q, k_ref[0, 0, cols, :], rows, cols,
                                 ik * bk + u * sk - row0, diff, bias_ref,
                                 qseg_ref, kseg_ref, scale=scale, edge=edge,
                                 causal=causal, window=window, sk=sk)
                m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_cur)
                p = jnp.exp(s - m_cur)                    # [sq, sk] f32
                l_cur = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
                pv = jax.lax.dot_general(
                    p.astype(v_ref.dtype), v_ref[0, 0, cols, :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return m_cur, l_cur, acc * alpha + pv

            if nk == 1:     # the block is the row's every key: no scratch
                carry = (jnp.full((sq, 1), NEG_INF, jnp.float32),
                         jnp.zeros((sq, 1), jnp.float32),
                         jnp.zeros((sq, dh), jnp.float32))
            else:
                carry = (m_scr[rows, :1], l_scr[rows, :1], acc_scr[rows, :])
            m, l, acc = _walk(
                _k_ranges(row0, ik * bk, bk // sk, sq=sq, sk=sk,
                          causal=causal, window=window)
                if nq == nk == 1 else None, step, carry)
            if nk == 1:
                finish(rows, m, l, acc)
            else:
                m_scr[rows, :] = jnp.broadcast_to(m, (sq, LANES))
                l_scr[rows, :] = jnp.broadcast_to(l, (sq, LANES))
                acc_scr[rows, :] = acc

    _when(_block_visible(iq, ik, causal=causal, window=window, bq=bq, bk=bk,
                         off=off), _body)

    if nk > 1:
        @pl.when(ik == nk - 1)
        def _finish():
            finish(slice(None), m_scr[:, :1], l_scr[:, :1], acc_scr[...])


def _bias_spec(bias, bq, bk, order="qk", clamp=None):
    """BlockSpec for a [1|B, 1|H, Tq, Tk] additive bias. ``clamp`` remaps the
    inner grid index on skipped blocks (DMA elision, see :func:`_clamp_k`)."""
    bb, bh = bias.shape[0], bias.shape[1]

    def index(b, h, i, j):
        iq, ik = (i, j) if order == "qk" else (j, i)
        if clamp is not None:
            if order == "qk":
                ik = clamp(ik, iq)
            else:
                iq = clamp(iq, ik)
        return (b if bb > 1 else 0, h if bh > 1 else 0, iq, ik)

    return pl.BlockSpec((1, 1, bq, bk), index)


def _fwd(q, k, v, bias, segment_ids, causal, scale, window, interpret,
         blocks=None):
    B, tq, H, dh = q.shape
    _, tk, KV, _ = k.shape
    rep = H // KV
    bq, bk = blocks if blocks is not None else _pick_blocks(tq, tk)
    nq, nk = tq // bq, tk // bk

    # [B, T, H, Dh] -> [B, H, T, Dh] so (T, Dh) are the tiled minor dims
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               window=window, bq=bq, bk=bk, nq=nq, nk=nk,
                               off=tk - tq,
                               tiles=_tiles("fwd", tq, tk, bq, bk, causal,
                                            window),
                               has_bias=bias is not None,
                               has_seg=segment_ids is not None)
    kb = dict(causal=causal, window=window, bq=bq, bk=bk, nk=nk, off=tk - tq)
    ck = functools.partial(_clamp_k, **kb)
    in_specs = [
        pl.BlockSpec((1, 1, bq, dh), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bk, dh),
                     lambda b, h, iq, ik: (b, h // rep, ck(ik, iq), 0)),
        pl.BlockSpec((1, 1, bk, dh),
                     lambda b, h, iq, ik: (b, h // rep, ck(ik, iq), 0)),
    ]
    args = [qt, kt, vt]
    if bias is not None:
        in_specs.append(_bias_spec(bias, bq, bk, clamp=ck))
        args.append(bias)
    if segment_ids is not None:
        qs, ks = _seg_specs(bq, bk, clamp=ck)
        in_specs += [qs, ks]
        args += list(_seg_inputs(segment_ids, B, tq, tk))

    with jax.named_scope("flash_mha_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid=(B, H, nq, nk),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, bq, dh), lambda b, h, iq, ik: (b, h, iq, 0)),
                pl.BlockSpec((1, 1, bq, LANES), lambda b, h, iq, ik: (b, h, iq, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, tq, dh), q.dtype),
                jax.ShapeDtypeStruct((B, H, tq, LANES), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, dh), jnp.float32),
            ],
            name="flash_mha_fwd",
            interpret=interpret,
        )(*args)
    # keep only column 0 as the residual: holding the lane-replicated copy
    # from forward to backward would be a 128x memory blow-up
    return out.transpose(0, 2, 1, 3), lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(*refs, causal, scale, window, bq, bk, nq, nk, off, tiles,
                   has_bias, has_seg):
    ((q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), bias_ref, qseg_ref,
     kseg_ref, rest) = _unpack_refs(refs, 6, has_bias, has_seg)
    dq_ref, dq_scr = rest
    iq, ik = _block_ids(nq, nk, 2, 3)
    sq, sk = tiles
    dh = q_ref.shape[-1]

    if nk > 1:
        @pl.when(ik == 0)
        def _init():
            dq_scr[...] = jnp.zeros_like(dq_scr)

    def _body():
        diff = _diff_iota(sq, sk, causal, window)
        for t in range(bq // sq):
            rows = pl.ds(t * sq, sq)
            row0 = iq * bq + t * sq + off
            q = q_ref[0, 0, rows, :]
            do = do_ref[0, 0, rows, :].astype(jnp.float32)      # [sq, dh]
            lse = lse_ref[0, 0, rows, :1]                 # [sq, 1] (lane-replicated)
            delta = delta_ref[0, 0, rows, :1]

            def step(u, dq, edge):
                cols = pl.ds(u * sk, sk)
                k = k_ref[0, 0, cols, :]
                s = _tile_logits(q, k, rows, cols, ik * bk + u * sk - row0,
                                 diff, bias_ref, qseg_ref, kseg_ref,
                                 scale=scale, edge=edge, causal=causal,
                                 window=window, sk=sk)
                p = jnp.exp(s - lse)                      # [sq, sk]
                dp = jax.lax.dot_general(
                    do, v_ref[0, 0, cols, :].astype(jnp.float32),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = p * (dp - delta) * scale             # [sq, sk]
                return dq + jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

            dq = _walk(
                _k_ranges(row0, ik * bk, bk // sk, sq=sq, sk=sk,
                          causal=causal, window=window)
                if nq == nk == 1 else None, step,
                jnp.zeros((sq, dh), jnp.float32) if nk == 1
                else dq_scr[rows, :])
            if nk == 1:
                dq_ref[0, 0, rows, :] = dq.astype(dq_ref.dtype)
            else:
                dq_scr[rows, :] = dq

    _when(_block_visible(iq, ik, causal=causal, window=window, bq=bq, bk=bk,
                         off=off), _body)

    if nk > 1:
        @pl.when(ik == nk - 1)
        def _finish():
            dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, causal, scale, window, bq, bk, nq, nk, off, tiles,
                    has_bias, has_seg):
    ((q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), bias_ref, qseg_ref,
     kseg_ref, rest) = _unpack_refs(refs, 6, has_bias, has_seg)
    dk_ref, dv_ref, dk_scr, dv_scr = rest
    iq, ik = _block_ids(nq, nk, 3, 2)
    sq, sk = tiles
    dh = k_ref.shape[-1]

    if nq > 1:
        @pl.when(iq == 0)
        def _init():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

    def _body():
        diff = _diff_iota(sq, sk, causal, window)
        for u in range(bk // sk):
            cols = pl.ds(u * sk, sk)
            col0 = ik * bk + u * sk
            k = k_ref[0, 0, cols, :]
            v = v_ref[0, 0, cols, :].astype(jnp.float32)

            def step(t, carry, edge):
                dk, dv = carry
                rows = pl.ds(t * sq, sq)
                q = q_ref[0, 0, rows, :]
                s = _tile_logits(q, k, rows, cols,
                                 col0 - (iq * bq + t * sq + off), diff,
                                 bias_ref, qseg_ref, kseg_ref, scale=scale,
                                 edge=edge, causal=causal, window=window,
                                 sk=sk)
                p = jnp.exp(s - lse_ref[0, 0, rows, :1])  # [sq, sk]
                do = do_ref[0, 0, rows, :].astype(jnp.float32)
                # dV += P^T @ dO
                dv = dv + jax.lax.dot_general(
                    p, do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                         preferred_element_type=jnp.float32)
                ds = p * (dp - delta_ref[0, 0, rows, :1]) * scale
                # dK += dS^T @ Q
                dk = dk + jax.lax.dot_general(
                    ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return dk, dv

            dk, dv = _walk(
                _q_ranges(col0, iq * bq + off, bq // sq, sq=sq, sk=sk,
                          causal=causal, window=window)
                if nq == nk == 1 else None, step,
                (jnp.zeros((sk, dh), jnp.float32),) * 2 if nq == 1
                else (dk_scr[cols, :], dv_scr[cols, :]))
            if nq == 1:
                dk_ref[0, 0, cols, :] = dk.astype(dk_ref.dtype)
                dv_ref[0, 0, cols, :] = dv.astype(dv_ref.dtype)
            else:
                dk_scr[cols, :] = dk
                dv_scr[cols, :] = dv

    _when(_block_visible(iq, ik, causal=causal, window=window, bq=bq, bk=bk,
                         off=off), _body)

    if nq > 1:
        @pl.when(iq == nq - 1)
        def _finish():
            dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(causal, scale, window, interpret, blocks, res, g):
    q, k, v, bias, segment_ids, out, lse = res
    B, tq, H, dh = q.shape
    _, tk, KV, _ = k.shape
    rep = H // KV
    bq, bk = blocks if blocks is not None else _pick_blocks(tq, tk)
    nq, nk = tq // bq, tk // bk

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = g.transpose(0, 2, 1, 3)
    ot = out.transpose(0, 2, 1, 3)

    # delta_i = rowsum(dO_i * O_i) — cheap in XLA, feeds both bwd kernels.
    # Broadcast delta and the saved LSE across LANES: the kernels read both
    # through lane-replicated [.., LANES] blocks (transient, backward-only).
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, LANES))
    lse = jnp.broadcast_to(lse[..., None], (*lse.shape, LANES))

    seg_args = None if segment_ids is None else _seg_inputs(segment_ids, B, tq, tk)

    kb = dict(causal=causal, window=window, bq=bq, bk=bk, off=tk - tq)
    ck = functools.partial(_clamp_k, nk=nk, **kb)
    cq = functools.partial(_clamp_q, nq=nq, **kb)

    qspec = pl.BlockSpec((1, 1, bq, dh), lambda b, h, iq, ik: (b, h, iq, 0))
    kspec = pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, iq, ik: (b, h // rep, ck(ik, iq), 0))
    dospec = qspec
    lspec = pl.BlockSpec((1, 1, bq, LANES), lambda b, h, iq, ik: (b, h, iq, 0))
    common = [qt, kt, vt, dot, lse, delta]

    def specs_with_extras(base, order, clamp):
        sp = list(base)
        args = list(common)
        if bias is not None:
            sp.append(_bias_spec(bias, bq, bk, order, clamp=clamp))
            args.append(bias)
        if seg_args is not None:
            qs, ks = _seg_specs(bq, bk, order, clamp=clamp)
            sp += [qs, ks]
            args += list(seg_args)
        return sp, args

    # dQ: grid (B, H, nq, nk), k innermost
    dq_specs, dq_args = specs_with_extras(
        [qspec, kspec, kspec, dospec, lspec, lspec], "qk", ck)
    dq_kernel = functools.partial(
        _bwd_dq_kernel, causal=causal, scale=scale, window=window,
        bq=bq, bk=bk, nq=nq, nk=nk, off=tk - tq,
        tiles=_tiles("dq", tq, tk, bq, bk, causal, window),
        has_bias=bias is not None, has_seg=seg_args is not None)
    with jax.named_scope("flash_mha_bwd_dq"):
        dq = pl.pallas_call(
            dq_kernel,
            grid=(B, H, nq, nk),
            in_specs=dq_specs,
            out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            out_shape=jax.ShapeDtypeStruct((B, H, tq, dh), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
            name="flash_mha_bwd_dq",
            interpret=interpret,
        )(*dq_args)

    # dK/dV: grid (B, H, nk, nq), q innermost; per-q-head results, GQA head
    # groups summed afterwards in XLA (rep is 1 for MHA so this is free there)
    kspec2 = pl.BlockSpec((1, 1, bk, dh), lambda b, h, ik, iq: (b, h // rep, ik, 0))
    qspec2 = pl.BlockSpec((1, 1, bq, dh),
                          lambda b, h, ik, iq: (b, h, cq(iq, ik), 0))
    lspec2 = pl.BlockSpec((1, 1, bq, LANES),
                          lambda b, h, ik, iq: (b, h, cq(iq, ik), 0))
    dkv_specs, dkv_args = specs_with_extras(
        [qspec2, kspec2, kspec2, qspec2, lspec2, lspec2], "kq", cq)
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, causal=causal, scale=scale, window=window,
        bq=bq, bk=bk, nq=nq, nk=nk, off=tk - tq,
        tiles=_tiles("dkv", tq, tk, bq, bk, causal, window),
        has_bias=bias is not None, has_seg=seg_args is not None)
    with jax.named_scope("flash_mha_bwd_dkv"):
        dk, dv = pl.pallas_call(
            dkv_kernel,
            grid=(B, H, nk, nq),
            in_specs=dkv_specs,
            out_specs=[
                pl.BlockSpec((1, 1, bk, dh), lambda b, h, ik, iq: (b, h, ik, 0)),
                pl.BlockSpec((1, 1, bk, dh), lambda b, h, ik, iq: (b, h, ik, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, tk, dh), k.dtype),
                jax.ShapeDtypeStruct((B, H, tk, dh), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, dh), jnp.float32),
                pltpu.VMEM((bk, dh), jnp.float32),
            ],
            name="flash_mha_bwd_dkv",
            interpret=interpret,
        )(*dkv_args)

    if rep > 1:
        dk = dk.reshape(B, KV, rep, tk, dh).sum(axis=2)
        dv = dv.reshape(B, KV, rep, tk, dh).sum(axis=2)

    dq = dq.transpose(0, 2, 1, 3)
    dk = dk.transpose(0, 2, 1, 3)
    dv = dv.transpose(0, 2, 1, 3)
    dbias = None if bias is None else jnp.zeros_like(bias)
    return dq, dk, dv, dbias, None


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, bias, segment_ids, causal, scale, window, interpret,
           blocks):
    out, _ = _fwd(q, k, v, bias, segment_ids, causal, scale, window, interpret,
                  blocks)
    return out


def _flash_fwd(q, k, v, bias, segment_ids, causal, scale, window, interpret,
               blocks):
    out, lse = _fwd(q, k, v, bias, segment_ids, causal, scale, window,
                    interpret, blocks)
    # the backward's residuals by name, INSIDE the custom_vjp: a remat policy
    # that saves these names (activation_checkpointing: "dots") keeps what
    # the backward reads, and the forward kernel runs once a layer. A name
    # on the output outside would save a value the backward never asks for.
    out = jax.ad_checkpoint.checkpoint_name(out, RESIDUAL_NAMES[0])
    lse = jax.ad_checkpoint.checkpoint_name(lse, RESIDUAL_NAMES[1])
    return out, (q, k, v, bias, segment_ids, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


def flash_mha(q, k, v, bias=None, causal=True, softmax_scale=None,
              window=None, segment_ids=None, interpret=False,
              block_config=None):
    """Flash attention. q [B,Tq,H,Dh]; k/v [B,Tk,KV,Dh], H % KV == 0.

    ``window``: sliding-window size (query i sees keys in
    ``(i + off - window, i + off]``, matching Mistral's local attention) —
    enforced in-kernel with whole-block skipping, never via a [Tq,Tk] bias.
    ``segment_ids``: int32 ``(q_ids [B,Tq], kv_ids [B,Tk])`` tuple or a single
    [B,T] array when Tq == Tk; positions in different segments do not attend
    (packed-sequence pretraining).

    Block sizes resolve env override > tuning table > hardcoded ladder
    (docs/AUTOTUNING.md); ``block_config`` — a ``BlockConfig`` or
    ``{"block_q": .., "block_k": ..}`` dict — pins them outright (the tuner
    sweep path). A pinned block that does not divide the sequence raises.

    Raises ValueError on unsupported shapes — callers (the op registry) are
    expected to gate on :func:`is_supported` and fall back to the XLA path.
    The additive ``bias`` is treated as a constant (zero cotangent): every
    in-tree caller passes masks built from positions, never learned tensors.
    """
    if segment_ids is not None and not isinstance(segment_ids, (tuple, list)):
        segment_ids = (segment_ids, segment_ids)
    seg_shape = None if segment_ids is None else (segment_ids[0].shape,
                                                  segment_ids[1].shape)
    reason = unsupported_reason(q.shape, k.shape,
                                None if bias is None else bias.shape,
                                window, seg_shape)
    if reason is not None:
        raise ValueError(f"flash_mha: {reason}")
    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    window = None if window is None else int(window)
    seg = None if segment_ids is None else tuple(segment_ids)
    return _dispatch_flash(q, k, v, bias, seg, causal, float(scale), window,
                           interpret, block_config)


def _dispatch_flash(q, k, v, bias, seg, causal, scale, window, interpret,
                    block_config=None):
    """Route ``_flash`` through the SPMD kernel dispatcher: batch over the
    active mesh's data axes, heads over the TP axis (k/v carry KV heads, so
    the head axis must divide KV — GQA sharding keeps whole KV groups
    together). Per-device shapes keep the kernel's own invariants: the seq
    dims are untouched, so blocks resolved on the global shapes are the
    per-shard blocks too."""
    from deepspeed_tpu.autotuning.kernel_table import BlockConfig
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.registry import sharded_kernel_call

    tq, dh = q.shape[1], q.shape[3]
    tk = k.shape[1]
    if block_config is not None:
        if not isinstance(block_config, BlockConfig):
            block_config = BlockConfig.make("flash_mha", source="sweep",
                                            **dict(block_config))
        bq = block_config.get("block_q")
        bk = block_config.get("block_k")
        if tq % bq != 0 or tk % bk != 0:
            raise ValueError(f"flash_mha: pinned blocks (bq={bq}, bk={bk}) "
                             f"do not divide seq lens (tq={tq}, tk={tk})")
        registry.note_block_config("flash_mha", block_config,
                                   reason=block_config.source)
    else:
        block_config = _resolve_blocks(tq, tk, dh, q.dtype)
    blocks = (block_config.get("block_q"), block_config.get("block_k"))
    registry.note_kernel_facts("flash_mha", visible_share=visible_share(
        tq, tk, *blocks, causal, window))

    args = [q, k, v]
    in_roles = [("data", None, "head", None), ("data", None, "head", None),
                ("data", None, "head", None)]
    if bias is not None:
        args.append(bias)
        in_roles.append(("data" if bias.shape[0] > 1 else None,
                         "head" if bias.shape[1] > 1 else None, None, None))
    if seg is not None:
        args.extend(seg)
        in_roles.extend([("data", None), ("data", None)])

    def call(*ts):
        q_, k_, v_ = ts[:3]
        i = 3
        b_ = None
        if bias is not None:
            b_ = ts[i]
            i += 1
        s_ = None if seg is None else (ts[i], ts[i + 1])
        return _flash(q_, k_, v_, b_, s_, causal, scale, window, interpret,
                      blocks)

    def accept(shard_shapes):
        # per-shard GQA ratio must stay integral (H and KV shrink together)
        (_, _, h, _), (_, _, kv, _) = shard_shapes[0], shard_shapes[1]
        return kv >= 1 and h % kv == 0

    return sharded_kernel_call(call, args, in_roles,
                               ("data", None, "head", None), accept=accept,
                               name="flash_mha", block_config=block_config)
