"""Attention kernels.

``mha`` is the framework-wide attention entry point (the analog of the
reference's fused attention kernels, ``csrc/transformer/inference/csrc/softmax.cu``
and the blocked_flash kernel family): callers always go through here, and the
best implementation for the backend is selected — the Pallas TPU
flash-attention kernel (``ops/pallas/flash_attention.py``) when on TPU and the
shapes are tileable, else the XLA einsum path (which XLA fuses well on its
own). Fallbacks are logged once per call-shape so a missing fast path is never
silent.

Grouped-query attention is first-class: k/v may carry fewer heads than q
(H % KV == 0) and both implementations handle the head grouping internally —
no caller-side ``jnp.repeat`` (which would materialize rep× K/V HBM traffic).
"""

import jax
import jax.ad_checkpoint  # jax 0.9 removed the lazy `jax.ad_checkpoint` attr
import jax.numpy as jnp

from deepspeed_tpu.ops.registry import OpBuilder, register_op_builder
from deepspeed_tpu.utils.logging import logger

NEG_INF = -1e9  # large finite; -inf breaks softmax rows that are fully masked

_warned_shapes = set()


def mha_reference(q, k, v, bias=None, causal=True, softmax_scale=None,
                  window=None, segment_ids=None):
    """Plain XLA attention. q [B,Tq,H,Dh]; k/v [B,Tk,KV,Dh] -> [B,Tq,H,Dh].

    ``window``: Mistral-style sliding window — query i sees keys in
    ``(i + off - window, i + off]`` where ``off = Tk - Tq``.
    ``segment_ids``: ``(q_ids [B,Tq], kv_ids [B,Tk])`` or single [B,T] array;
    cross-segment attention is masked (packed sequences)."""
    *_, H, Dh = q.shape
    KV = k.shape[2]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = softmax_scale if softmax_scale is not None else 1.0 / (Dh ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias
    Tq, Tk = logits.shape[-2], logits.shape[-1]
    off = Tk - Tq
    if causal or window is not None:
        qpos = jnp.arange(Tq)[:, None]
        kpos = jnp.arange(Tk)[None, :]
        mask = jnp.ones((Tq, Tk), dtype=bool)
        if causal:
            mask &= qpos + off >= kpos
        if window is not None:
            mask &= kpos > qpos + off - window
        logits = jnp.where(mask, logits, NEG_INF)
    if segment_ids is not None:
        if not isinstance(segment_ids, (tuple, list)):
            segment_ids = (segment_ids, segment_ids)
        q_seg, kv_seg = segment_ids
        same = q_seg[:, None, :, None] == kv_seg[:, None, None, :]  # [B,1,Tq,Tk]
        logits = jnp.where(same, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _pad_seq_to_lanes(q, k, v, bias, segment_ids, causal):
    """Pad Tq == Tk sequences to a multiple of 128 so they stay on the
    kernel path (packed/odd-length inputs). Padding goes at the END: under
    causal masking real queries never see the later pad keys, and for
    bidirectional attention pad keys get a reserved segment id no real token
    carries. Returns (padded tensors..., original T) — caller slices the
    output back. Tq != Tk is NOT padded (bottom-right causal alignment would
    shift with unequal pads)."""
    T = q.shape[1]
    pad = (-T) % 128
    padded = lambda x, val=0: jnp.pad(
        x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2), constant_values=val)
    q2, k2, v2 = padded(q), padded(k), padded(v)
    if bias is not None:
        bias = jnp.pad(bias, [(0, 0), (0, 0), (0, pad), (0, pad)])
    if segment_ids is not None:
        qs, ks = segment_ids
        # reserved pad id: one past the max real id, so pads never match
        pad_id = jnp.maximum(jnp.max(qs), jnp.max(ks)) + 1
        in_real = jnp.arange(T + pad)[None, :] < T
        qs2 = jnp.where(in_real, padded(qs.astype(jnp.int32)), pad_id)
        ks2 = jnp.where(in_real, padded(ks.astype(jnp.int32)), pad_id)
        segment_ids = (qs2, ks2)
    elif not causal:
        # bidirectional without user segments: synthesize real/pad segments
        real = (jnp.arange(T + pad)[None, :] < T).astype(jnp.int32)
        seg = jnp.broadcast_to(real, (q.shape[0], T + pad))
        segment_ids = (seg, seg)
    return q2, k2, v2, bias, segment_ids, T


def mha(q, k, v, bias=None, causal=True, softmax_scale=None, window=None,
        segment_ids=None):
    if window is not None and int(window) <= 0:
        # invalid everywhere, not a kernel limitation — never "fall back"
        raise ValueError(f"mha: sliding window must be positive or None, "
                         f"got {window}")
    builder = FlashAttnBuilder()
    if builder.is_compatible():
        from deepspeed_tpu.ops.pallas import flash_attention as fa
        if segment_ids is not None and not isinstance(segment_ids, (tuple, list)):
            segment_ids = (segment_ids, segment_ids)
        orig = (q, k, v, bias, segment_ids)
        orig_t = None
        T = q.shape[1]
        # only pad when the bias (if any) is a full [.,.,T,T] — padding a
        # non-4D or Tq/Tk-broadcast bias would corrupt or crash, and those
        # shapes belong on the reference fallback anyway
        bias_paddable = bias is None or (
            bias.ndim == 4 and bias.shape[2] == T and bias.shape[3] == T)
        if (T == k.shape[1] and T % 128 != 0 and T >= 16 and bias_paddable):
            # check the WOULD-BE padded shapes first: unsupported_reason is
            # shape-only, so an ultimately-unsupported config (head dim,
            # GQA ratio, ...) never pays for materializing padded copies
            Tp = T + ((-T) % 128)
            pq = (q.shape[0], Tp, q.shape[2], q.shape[3])
            pk = (k.shape[0], Tp, k.shape[2], k.shape[3])
            pb = None if bias is None else (bias.shape[0], bias.shape[1],
                                            Tp, Tp)
            ps = ((q.shape[0], Tp), (k.shape[0], Tp)) \
                if (segment_ids is not None or not causal) else None
            if fa.unsupported_reason(pq, pk, pb, window, ps) is None:
                q, k, v, bias, segment_ids, orig_t = _pad_seq_to_lanes(
                    q, k, v, bias, segment_ids, causal)
        seg_shape = None if segment_ids is None else (segment_ids[0].shape,
                                                      segment_ids[1].shape)
        reason = fa.unsupported_reason(q.shape, k.shape,
                                       None if bias is None else bias.shape,
                                       window, seg_shape)
        if reason is None:
            from deepspeed_tpu.ops.registry import pallas_interpret
            out = fa.flash_mha(q, k, v, bias=bias, causal=causal,
                               softmax_scale=softmax_scale, window=window,
                               segment_ids=segment_ids,
                               interpret=pallas_interpret())
            if orig_t is not None:
                out = out[:, :orig_t]
            # named so remat policies can choose to save attention outputs
            # (see activation_checkpointing "dots" policy): the projection
            # behind reads it in backward. What the flash BACKWARD reads is
            # named inside the kernel's custom_vjp (RESIDUAL_NAMES there);
            # this name alone never kept the forward kernel from rerunning
            return jax.ad_checkpoint.checkpoint_name(out, "flash_attn_out")
        q, k, v, bias, segment_ids = orig  # fall back on the UNpadded inputs
        if orig_t is not None:
            # re-derive the reason from the shapes the CALLER passed so the
            # warning is actionable (the padded-shape reason can name sizes
            # the user never wrote)
            seg_shape = None if segment_ids is None else (
                segment_ids[0].shape, segment_ids[1].shape)
            reason = fa.unsupported_reason(
                q.shape, k.shape, None if bias is None else bias.shape,
                window, seg_shape) or reason
        key = (q.shape, k.shape, None if bias is None else bias.shape,
               window, seg_shape)
        if key not in _warned_shapes:
            _warned_shapes.add(key)
            logger.warning(f"flash_attn: {reason}; using XLA fallback")
    return mha_reference(q, k, v, bias=bias, causal=causal,
                         softmax_scale=softmax_scale, window=window,
                         segment_ids=segment_ids)


@register_op_builder
class FlashAttnBuilder(OpBuilder):
    """Pallas flash attention slot (reference evoformer/blocked_flash analog)."""
    NAME = "flash_attn"

    def reference_impl(self):
        return mha_reference

    def pallas_impl(self):
        from deepspeed_tpu.ops.pallas.flash_attention import flash_mha
        return flash_mha
