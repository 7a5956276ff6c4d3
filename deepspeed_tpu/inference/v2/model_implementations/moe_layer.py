"""The sparse-expert feed-forward layer of a ragged forward: the one place
shared by every family that has one (``mixtral.py``, ``mellum2.py``,
``kanana2.py``, ``keye_vl2.py``, ``longcat_flash.py``).

``moe_ffn`` routes a flat batch of token slots (by default softmax over all
experts, the ``k`` largest, renormalised: ``grouped_gemm.topk_router``; with
``scoring="sigmoid"`` the DeepSeek-V3 router, ``sigmoid_router``; with
``scoring="softmax_bias"`` LongCat-Flash's, ``softmax_bias_router``) and runs the
chosen experts' SwiGLU: the ragged grouped GEMM (``ops/pallas/grouped_gemm.py``:
rows sorted by expert, no capacity dimension) when Pallas is on and the dims
tile, else the GShard dense dispatch-combine einsum below, which
``force_einsum`` pins as the tests' oracle.

Padded token slots take no expert rows. A dispatch's token slots are
``[S, Q]`` padded to buckets; ``valid`` marks the real ones. A slot that is
not valid is sorted past every expert's group (the grouped GEMM never visits
it) or has an all-zero dispatch row (the einsum), and its output is zero. With
8 experts of 2 a token the padding was a few wasted rows a group; with 64 of 8
it would be whole groups. ``dispatch_report`` is what the engine's spans and
the scheduler's counters say of it.

A share of the experts. ``experts_held = (first, count)`` says that ``w1`` /
``w2`` / ``w3`` hold the ``count`` experts from ``first`` on, of the router's
whole width: the layer routes over ALL experts (the gate's columns, the
weights' normalisation over all ``k`` chosen), and computes its own experts'
part of the sum. A row whose expert is not held is sorted past every group
exactly as a padded slot's rows are (expert index ``count``: the GEMM never
visits it; in the einsum a zero dispatch row). A token none of whose experts
are held gets the shared expert's output alone. Nothing stands in for the
other shares or their exchange.

Experts that compute nothing. ``zero_experts=n`` says that the router's LAST
``n`` columns are identity experts (LongCat-Flash's ``zero_expert_type``
``identity``): a row chosen there is a third outcome beside a group row and a
row of another share. It is sorted past every group exactly as those are (no
GEMM row), and its token gets ``(the sum of its zero picks' weights) x`` added.
``experts_held`` keeps meaning a range of the REAL experts (the columns before
the zero ones); the zero experts are every share's, so a sum over shares counts
their term once. ``counts=True`` also returns what only the device knows of
the dispatch, int32 ``COUNTS``: the rows routed for valid tokens, those that
took a zero expert, those that landed on an expert held here, and the held
experts with at least one row.

Scopes for the device trace: everything here is under ``moe_ffn``; inside it
the router under ``moe_router``, the sort and gather of rows under
``moe_sort``, each grouped GEMM under ``moe_ffn_gmm``, the unsort and the
weighted sum of a token's ``k`` rows under ``moe_unsort``, a shared expert
(every token's, dense) under ``moe_shared``, the zero experts' term under
``moe_zero``, the counts under ``moe_counts``.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.registry import pallas_interpret, takes_kernel


#: what ``moe_ffn(counts=True)`` counts of one layer of one dispatch, in order
COUNTS = ("routed_rows", "zero_rows", "held_rows", "experts_hit")


def dispatch_report(cfg, real_tokens, chunk=None):
    """What a dispatch of ``real_tokens`` real tokens (in rows of ``chunk``
    token slots, which the expert layers do not ask) reports of the expert
    layers of a model of config ``cfg``: the family's module exports it
    (``engine_factory.resolve_report_fn``), and the engine carries the two
    mappings without reading them. Added to the round's counts:
    ``expert_rows``, the rows ROUTED for real tokens (tokens x experts a
    token x expert layers, every layer unless the config counts
    ``num_expert_layers``), and ``expert_rows_padded``, those for the
    dispatch's padded slots: 0 however many those are, because ``moe_ffn``
    sorts slots that are not ``valid`` past every group. Under a share of the
    experts (``experts_held``) the first is still every row the router chose,
    held here or not (which of them land on this share's experts is data,
    known on the device alone), and ``experts_held`` beside
    ``experts_routed_over`` rides on the span alone."""
    layers = getattr(cfg, "num_expert_layers", cfg.num_hidden_layers)
    adds = {"expert_rows": real_tokens * cfg.num_experts_per_tok * layers,
            "expert_rows_padded": 0}
    held = getattr(cfg, "experts_held", None)
    rides = {"experts_held": held[1],
             "experts_routed_over": cfg.n_routed_experts} if held else {}
    return adds, rides


def sigmoid_router(x, gate_wg, bias, k, scale):
    """The DeepSeek-V3 router (``topk_method: noaux_tc`` with one group, so
    that the group step is the identity): ``s = sigmoid(x W_g)`` in float32
    over all experts; chosen are the ``k`` largest of ``s + bias`` (the
    learned ``e_score_correction_bias`` selects and never weighs); weights
    ``s[chosen] / (sum s[chosen] + 1e-20) * scale``. -> (weights, indices),
    each [T, k]."""
    scores = jax.nn.sigmoid(
        jnp.dot(x, gate_wg, preferred_element_type=jnp.float32))
    _, top_idx = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    top_vals = jnp.take_along_axis(scores, top_idx, axis=-1)
    return top_vals / (jnp.sum(top_vals, -1, keepdims=True) + 1e-20) * scale, \
        top_idx


def softmax_bias_router(x, gate_wg, bias, k, scale):
    """LongCat-Flash's router: ``p = softmax(x W_r)`` in float32 over ALL
    columns (the zero experts' among them); chosen are the ``k`` largest of
    ``p + bias`` (``e_score_correction_bias`` selects and never weighs);
    weights ``scale * p[chosen]``, NOT renormalised. -> (weights, indices),
    each [T, k]."""
    probs = jax.nn.softmax(
        jnp.dot(x, gate_wg, preferred_element_type=jnp.float32), axis=-1)
    _, top_idx = jax.lax.top_k(probs + bias.astype(jnp.float32), k)
    return jnp.take_along_axis(probs, top_idx, axis=-1) * scale, top_idx


def moe_ffn(x, gate_wg, w1, w2, w3, *, k, dtype, valid=None,
            force_einsum=False, scoring="softmax", score_bias=None,
            routed_scale=1.0, shared=None, experts_held=None, zero_experts=0,
            counts=False):
    """x: [T, D]; gate_wg: [D, E]; w1/w3: [E_held, D, F]; w2: [E_held, F, D]
    (``E_held`` is E unless ``experts_held`` says otherwise); ``valid``: [T]
    bool, None for all. Returns [T, D], zero where not valid.

    ``scoring``: ``"softmax"`` (Mixtral, Mellum2: softmax, top-k,
    renormalised) or ``"sigmoid"`` (``sigmoid_router`` with ``score_bias``
    [E] and ``routed_scale``) or ``"softmax_bias"`` (``softmax_bias_router``
    with the same two). ``shared``: ``(w1, w2, w3)`` of a dense SwiGLU
    every valid token takes beside its routed experts, or None.
    ``experts_held``: ``(first, count)`` of the router's E columns whose
    experts the weights hold, None for all (module docstring).
    ``zero_experts``: how many of the router's last columns are identity
    experts; ``E`` then counts the columns before them. ``counts``: return
    ``(y, int32 [len(COUNTS)])`` (module docstring).

    Inference uses LOSSLESS capacity C = T: no token is ever dropped. The
    training-side capacity_factor machinery (moe/sharded_moe.py) does not
    apply here.
    """
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    T, D = x.shape
    E = gate_wg.shape[1] - zero_experts
    F = w1.shape[-1]
    if valid is None:
        valid = jnp.ones((T,), bool)
    with jax.named_scope("moe_ffn"):
        # single routing implementation for both dispatch backends
        with jax.named_scope("moe_router"):
            if scoring == "softmax":
                top_vals, top_idx = gg.topk_router(x, gate_wg, k)    # [T, k]
            elif scoring in ("sigmoid", "softmax_bias"):
                route = sigmoid_router if scoring == "sigmoid" \
                    else softmax_bias_router
                top_vals, top_idx = route(x, gate_wg, score_bias, k,
                                          routed_scale)
            else:
                raise ValueError(f"unknown router scoring {scoring!r}")
            top_vals = jnp.where(valid[:, None], top_vals, 0.0)
            if zero_experts:
                # a zero expert's row becomes index E (of the real experts):
                # past every group; its weight goes to the identity term
                is_zero = top_idx >= E
                zero_w = jnp.sum(jnp.where(is_zero, top_vals, 0.0), -1)
                top_idx = jnp.where(is_zero, E, top_idx)
                top_vals = jnp.where(is_zero, 0.0, top_vals)
            if experts_held is not None:
                first, E = experts_held
                assert w1.shape[0] == E, "the weights hold experts_held"
                # an expert that is not held becomes index E: past every
                # group, as a padded slot's rows are
                top_idx = top_idx - first
                held = (top_idx >= 0) & (top_idx < E)
                top_idx = jnp.where(held, top_idx, E)
                top_vals = jnp.where(held, top_vals, 0.0)
        if not force_einsum and takes_kernel(
                "moe_ffn_gmm", gg.is_supported(D, F),
                f"dims ({D}, {F}) not 128-tileable for gmm"):
            y = gg.moe_ffn_gmm(x, top_vals, top_idx, w1, w2, w3,
                               n_experts=E, dtype=dtype, valid=valid,
                               interpret=pallas_interpret())
        else:
            y = _moe_ffn_einsum(x, top_vals, top_idx, valid, w1, w2, w3, dtype)
        if zero_experts:
            with jax.named_scope("moe_zero"):
                y = y + (zero_w[:, None] * x.astype(jnp.float32)).astype(dtype)
        if shared is not None:
            with jax.named_scope("moe_shared"):
                s1, s2, s3 = shared
                h = (jax.nn.silu(x @ s1) * (x @ s3)) @ s2
                y = y + jnp.where(valid[:, None], h, 0).astype(dtype)
        if not counts:
            return y
        with jax.named_scope("moe_counts"):
            lands = valid[:, None] & (top_idx < E)        # on an expert held
            rows = jnp.zeros((E,), jnp.int32).at[
                jnp.where(lands, top_idx, E).reshape(-1)].add(1, mode="drop")
            zero = jnp.sum(valid[:, None] & is_zero) if zero_experts else 0
            return y, jnp.stack([
                jnp.sum(valid) * k, zero, jnp.sum(rows), jnp.sum(rows > 0)
            ]).astype(jnp.int32)


def _moe_ffn_einsum(x, top_vals, top_idx, valid, w1, w2, w3, dtype):
    T, E = x.shape[0], w1.shape[0]
    k = top_idx.shape[-1]
    C = T
    # top_k_gating: position of each (token, slot) inside its expert's bucket
    onehot = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)       # [T, k, E]
    onehot = onehot * valid[:, None, None]
    flat = onehot.reshape(T * k, E)
    pos = jnp.cumsum(flat, axis=0) * flat - flat                 # [T*k, E]
    keep = (pos < C).astype(jnp.float32) * flat
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)
    # dispatch [T, k, E, C] -> moe_scatter matrix [T, E, C]
    disp = (keep[..., None] * pos_oh).reshape(T, k, E, C)
    dispatch = disp.sum(axis=1)
    combine = (disp * top_vals[..., None, None]).sum(axis=1)     # [T, E, C]

    x = jnp.where(valid[:, None], x, 0)
    xe = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32)).astype(dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w1)) * \
        jnp.einsum("ecd,edf->ecf", xe, w3)                        # grouped GEMMs
    out_e = jnp.einsum("ecf,efd->ecd", h, w2)                    # [E, C, D]
    return jnp.einsum("tec,ecd->td", combine,
                      out_e.astype(jnp.float32)).astype(dtype)
