"""The sparse-expert feed-forward layer of a ragged forward: the one place
shared by every family that has one (``mixtral.py``, ``mellum2.py``).

``moe_ffn`` routes a flat batch of token slots (softmax over all experts, the
``k`` largest, renormalised: ``grouped_gemm.topk_router``) and runs the chosen
experts' SwiGLU: the ragged grouped GEMM (``ops/pallas/grouped_gemm.py``:
rows sorted by expert, no capacity dimension) when Pallas is on and the dims
tile, else the GShard dense dispatch-combine einsum below, which
``force_einsum`` pins as the tests' oracle.

Padded token slots take no expert rows. A dispatch's token slots are
``[S, Q]`` padded to buckets; ``valid`` marks the real ones. A slot that is
not valid is sorted past every expert's group (the grouped GEMM never visits
it) or has an all-zero dispatch row (the einsum), and its output is zero. With
8 experts of 2 a token the padding was a few wasted rows a group; with 64 of 8
it would be whole groups. ``expert_rows`` is what the engine's spans and the
scheduler's counters say of it.

Scopes for the device trace: everything here is under ``moe_ffn``; inside it
the router under ``moe_router``, the sort and gather of rows under
``moe_sort``, each grouped GEMM under ``moe_ffn_gmm``, the unsort and the
weighted sum of a token's ``k`` rows under ``moe_unsort``.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.registry import pallas_interpret, takes_kernel


def expert_rows(real_tokens, k, layers):
    """``(expert_rows, expert_rows_padded)`` of a dispatch of ``real_tokens``
    real tokens through ``layers`` expert layers of ``k`` experts a token:
    rows that reach the expert GEMMs for real tokens, and for the dispatch's
    padded slots. The second is 0 however many those are, because ``moe_ffn``
    sorts slots that are not ``valid`` past every group."""
    return real_tokens * k * layers, 0


def moe_ffn(x, gate_wg, w1, w2, w3, *, k, dtype, valid=None,
            force_einsum=False):
    """x: [T, D]; gate_wg: [D, E]; w1/w3: [E, D, F]; w2: [E, F, D];
    ``valid``: [T] bool, None for all. Returns [T, D], zero where not valid.

    Inference uses LOSSLESS capacity C = T: no token is ever dropped. The
    training-side capacity_factor machinery (moe/sharded_moe.py) does not
    apply here.
    """
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    T, D = x.shape
    E = gate_wg.shape[1]
    F = w1.shape[-1]
    if valid is None:
        valid = jnp.ones((T,), bool)
    with jax.named_scope("moe_ffn"):
        # single routing implementation for both dispatch backends
        with jax.named_scope("moe_router"):
            top_vals, top_idx = gg.topk_router(x, gate_wg, k)    # [T, k]
            top_vals = jnp.where(valid[:, None], top_vals, 0.0)
        if not force_einsum and takes_kernel(
                "moe_ffn_gmm", gg.is_supported(D, F),
                f"dims ({D}, {F}) not 128-tileable for gmm"):
            return gg.moe_ffn_gmm(x, top_vals, top_idx, w1, w2, w3,
                                  n_experts=E, dtype=dtype, valid=valid,
                                  interpret=pallas_interpret())
        return _moe_ffn_einsum(x, top_vals, top_idx, valid, w1, w2, w3, dtype)


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
