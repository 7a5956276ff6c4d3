"""Ragged grouped-GEMM MoE FFN (megablox) vs the GShard einsum oracle
(reference ``tests/unit/inference/v2/kernels/cutlass_ops`` +
``ragged_ops/moe_*`` analogs). Interpret mode on CPU; real-TPU lowering is
covered by scripts/tpu_kernel_smoke.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.moe_layer import moe_ffn
from deepspeed_tpu.ops.pallas.grouped_gemm import (is_supported, moe_ffn_gmm,
                                                   topk_router)


def make_case(T=16, D=128, F=256, E=4, k=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (T, D), jnp.float32)
    gate = jax.random.normal(ks[1], (D, E), jnp.float32) * 0.3
    w1 = jax.random.normal(ks[2], (E, D, F), jnp.float32) * 0.05
    w2 = jax.random.normal(ks[3], (E, F, D), jnp.float32) * 0.05
    w3 = jax.random.normal(ks[4], (E, D, F), jnp.float32) * 0.05
    return x, gate, w1, w2, w3, k


@pytest.mark.parametrize("T", [16, 40])
def test_matches_einsum_oracle(T):
    x, gate, w1, w2, w3, k = make_case(T=T)
    tv, ti = topk_router(x, gate, k)
    got = moe_ffn_gmm(x, tv, ti, w1, w2, w3, n_experts=gate.shape[1],
                      dtype=jnp.float32, interpret=True)
    want = moe_ffn(x, gate, w1, w2, w3, k=k, dtype=jnp.float32,
                   force_einsum=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_skewed_routing():
    """Heavily skewed routing (one expert takes nearly all tokens): ragged
    groups handle it with no capacity overflow, matching the lossless
    einsum oracle."""
    x, gate, w1, w2, w3, k = make_case(T=24, seed=3)
    x = jnp.abs(x)                  # positive tokens: the col-0 bump then
    gate = gate.at[:, 0].add(5.0)   # routes every token to expert 0
    logits = (x @ gate).astype(jnp.float32)
    top_idx = jnp.argmax(logits, axis=-1)
    assert int((top_idx == 0).sum()) >= 22  # fixture sanity: real skew
    tv, ti = topk_router(x, gate, 1)
    got = moe_ffn_gmm(x, tv, ti, w1, w2, w3, n_experts=gate.shape[1],
                      dtype=jnp.float32, interpret=True)
    want = moe_ffn(x, gate, w1, w2, w3, k=1, dtype=jnp.float32,
                   force_einsum=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_is_supported_gate():
    assert is_supported(128, 256)
    assert not is_supported(96, 256)
    assert not is_supported(128, 200)
