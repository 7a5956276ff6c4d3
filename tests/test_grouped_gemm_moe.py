"""Ragged grouped-GEMM MoE FFN (megablox) vs the GShard einsum oracle
(reference ``tests/unit/inference/v2/kernels/cutlass_ops`` +
``ragged_ops/moe_*`` analogs). Interpret mode on CPU; real-TPU lowering is
covered by scripts/tpu_kernel_smoke.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.moe_layer import (
    _moe_ffn_einsum, moe_ffn)
from deepspeed_tpu.ops import registry
from deepspeed_tpu.ops.pallas import grouped_gemm as gg
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


# ---------------------------------------------------------------------------
# a tiling a GEMM: widths whose common divisor is 128 (D 384 = 3 x 128, F 256),
# so the up GEMMs' tiles (128, 384, 256) are not the down GEMM's (128, 256, 384)
# and neither divides the other GEMM's widths
# ---------------------------------------------------------------------------

D_ODD, F_ODD, E_ODD, K_ODD = 384, 256, 8, 2


def odd_case(T=21):
    """21 token slots of which 15 are valid: 30 expert rows, no multiple of
    tile_m, in 8 groups."""
    x, gate, w1, w2, w3, k = make_case(T=T, D=D_ODD, F=F_ODD, E=E_ODD,
                                       k=K_ODD, seed=5)
    valid = jnp.arange(T) % 7 < 5
    tv, ti = topk_router(x, gate, k)
    tv = jnp.where(valid[:, None], tv, 0.0)
    return x, tv, ti, valid, w1, w2, w3


def check_forward_parity():
    x, tv, ti, valid, w1, w2, w3 = odd_case()
    got = moe_ffn_gmm(x, tv, ti, w1, w2, w3, n_experts=E_ODD,
                      dtype=jnp.float32, valid=valid, interpret=True)
    want = _moe_ffn_einsum(x, tv, ti, valid, w1, w2, w3, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    assert not np.asarray(got)[~np.asarray(valid)].any()


def check_gradient_parity():
    """The training call (``moe/sharded_moe.py`` differentiates through
    ``moe_ffn_gmm``): gradients in the tokens, the gates and all three
    weight stacks against the einsum oracle's."""
    x, tv, ti, _, w1, w2, w3 = odd_case()
    valid = jnp.ones(x.shape[0], bool)      # training pads no token slots

    def loss(ffn):
        return lambda x, tv, w1, w2, w3: jnp.sum(ffn(x, tv, w1, w2, w3) ** 2)

    kernel = loss(lambda x, tv, w1, w2, w3: moe_ffn_gmm(
        x, tv, ti, w1, w2, w3, n_experts=E_ODD, dtype=jnp.float32,
        interpret=True))
    oracle = loss(lambda x, tv, w1, w2, w3: _moe_ffn_einsum(
        x, tv, ti, valid, w1, w2, w3, jnp.float32))
    got = jax.grad(kernel, argnums=(0, 1, 2, 3, 4))(x, tv, w1, w2, w3)
    want = jax.grad(oracle, argnums=(0, 1, 2, 3, 4))(x, tv, w1, w2, w3)
    for g, w in zip(got, want):
        assert np.abs(np.asarray(w)).max() > 1e-3     # a gradient, not zeros
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-3)


def check_rows_gradient_parity():
    """The expert-parallel training call: ``moe_ffn_gmm_rows`` on the
    receiving shard, a row an expert, against a plain loop over the rows."""
    x, _, ti, _, w1, w2, w3 = odd_case()
    experts = ti[:, 0].astype(jnp.int32)

    def kernel(x, w1, w2, w3):
        return jnp.sum(gg.moe_ffn_gmm_rows(
            x, experts, w1, w2, w3, n_experts=E_ODD, dtype=jnp.float32,
            interpret=True) ** 2)

    def oracle(x, w1, w2, w3):
        h = (jax.nn.silu(jnp.einsum("rd,rdf->rf", x, w1[experts]))
             * jnp.einsum("rd,rdf->rf", x, w3[experts]))
        return jnp.sum(jnp.einsum("rf,rfd->rd", h, w2[experts]) ** 2)

    np.testing.assert_allclose(float(kernel(x, w1, w2, w3)),
                               float(oracle(x, w1, w2, w3)), rtol=2e-3)
    got = jax.grad(kernel, argnums=(0, 1, 2, 3))(x, w1, w2, w3)
    want = jax.grad(oracle, argnums=(0, 1, 2, 3))(x, w1, w2, w3)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-3)


def check_tiles_and_grid_steps():
    """The three calls' tiles are their own GEMM's, the registry's record
    shows both shapes', and ``gmm_grid_steps`` is the grid megablox's own
    ``make_group_metadata`` builds for them."""
    import importlib
    import jax.experimental.pallas.ops.tpu.megablox as megablox
    backend = importlib.import_module(megablox.__name__ + ".gmm")
    calls, real = [], megablox.gmm

    def spy(lhs, rhs, group_sizes, **kw):
        calls.append((lhs.shape, rhs.shape, np.asarray(group_sizes),
                      kw["tiling"]))
        return real(lhs, rhs, group_sizes, **kw)

    x, tv, ti, valid, w1, w2, w3 = odd_case()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(megablox, "gmm", spy)
        moe_ffn_gmm(x, tv, ti, w1, w2, w3, n_experts=E_ODD,
                    dtype=jnp.float32, valid=valid, interpret=True)
    up, down = (128, D_ODD, F_ODD), (128, F_ODD, D_ODD)
    assert [c[3] for c in calls] == [up, up, down]
    assert registry.active_kernel_configs()["moe_ffn_gmm"] == {
        "tile_m": 128, "up_tile_k": D_ODD, "up_tile_n": F_ODD,
        "down_tile_k": F_ODD, "down_tile_n": D_ODD, "source": "ladder"}
    rows = int(valid.sum()) * K_ODD
    for (m, k), (_, _, n), sizes, tiling in calls:
        assert m == 128 and int(sizes.sum()) == rows == 30  # rows padded to tile_m
        assert k % tiling[1] == 0 and n % tiling[2] == 0
        for tm, tk, tn in (tiling, (128, 128, 128), (32, 128, n)):
            m_pad = -(-m // tm) * tm
            (_, group_ids, _), visits = backend.make_group_metadata(
                group_sizes=jnp.asarray(sizes), m=m_pad, tm=tm,
                start_group=jnp.int32(0), num_nonzero_groups=E_ODD,
                visit_empty_groups=False)
            steps = gg.gmm_grid_steps(m, E_ODD, k, n, (tm, tk, tn))
            assert steps == group_ids.size * (k // tk) * (n // tn)
            assert int(visits) * (k // tk) * (n // tn) <= steps
    assert gg.gmm_grid_steps(30, E_ODD, D_ODD, F_ODD, up) == 8      # was 8 x 6
    assert gg.gmm_grid_steps(30, E_ODD, D_ODD, F_ODD, (128,) * 3) == 48


@pytest.mark.parametrize("check", [
    check_forward_parity, check_gradient_parity, check_rows_gradient_parity,
    check_tiles_and_grid_steps], ids=lambda f: f.__name__[6:])
def test_a_tiling_a_gemm(check):
    check()


MELLUM2 = (2304, 896, 64)       # hidden, expert width, experts
MIXTRAL = (4096, 14336, 8)
TUNER = (128, 256, 4)           # kernel_table.BENCH_SHAPES


@pytest.mark.parametrize("rows,widths,itemsize,steps_a_visit", [
    (512, MELLUM2, 2, 1), (4096, MELLUM2, 2, 1), (512, MELLUM2, 4, 2),
    (128, MIXTRAL, 2, 28), (1024, MIXTRAL, 4, 56), (128, TUNER, 2, 1)],
    ids=["mellum2-decode", "mellum2-chunk", "mellum2-f32", "mixtral-decode",
         "mixtral-f32", "tuner"])
def test_the_rule_by_arithmetic(rows, widths, itemsize, steps_a_visit):
    """No kernel runs: each GEMM's tiles divide ITS widths in multiples of
    128, the VMEM reckoning stays under the budget, and a visited group costs
    the fewest steps the budget allows (at Mellum2's widths the whole expert
    in one block; never megablox's 7 x 18 = 126)."""
    d, f, experts = widths
    for k, n in ((d, f), (f, d)):
        tiling = tm, tk, tn = gg.gmm_tiling(k, n, itemsize)
        assert tm == 128 and tk % 128 == 0 and tn % 128 == 0
        assert k % tk == 0 and n % tn == 0
        assert gg.gmm_vmem_bytes(tiling, itemsize) <= gg.VMEM_BUDGET
        assert (k // tk) * (n // tn) == steps_a_visit <= (k // 128) * (n // 128)
        visits = -(-rows // 128) + experts - 1
        assert gg.gmm_grid_steps(rows, experts, k, n, tiling) == visits * steps_a_visit
        every = gg.gmm_tilings(k, n, itemsize)
        assert every[0] == tiling and every[-1] == (128, 128, 128)
        assert all(gg.gmm_vmem_bytes(t, itemsize) <= gg.VMEM_BUDGET for t in every)
        # a wider block of either kind would not have fit
        assert all((k // t[1]) * (n // t[2]) >= steps_a_visit for t in every)
    blocks = gg.ffn_blocks(gg.gmm_tiling(d, f, itemsize), gg.gmm_tiling(f, d, itemsize))
    assert gg._tiling_fits(blocks, d, f)
    if widths == MELLUM2 and itemsize == 2:
        assert gg.ffn_tilings(blocks) == ((128, 2304, 896), (128, 896, 2304))
        swapped = gg.ffn_blocks(*reversed(gg.ffn_tilings(blocks)))
        assert not gg._tiling_fits(swapped, d, f)   # a GEMM's tiles are its own
        assert gg.gmm_grid_steps(512, 64, d, f, (128, 128, 128)) == 67 * 126
