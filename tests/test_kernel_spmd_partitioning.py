"""Sharded-vs-single-device numerics parity for every SPMD-dispatched Pallas
kernel (the GSPMD-partitionability tentpole).

GSPMD cannot auto-partition Mosaic kernels — compiling one under a
multi-device sharding fails with "Mosaic kernels cannot be automatically
partitioned. Please wrap the call in a shard_map." — so every Pallas kernel
wrapper routes through ``ops/registry.sharded_kernel_call``, which shard_maps
the invocation over the active mesh (``parallel/topology.use_kernel_mesh``).

These tests run the kernels in interpret mode on the 8-virtual-CPU-device
mesh and assert (a) the dispatcher really emits a ``shard_map`` (jaxpr
inspection — parity alone could pass through the unsharded fallback) and
(b) sharded output == single-device output. Real-Mosaic *lowering* of the
same dispatch layer is covered by ``scripts/aot_tpu_check.py``'s multichip
legs (tests/test_aot_tpu_lowering.py).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from deepspeed_tpu.parallel import groups, topology
from deepspeed_tpu.parallel.topology import use_kernel_mesh


def _mesh(axes, shape, devices=None):
    devices = devices if devices is not None else jax.devices()
    n = int(np.prod(shape))
    return Mesh(np.array(devices[:n]).reshape(shape), axes)


def _assert_dispatched(fn, *args):
    """The kernel call must go through shard_map (not the unsharded
    fallback) under the active mesh."""
    jaxpr = str(jax.make_jaxpr(fn)(*args))
    assert "shard_map" in jaxpr, "kernel was not routed through shard_map"


def _close(a, b, tol=0.0):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=tol, rtol=tol)


# --------------------------------------------------------------------- flash

def _flash_inputs():
    B, T, H, KV, Dh = 4, 128, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, KV, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, KV, Dh), jnp.float32)
    return q, k, v


def test_flash_fwd_bwd_parity(eight_devices):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_mha
    q, k, v = _flash_inputs()

    def loss(q, k, v):
        return jnp.sum(flash_mha(q, k, v, causal=True, interpret=True) ** 2)

    ref = flash_mha(q, k, v, causal=True, interpret=True)
    gref = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    mesh = _mesh(("dp", "tp"), (2, 2))
    with use_kernel_mesh(mesh):
        _assert_dispatched(
            lambda q, k, v: flash_mha(q, k, v, causal=True, interpret=True),
            q, k, v)
        out = flash_mha(q, k, v, causal=True, interpret=True)
        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    _close(ref, out)
    for a, b in zip(gref, g):
        _close(a, b)


def test_flash_dispatch_via_global_topology(eight_devices):
    """No explicit context: engines install the groups topology and kernels
    must pick it up (batch over dpr*dp*ep, heads over tp)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_mha
    q, k, v = _flash_inputs()
    ref = flash_mha(q, k, v, causal=True, interpret=True)
    groups.initialize(mesh_topology=topology.MeshTopology(dp=4, tp=2))
    _assert_dispatched(
        lambda q, k, v: flash_mha(q, k, v, causal=True, interpret=True),
        q, k, v)
    out = flash_mha(q, k, v, causal=True, interpret=True)
    _close(ref, out)
    # an explicit None context must disable dispatch again
    with use_kernel_mesh(None):
        jaxpr = str(jax.make_jaxpr(
            lambda q, k, v: flash_mha(q, k, v, causal=True,
                                      interpret=True))(q, k, v))
    assert "shard_map" not in jaxpr


def test_flash_no_double_wrap_inside_shard_map(eight_devices):
    """Inside an explicit shard_map (Ulysses pattern) every mesh axis is
    already manual — the dispatcher must detect that and not nest."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_mha
    from jax.sharding import PartitionSpec as P
    q, k, v = _flash_inputs()
    ref = flash_mha(q, k, v, causal=True, interpret=True)
    mesh = _mesh(("dp", "tp"), (2, 2))
    with use_kernel_mesh(mesh):
        out = jax.shard_map(
            lambda q_, k_, v_: flash_mha(q_, k_, v_, causal=True,
                                         interpret=True),
            mesh=mesh, in_specs=(P("dp"),) * 3, out_specs=P("dp"),
            check_vma=False)(q, k, v)
    _close(ref, out)


def test_flash_indivisible_falls_back(eight_devices):
    """KV heads not divisible by tp: the head role must be dropped (not
    crash, not shard unevenly); batch still shards."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_mha
    B, T, H, KV, Dh = 4, 128, 3, 3, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, T, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, KV, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, KV, Dh), jnp.float32)
    ref = flash_mha(q, k, v, causal=True, interpret=True)
    with use_kernel_mesh(_mesh(("dp", "tp"), (2, 2))):
        out = flash_mha(q, k, v, causal=True, interpret=True)
    _close(ref, out)


# --------------------------------------------------------------------- paged

@pytest.mark.parametrize("Dh", [64, 128], ids=["grid", "walk"])
def test_paged_mha_parity(eight_devices, Dh):
    """Heads of 64 take the grid kernel, heads of 128 the walk over live
    pages: under tp each sees its shard of the KV heads, under dp its rows."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_mha
    S, Q, H, KV, NB, bs, MB = 4, 2, 4, 2, 10, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (S, Q, H, Dh), jnp.float32)
    kp = jax.random.normal(ks[1], (NB, KV, bs, Dh), jnp.float32)
    vp = jax.random.normal(ks[2], (NB, KV, bs, Dh), jnp.float32)
    bt = (jnp.arange(S * MB, dtype=jnp.int32).reshape(S, MB)) % NB
    seen = jnp.array([10, 20, 30, 5], jnp.int32)
    ql = jnp.full((S,), Q, jnp.int32)
    ref = paged_mha(q, kp, vp, bt, seen, ql, interpret=True)
    with use_kernel_mesh(_mesh(("dp", "tp"), (2, 2))):
        _assert_dispatched(
            lambda *a: paged_mha(*a, interpret=True), q, kp, vp, bt, seen, ql)
        out = paged_mha(q, kp, vp, bt, seen, ql, interpret=True)
    _close(ref, out)


# -------------------------------------------------------------- block-sparse

def test_block_sparse_parity(eight_devices):
    from deepspeed_tpu.ops.pallas.block_sparse_attention import sparse_mha
    B, H, S, D, block = 4, 2, 256, 64, 128
    nq = S // block
    rng = np.random.default_rng(0)
    layout = ((rng.random((H, nq, nq)) < 0.6)
              | np.eye(nq, dtype=bool)[None]).astype(np.int32)
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(sparse_mha(q, k, v, layout, block, causal=True,
                                  interpret=True) ** 2)

    ref = sparse_mha(q, k, v, layout, block, causal=True, interpret=True)
    gref = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    # batch shards over data axes; heads stay replicated (host-side layout
    # closure is indexed by global head) — see sparse_mha
    with use_kernel_mesh(_mesh(("dp", "tp"), (2, 2))):
        _assert_dispatched(
            lambda q, k, v: sparse_mha(q, k, v, layout, block, causal=True,
                                       interpret=True), q, k, v)
        out = sparse_mha(q, k, v, layout, block, causal=True, interpret=True)
        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    _close(ref, out)
    for a, b in zip(gref, g):
        _close(a, b, tol=1e-5)


# -------------------------------------------------------------- grouped gemm

def test_grouped_gemm_parity(eight_devices):
    from deepspeed_tpu.ops.pallas.grouped_gemm import moe_ffn_gmm
    T, D, F, E, k = 64, 128, 256, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    x = jax.random.normal(ks[0], (T, D), jnp.float32)
    tv = jax.nn.softmax(jax.random.normal(ks[1], (T, k)))
    ti = jax.random.randint(ks[2], (T, k), 0, E)
    w1 = jax.random.normal(ks[3], (E, D, F)) * 0.02
    w2 = jax.random.normal(ks[4], (E, F, D)) * 0.02
    w3 = jax.random.normal(ks[5], (E, D, F)) * 0.02

    def run(x, tv, ti):
        return moe_ffn_gmm(x, tv, ti, w1, w2, w3, n_experts=E,
                           dtype=jnp.float32, interpret=True)

    ref = run(x, tv, ti)
    # tokens shard over dp AND ep jointly — the expert world is carved out
    # of the data-parallel world
    with use_kernel_mesh(_mesh(("dp", "ep"), (2, 2))):
        _assert_dispatched(run, x, tv, ti)
        out = run(x, tv, ti)
    _close(ref, out, tol=1e-5)


# ---------------------------------------------------------- quantized matmul

def test_quantized_matmul_parity(eight_devices):
    from deepspeed_tpu.ops.pallas.quantized_matmul import quantized_matmul
    M, K, N, G = 16, 512, 512, 128
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (M, K), jnp.float32)
    qw = jax.random.randint(ks[1], (K, N), -128, 127, jnp.int8)
    sc = (jax.random.uniform(ks[2], (K, N // G)) + 0.5).astype(jnp.float32)
    ref = quantized_matmul(x, qw, sc, G, interpret=True)
    # rows over dp, output features (+ scale columns) over tp: per-shard
    # N=256 == BN keeps the kernel's block constraints satisfied
    with use_kernel_mesh(_mesh(("dp", "tp"), (1, 2), jax.devices()[:2])):
        _assert_dispatched(
            lambda x, q, s: quantized_matmul(x, q, s, G, interpret=True),
            x, qw, sc)
        out = quantized_matmul(x, qw, sc, G, interpret=True)
    _close(ref, out)


def test_quantized_matmul_vetoes_bad_blocks(eight_devices):
    """tp=4 would leave per-shard N=128 < BN: the accept hook must veto the
    head role and fall back rather than emit an invalid grid."""
    from deepspeed_tpu.ops.pallas.quantized_matmul import quantized_matmul
    M, K, N, G = 16, 512, 512, 128
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(ks[0], (M, K), jnp.float32)
    qw = jax.random.randint(ks[1], (K, N), -128, 127, jnp.int8)
    sc = (jax.random.uniform(ks[2], (K, N // G)) + 0.5).astype(jnp.float32)
    ref = quantized_matmul(x, qw, sc, G, interpret=True)
    with use_kernel_mesh(_mesh(("dp", "tp"), (2, 4))):
        out = quantized_matmul(x, qw, sc, G, interpret=True)
    _close(ref, out)
