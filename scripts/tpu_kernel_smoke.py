"""Real-hardware smoke test for every Pallas kernel in the tree.

Interpret-mode (CPU) tests validate numerics but NOT Mosaic lowering — block
shapes that violate the (8, 128) tiling rules only fail on a real TPU. This
script compiles and runs each kernel on the attached chip and checks numerics
against its pure-XLA twin. Run it after touching any kernel:

    python scripts/tpu_kernel_smoke.py [--only KERNEL]

One process, every kernel in turn; needs a TPU and exits non-zero when a
kernel fails to compile or to match. (``chip_smoke.py`` at the repo root is
the end-to-end proof; this is the per-kernel one, at small shapes.)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

FAILED = []


def check(name, got, want, atol, rtol=1e-2):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.max(np.abs(got - want))
    ok = np.allclose(got, want, atol=atol, rtol=rtol)
    print(f"{'PASS' if ok else 'FAIL'} {name}: max err {err:.4g}", flush=True)
    if not ok:
        FAILED.append(name)


def smoke_flash():
    from deepspeed_tpu.ops.flash_attention import mha_reference
    from deepspeed_tpu.ops.pallas.flash_attention import flash_mha

    B, T, H, Dh = 2, 512, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, Dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, T, H, Dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, H, Dh), jnp.bfloat16)
    out = jax.jit(lambda q, k, v: flash_mha(q, k, v, causal=True))(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    check("flash_mha fwd", out, ref, atol=0.05)

    def loss(f):
        return lambda q, k, v: jnp.sum(
            f(q, k, v, causal=True).astype(jnp.float32) ** 2)

    g = jax.jit(jax.grad(loss(flash_mha), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    for n, a, b in zip("qkv", g, gr):
        scale = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) or 1.0
        check(f"flash_mha d{n}", np.asarray(a) / scale, np.asarray(b) / scale,
              atol=0.05)

    # sliding window (in-kernel block skip + DMA-clamped index maps) — the
    # clamped index maps are traced scalar programs that must lower on Mosaic
    out_w = jax.jit(lambda q, k, v: flash_mha(q, k, v, causal=True,
                                              window=128))(q, k, v)
    ref_w = mha_reference(q, k, v, causal=True, window=128)
    check("flash_mha window fwd", out_w, ref_w, atol=0.05)
    gw = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_mha(q, k, v, causal=True, window=128)
                                .astype(jnp.float32) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    gwr = jax.grad(
        lambda q, k, v: jnp.sum(mha_reference(q, k, v, causal=True,
                                              window=128)
                                .astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for n, a, b in zip("qkv", gw, gwr):
        scale = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) or 1.0
        check(f"flash_mha window d{n}", np.asarray(a) / scale,
              np.asarray(b) / scale, atol=0.05)

    # packed-sequence segment ids (lane-/sublane-replicated tile layouts)
    rng = np.random.default_rng(0)
    cuts = np.sort(rng.choice(np.arange(1, T), size=3, replace=False))
    seg = jnp.asarray(np.searchsorted(cuts, np.arange(T), side="right")
                      [None, :].repeat(B, axis=0).astype(np.int32))
    out_s = jax.jit(lambda q, k, v: flash_mha(q, k, v, causal=True,
                                              segment_ids=(seg, seg)))(q, k, v)
    ref_s = mha_reference(q, k, v, causal=True, segment_ids=(seg, seg))
    check("flash_mha segments fwd", out_s, ref_s, atol=0.05)


def smoke_paged():
    from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
        _paged_attention_dense)
    from deepspeed_tpu.ops.pallas.paged_attention import paged_mha

    S, Q, H, KV, Dh, NB, bs, MB = 3, 2, 4, 2, 64, 10, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (S, Q, H, Dh), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (NB, KV, bs, Dh), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (NB, KV, bs, Dh), jnp.bfloat16)
    rng = np.random.default_rng(0)
    bt = jnp.asarray(rng.permutation((NB - 1) * MB)[: S * MB]
                     .reshape(S, MB) % (NB - 1), jnp.int32)
    seen = jnp.asarray(rng.integers(0, MB * bs - Q, size=S), jnp.int32)
    q_len = jnp.full((S,), Q, jnp.int32)
    out = jax.jit(paged_mha)(q, kp, vp, bt, seen, q_len)
    ref = _paged_attention_dense(q, kp, vp, bt, seen, bs)
    mask = np.arange(Q)[None, :] < np.asarray(q_len)[:, None]
    check("paged_mha decode", np.asarray(out)[mask], np.asarray(ref)[mask],
          atol=0.05)

    # the walk over live pages (heads of 128): a row has 1-50 pages live, the
    # last row is padding; a ragged [8, 8] dispatch (a verify round) over a
    # 256-slot table and a [64, 1] one (a decode round: 4 query rows a KV
    # head, under a sublane tile) over the cells' 64 slots; every dead slot
    # points at a page of NaN, which the dense twin reads zeroed and the
    # kernel must never read
    H, KV, Dh, bs = 32, 8, 128, 64
    for S, Q, MB in ((8, 8, 256), (64, 1, 64)):
        live = rng.integers(1, 51, size=S)
        NB = int(live.sum()) + 2
        poison, trash = NB - 2, NB - 1
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (S, Q, H, Dh), jnp.bfloat16)
        kp = jax.random.normal(ks[1], (NB, KV, bs, Dh), jnp.bfloat16)
        vp = jax.random.normal(ks[2], (NB, KV, bs, Dh), jnp.bfloat16)
        bt = np.full((S, MB), poison, np.int32)
        pages = rng.permutation(NB - 2)
        for i, n in enumerate(live):
            bt[i, :n], pages = pages[:n], pages[n:]
        q_len = rng.integers(1, Q + 1, size=S).astype(np.int32)
        seen = (live * bs - q_len - rng.integers(0, bs - Q, size=S)).astype(np.int32)
        bt[-1], seen[-1], q_len[-1] = trash, 0, 0
        nan = lambda pool: pool.at[poison].set(jnp.nan)
        out = jax.jit(paged_mha)(q, nan(kp), nan(vp), bt, seen, q_len)
        zero = lambda pool: pool.at[poison].set(0)
        ref = _paged_attention_dense(q, zero(kp), zero(vp), jnp.asarray(bt),
                                     jnp.asarray(seen), bs)
        mask = np.arange(Q)[None, :] < q_len[:, None]
        assert np.isfinite(np.asarray(out, np.float32)).all(), \
            "paged_mha read past a row's live pages"
        check(f"paged_mha ragged walk [{S}, {Q}], table of {MB}",
              np.asarray(out)[mask], np.asarray(ref)[mask], atol=0.05)


def smoke_block_sparse():
    from deepspeed_tpu.ops.pallas.block_sparse_attention import sparse_mha
    from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import (
        sparse_attention)

    B, H, S, D, block = 2, 4, 1024, 64, 128
    nq = S // block
    rng = np.random.default_rng(2)
    layout = (rng.random((H, nq, nq)) < 0.4)
    layout |= np.eye(nq, dtype=bool)[None]
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.bfloat16)
    out = sparse_mha(q, k, v, layout.astype(np.int32), block, causal=True)
    ref = sparse_attention(q, k, v, layout.astype(np.int32), block,
                           causal=True)
    check("sparse_mha fwd", out, ref, atol=0.05)


def smoke_grouped_gemm():
    from deepspeed_tpu.inference.v2.model_implementations.moe_layer import (
        moe_ffn)
    from deepspeed_tpu.ops.pallas.grouped_gemm import moe_ffn_gmm, topk_router

    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    T, D, F, E, k = 40, 128, 256, 4, 2
    x = jax.random.normal(ks[0], (T, D), jnp.bfloat16)
    gate = jax.random.normal(ks[1], (D, E), jnp.float32) * 0.3
    w1 = jax.random.normal(ks[2], (E, D, F), jnp.bfloat16) * 0.05
    w2 = jax.random.normal(ks[3], (E, F, D), jnp.bfloat16) * 0.05
    w3 = jax.random.normal(ks[4], (E, D, F), jnp.bfloat16) * 0.05
    tv, ti = topk_router(x, gate, k)
    out = jax.jit(lambda *a: moe_ffn_gmm(*a, n_experts=E, dtype=jnp.bfloat16))(
        x, tv, ti, w1, w2, w3)
    ref = moe_ffn(x, gate, w1, w2, w3, k=k, dtype=jnp.bfloat16,
                  force_einsum=True)
    check("moe_ffn_gmm", out, ref, atol=0.05)


def smoke_quantized_matmul():
    from deepspeed_tpu.inference.quantization.quantization import (
        QuantizedParameter)
    from deepspeed_tpu.ops.pallas.quantized_matmul import quantized_matmul

    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(ks[0], (16, 512), jnp.bfloat16)
    w = np.asarray(jax.random.normal(ks[1], (512, 256), jnp.float32)) * 0.1
    qp = QuantizedParameter.from_array(w, num_bits=8, group_size=128)
    out = jax.jit(lambda a, q, s: quantized_matmul(a, q, s, 128))(
        x, qp.q, qp.scale)
    ref = x @ qp.dequantized(jnp.bfloat16)
    check("quantized_matmul", out, ref, atol=0.1)


SMOKES = {"flash": smoke_flash, "paged": smoke_paged,
          "block_sparse": smoke_block_sparse,
          "grouped_gemm": smoke_grouped_gemm,
          "quantized_matmul": smoke_quantized_matmul}


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=sorted(SMOKES),
                    help="run a single kernel smoke")
    args = ap.parse_args()

    devs = jax.devices()
    print("devices:", devs, flush=True)
    if devs[0].platform != "tpu":
        sys.exit(f"tpu_kernel_smoke: needs a TPU, found {devs[0].platform!r}")
    for name in ([args.only] if args.only else SMOKES):
        print(f"== {name}", flush=True)
        SMOKES[name]()
    if FAILED:
        print("FAILED:", FAILED, flush=True)
        sys.exit(1)
    print("all kernels lower and match on TPU", flush=True)


if __name__ == "__main__":
    main()
