"""``model_implementations/paged_layer.py``: the one place a ragged forward
touches its paged cache and picks its kernel, and the three call sites that
choose a Pallas kernel from what they can observe (Pallas on, shapes tile)
with a dispatch record for the other way out."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.v2.model_implementations import (
    mixtral, moe_layer, opt, paged_layer, parallel_block)


@pytest.fixture
def dispatch(monkeypatch):
    """Interpret-mode kernels and the dispatch records of this test alone:
    ``records()`` -> {(kernel, outcome, reason)} without the tuning rows and
    the single-device ones of the kernel's own dispatch."""
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("DS_TPU_DISABLE_PALLAS", raising=False)
    telemetry.reset()
    telemetry.configure(enabled=True)
    yield lambda: {k for k in telemetry.get_telemetry().dispatch_stats
                   if k[1] != "tuning" and k[2] != "no_mesh"}
    telemetry.configure(enabled=False)
    telemetry.reset()


def _traces_kernel(fn, *args):
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


# -- which kernel reads the pages ---------------------------------------------

@pytest.mark.parametrize("block_size,disable,kernel,record", [
    (8, False, True, None),
    (12, False, False, ("paged_mha", "fallback", "unsupported_shape")),
    (8, True, False, ("paged_mha", "fallback", "no_tpu")),
], ids=["tileable", "block-size-12", "pallas-disabled"])
def test_paged_attention_choice(dispatch, monkeypatch, block_size, disable,
                                kernel, record):
    """The kernel for shapes it tiles, the dense twin for a block size it
    refuses and with Pallas off, each way out with its record and reason;
    either way the dense twin's numbers."""
    if disable:
        monkeypatch.setenv("DS_TPU_DISABLE_PALLAS", "1")
    rng = np.random.default_rng(0)
    S, Q, H, KV, Dh, NB, MB = 3, 2, 4, 2, 64, 10, 3
    q = jnp.asarray(rng.normal(size=(S, Q, H, Dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(NB, KV, block_size, Dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(NB, KV, block_size, Dh)), jnp.float32)
    tables = jnp.asarray(rng.permutation(NB - 1)[:S * MB].reshape(S, MB),
                         jnp.int32)
    seen = jnp.asarray([0, 5, block_size + 1], jnp.int32)
    q_len = jnp.asarray([2, 1, 2], jnp.int32)

    def read(q, kp, vp):
        return paged_layer._paged_attention(q, kp, vp, tables, seen,
                                            block_size, q_len, window=None)

    assert _traces_kernel(read, q, kp, vp) == kernel
    assert dispatch() == ({record} if record else set())
    want = paged_layer._paged_attention_dense(q, kp, vp, tables, seen,
                                              block_size)
    valid = np.arange(Q)[None, :] < np.asarray(q_len)[:, None]
    np.testing.assert_allclose(np.asarray(read(q, kp, vp))[valid],
                               np.asarray(want)[valid], atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("d_model,d_ff,kernel", [(128, 256, True),
                                                 (96, 256, False)])
def test_moe_ffn_choice(dispatch, d_model, d_ff, kernel):
    """The grouped GEMM for 128-tileable dims, the einsum otherwise; the
    einsum is the oracle of both."""
    rng = np.random.default_rng(1)
    T, E = 8, 4
    x = jnp.asarray(rng.normal(size=(T, d_model)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(d_model, E)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.normal(size=(E, d_model, d_ff)) / 8, jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(E, d_ff, d_model)) / 8, jnp.float32)

    def ffn(x, **kw):
        return moe_layer.moe_ffn(x, gate, w1, w2, w3, k=2, dtype=jnp.float32,
                                 **kw)

    assert _traces_kernel(ffn, x) == kernel
    assert dispatch() == (set() if kernel else
                          {("moe_ffn_gmm", "fallback", "unsupported_shape")})
    assert not _traces_kernel(lambda x: ffn(x, force_einsum=True), x)
    np.testing.assert_allclose(np.asarray(ffn(x)),
                               np.asarray(ffn(x, force_einsum=True)),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("shape,group,kernel", [((512, 512), 128, True),
                                                ((2, 128, 128), 64, False)],
                         ids=["2d-kernel", "3d-dense"])
def test_quantized_matmul_choice(dispatch, shape, group, kernel):
    """The fused dequant-GEMM for a 2-D weight it tiles, in parity with
    dequantize-then-matmul; a 3-D weight takes the dense path."""
    from deepspeed_tpu.inference.quantization import quantize_param_tree
    w = np.random.default_rng(0).normal(size=shape).astype(np.float32)
    qp = quantize_param_tree({"k": {"kernel": w}}, num_bits=8,
                             group_size=group)["k"]["kernel"]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(8, shape[-2])),
                    jnp.float32)
    assert _traces_kernel(qp.matmul, x) == kernel
    assert dispatch() == (
        set() if kernel else
        {("quantized_matmul", "fallback", "unsupported_shape")})
    np.testing.assert_allclose(np.asarray(qp.matmul(x)),
                               np.asarray(x @ qp.dequantized(x.dtype)),
                               rtol=2e-2, atol=2e-2)


# -- the layout: what a forward writes, and where -----------------------------

def _mixtral():
    from deepspeed_tpu.models.mixtral import MixtralConfig, MixtralForCausalLM
    cfg = MixtralConfig.tiny(remat=False, dtype=jnp.float32)
    return mixtral, MixtralForCausalLM(cfg)


def _opt(scan_layers):
    from deepspeed_tpu.models.opt import OPTConfig, OPTForCausalLM
    cfg = OPTConfig.tiny(scan_layers=scan_layers, remat=False,
                         dtype=jnp.float32)
    return opt, OPTForCausalLM(cfg)


def _parallel(config):
    from deepspeed_tpu.models.parallel_block import ParallelBlockForCausalLM
    return parallel_block, ParallelBlockForCausalLM(
        config(remat=False, dtype=jnp.float32))


def _falcon():
    from deepspeed_tpu.models.falcon import tiny_falcon_config
    return _parallel(tiny_falcon_config)


def _phi():
    from deepspeed_tpu.models.phi import tiny_phi_config
    return _parallel(tiny_phi_config)


FAMILIES = {"mixtral": _mixtral, "opt-scan": lambda: _opt(True),
            "opt-layers": lambda: _opt(False), "falcon": _falcon,
            "phi": _phi}

BS, NB, MB = 8, 6, 3          # tokens a page, pages a layer (+1 trash), table
SENTINEL = 77


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    impl, model = FAMILIES[request.param]()
    ids = np.zeros((1, 8), np.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    return impl, model.config, params


def _pools(cfg, int8):
    """Stacked K and V pools [L, NB+1, KV, BS, Dh] holding SENTINEL (pairs
    with their scale pools when ``int8``)."""
    from deepspeed_tpu.inference.v2.ragged.cache_groups import homogeneous
    g, = homogeneous(cfg)
    shape = (g.layers, NB + 1, g.kv_heads, BS, g.head_dim)

    def pool():
        if not int8:
            return jnp.full(shape, SENTINEL, jnp.float32)
        return (jnp.full(shape, SENTINEL, jnp.int8),
                jnp.full(shape[:3] + (1, BS), SENTINEL, jnp.float32))
    return pool(), pool()


def _mixed_batch(cfg):
    """A prompt chunk across a page boundary, a decode row deep in its
    second page, a first chunk of 3 and a padded row (no tokens, a table of
    trash pages): rows x 8 positions, most of them padding."""
    rng = np.random.default_rng(5)
    q_len = np.asarray([8, 1, 3, 0], np.int32)
    seen = np.asarray([5, 13, 0, 0], np.int32)
    tables = np.full((4, MB), NB, np.int32)
    tables[0, :2], tables[1, :2], tables[2, :1] = [4, 1], [0, 5], [2]
    tokens = rng.integers(0, cfg.vocab_size, (4, 8)).astype(np.int32)
    return tokens, q_len, seen, tables


@pytest.mark.parametrize("int8", [False, True], ids=["fp", "int8"])
def test_forward_writes_the_pages_of_a_layer_by_layer_write(
        family, int8, monkeypatch):
    """One forward over a mixed batch leaves the stacked pools as writing
    each layer's K and V rows into THAT layer's pool, page by page through
    the sequence's own table, leaves them: every real token in its page and
    slot, no padded position in a page of any sequence (the layer's trash
    page may hold any value), every other page as it was."""
    impl, cfg, params = family
    tokens, q_len, seen, tables = _mixed_batch(cfg)
    wrote = []                       # a layer's (k, v) rows, in layer order
    scatter = impl._scatter_kv

    def recording(k_pool, v_pool, k, v, *rest, **kw):
        wrote.append((np.asarray(k), np.asarray(v)))
        return scatter(k_pool, v_pool, k, v, *rest, **kw)

    monkeypatch.setattr(impl, "_scatter_kv", recording)
    with jax.disable_jit():          # the loop's values, not its tracers
        _, cache = impl.ragged_forward(
            cfg, params, {"kv": _pools(cfg, int8)}, jnp.asarray(tokens),
            jnp.asarray(q_len), jnp.asarray(seen), {"kv": jnp.asarray(tables)})
    assert len(wrote) == cfg.num_hidden_layers

    # the layer-by-layer write, in numpy, on dequantised pools
    shape = np.shape(_pools(cfg, False)[0])
    blank = float(SENTINEL * (SENTINEL if int8 else 1))   # int8 x its scale
    want = [np.full(shape, blank, np.float32) for _ in "kv"]
    touched = np.zeros(shape[:2], bool)
    slots = np.zeros(shape[:2] + (BS,), bool)     # the real tokens' slots
    for layer, rows in enumerate(wrote):
        touched[layer, NB] = True                 # its trash page: any value
        for s in range(len(q_len)):
            for t in range(q_len[s]):
                page = tables[s, (seen[s] + t) // BS]
                for pool, row in zip(want, rows):
                    pool[layer, page, :, (seen[s] + t) % BS] = row[s, t]
                touched[layer, page] = True
                slots[layer, page, (seen[s] + t) % BS] = True

    for got, ref in zip(cache["kv"], want):
        data, scale = paged_layer._pool_parts(got)
        assert data.shape == shape
        data = np.asarray(data, np.float32)
        tol = np.zeros_like(data)
        if int8:                     # a written row is off by half a step
            scale = np.swapaxes(np.asarray(scale), -1, -2)   # [.., BS, 1]
            assert (scale[~touched] == SENTINEL).all()
            data, tol = data * scale, 0.51 * scale + tol
        real = touched.copy()
        real[:, NB] = False
        assert (np.abs(data - ref)[real] <= tol[real]).all()
        assert (data[~touched] == blank).all()
        # no padded position landed in a page of any sequence: the slots
        # written there are the real tokens' and no other (the layer's own
        # trash page may hold any value)
        raw = np.asarray(paged_layer._pool_parts(got)[0])
        assert ((raw != SENTINEL).any(axis=(2, 4))[real] == slots[real]).all()


# -- the write's forms: pages through the block table, or rows -----------------

def _write_case(S, Q, KV, W, bs, seen, q_len, dtype=jnp.bfloat16, held=None,
                width=None):
    """A dispatch's ``[S, Q]`` slots over pools of random content: row ``s``
    holds ``held[s]`` pages (what ``seen + q_len`` needs where not given),
    the rest of its table names the trash page, the last of the pool."""
    need = [-(-(a + b) // bs) for a, b in zip(seen, q_len)]
    held = need if held is None else held
    width = width or max(need) + 1
    NB = sum(held) + 3
    tables = np.full((S, width), NB, np.int32)
    pages = iter(np.random.default_rng(1).permutation(NB))
    for s in range(S):
        tables[s, :held[s]] = [next(pages) for _ in range(held[s])]
    return dict(S=S, Q=Q, KV=KV, W=W, bs=bs, dtype=dtype, NB=NB, tables=tables,
                seen=np.asarray(seen, np.int32), q_len=np.asarray(q_len, np.int32))


_WRITES = {
    # [64, 1]: every offset of a page among the rows, first slots and last
    "decode-64x1": lambda: _write_case(64, 1, 8, 128, 64, list(range(62, 126)), [1] * 64),
    "decode-4x1-f32": lambda: _write_case(4, 1, 4, 128, 64, [0, 1, 63, 130], [1] * 4,
                                          jnp.float32),
    "chunk-1x16-crosses-a-page": lambda: _write_case(1, 16, 8, 128, 64, [63], [16]),
    "chunk-1x16-inside-a-page": lambda: _write_case(1, 16, 1, 128, 64, [1], [9]),
    # starts and ends inside pages, q_len < Q: a closed cell's usual chunk
    "chunk-1x512-mid-pages": lambda: _write_case(1, 512, 8, 128, 64, [300], [449]),
    "chunk-1x512-whole-pages": lambda: _write_case(1, 512, 4, 128, 64, [64], [512],
                                                   jnp.float32),
    # the row's last pages are past what it holds and past the table's width
    "chunk-1x512-past-the-allocation": lambda: _write_case(1, 512, 8, 128, 64, [1], [400],
                                                           width=8),
    # a prompt's tail, a first chunk, a padded row (a table of trash pages)
    # and a decode row in one verify-shaped dispatch
    "mixed-4x8": lambda: _write_case(4, 8, 8, 128, 64, [63, 0, 0, 77], [8, 3, 0, 1]),
    "mixed-4x8-kv4-bs8": lambda: _write_case(4, 8, 4, 64, 8, [7, 8, 0, 1], [8, 8, 0, 5],
                                             jnp.float32),
    "nothing-real-2x8": lambda: _write_case(2, 8, 4, 128, 64, [0, 70], [0, 0], held=[0, 2]),
    # a page of one leaf, and the index leaf
    "latent-1x512-w640": lambda: _write_case(1, 512, 1, 640, 64, [129], [500]),
    "latent-64x1-w640": lambda: _write_case(64, 1, 1, 640, 64, list(range(0, 128, 2)),
                                            [1] * 64),
    "latent-4x8-w640-f32": lambda: _write_case(4, 8, 1, 640, 64, [63, 0, 0, 200], [8, 1, 0, 8],
                                               jnp.float32),
    "index-1x16-w128": lambda: _write_case(1, 16, 1, 128, 64, [63], [16]),
    "index-4x8-w128": lambda: _write_case(4, 8, 1, 128, 64, [63, 0, 0, 77], [8, 3, 0, 1]),
}


def _written(pool, rows, c):
    """``rows`` [S, Q, KV, W] at their pages and slots of ``pool``, in numpy."""
    want = np.array(pool)
    for s in range(c["S"]):
        for t in range(c["q_len"][s]):
            pos = c["seen"][s] + t
            want[c["tables"][s, pos // c["bs"]], :, pos % c["bs"]] = rows[s, t]
    return want


@pytest.mark.parametrize("form", ["rows", "pages", "rule"])
@pytest.mark.parametrize("case", sorted(_WRITES))
def test_every_form_of_the_write_leaves_the_pages_the_row_wise_scatter_leaves(
        case, form, monkeypatch):
    """The row-wise scatter (the twin), the page-wise form and whichever the
    rule picks for the shapes, through the entry point its leaf has: bit for
    bit the same on every real slot, on every other slot of the pages a row
    fills and on every page no real slot touches. Only the trash page may
    differ."""
    c = _WRITES[case]()
    if form != "rule":
        monkeypatch.setattr(paged_layer, "writes_pages", lambda *a: form == "pages")
    rng = np.random.default_rng(2)
    S, Q, KV, W, NB, dt = (c[k] for k in ("S", "Q", "KV", "W", "NB", "dtype"))
    pool = lambda: jnp.asarray(rng.normal(size=(NB + 1, KV, c["bs"], W)), dt)
    rows = lambda w=W: jnp.asarray(rng.normal(size=(S, Q, KV, w)), dt)
    args = (jnp.asarray(c["tables"]), jnp.asarray(c["seen"]), jnp.asarray(c["q_len"]),
            c["bs"], NB)
    if case.startswith("latent"):
        pools, new = (pool(),), (rows(),)
        got = (paged_layer._scatter_latent(pools[0], new[0][:, :, 0], *args),)
    elif case.startswith("index"):
        # the key fills half of the row; zeros behind it
        pools, keys = (pool(),), rows(W // 2)
        new = (jnp.pad(keys, ((0, 0),) * 3 + ((0, W // 2),)),)
        got = (paged_layer._scatter_index(pools[0], keys[:, :, 0], *args),)
    else:
        pools, new = (pool(), pool()), (rows(), rows())
        got = paged_layer._scatter_kv(*pools, *new, *args)
    for before, x, after in zip(pools, new, got):
        after, want = np.asarray(after), _written(np.asarray(before), np.asarray(x), c)
        assert after.dtype == want.dtype
        np.testing.assert_array_equal(after[:NB], want[:NB])


def test_int8_pages_take_the_row_wise_scatter_whatever_the_shapes(monkeypatch):
    """An ``(int8, scale)`` pair keeps the row-wise form whole, whatever the
    rule says of the shapes: the quantized rows and their scales where the
    row-wise scatter puts them, bit for bit."""
    c = _write_case(4, 8, 4, 128, 64, [63, 0, 0, 77], [8, 3, 0, 1])
    monkeypatch.setattr(paged_layer, "writes_pages", lambda *a: True)
    rng = np.random.default_rng(3)
    NB, bs = c["NB"], c["bs"]
    pool = lambda: (jnp.asarray(rng.integers(-127, 127, (NB + 1, 4, bs, 128)), jnp.int8),
                    jnp.asarray(rng.normal(size=(NB + 1, 4, 1, bs)), jnp.float32))
    pools = pool(), pool()
    new = [jnp.asarray(rng.normal(size=(4, 8, 4, 128)), jnp.bfloat16) for _ in "kv"]
    got = paged_layer._scatter_kv(*pools, *new, jnp.asarray(c["tables"]),
                                  jnp.asarray(c["seen"]), jnp.asarray(c["q_len"]), bs, NB)
    for (data, scale), x, (got_data, got_scale) in zip(pools, new, got):
        q, s = paged_layer._quantize_kv_rows(x)
        np.testing.assert_array_equal(
            np.asarray(got_data)[:NB], _written(np.asarray(data), np.asarray(q), c)[:NB])
        # a scale page is [KV, 1, bs]: the slot is its last dimension
        want = _written(np.swapaxes(np.asarray(scale), -1, -2), np.asarray(s)[..., None], c)
        np.testing.assert_array_equal(np.asarray(got_scale)[:NB],
                                      np.swapaxes(want, -1, -2)[:NB])


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def test_no_layer_of_the_pool_is_sliced_out_and_written_back(family):
    """The traced forward never updates a slice of a stacked pool
    (``pool.at[i].set(layer)``) and never hands a stacked pool to a scan as
    input or output: the pools are merged and ride the loop's carry."""
    impl, cfg, params = family
    tokens, q_len, seen, tables = _mixed_batch(cfg)
    pools = _pools(cfg, False)
    stacked = pools[0].shape
    jaxpr = jax.make_jaxpr(
        lambda p, c, *a: impl.ragged_forward(cfg, p, c, *a[:-1],
                                             {"kv": a[-1]}))(
        params, {"kv": pools}, tokens, q_len, seen, tables)
    scans = 0
    for eqn in _walk(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in ("dynamic_update_slice", "scatter"):
            assert eqn.invars[0].aval.shape != stacked, eqn
        if name == "scan":
            scans += 1
            carried = eqn.params["num_consts"] + eqn.params["num_carry"]
            through = [v.aval.shape for v in eqn.invars[carried:]] + \
                [v.aval.shape for v in eqn.outvars[eqn.params["num_carry"]:]]
            assert stacked not in through, through
    assert scans == (1 if "layers" in params else 0)


# -- a page of one leaf: the latent write and read ------------------------------

def _latent_case(S=3, Q=4, H=4, r=128, dr=16, dn=32, dv=32, bs=8, MB=6, seed=0):
    rng = np.random.default_rng(seed)
    W = 256
    n = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[0]), jnp.float32)
    NB = S * MB + 1
    tables = jnp.asarray(rng.permutation(NB - 1).reshape(S, MB), jnp.int32)
    seen = jnp.asarray([0, 5, 2 * bs + 1][:S], jnp.int32)
    q_len = jnp.asarray([Q, 1, Q - 1][:S], jnp.int32)
    # the pages as a forward leaves them: every visible token's row written
    pool = jnp.zeros((NB, 1, bs, W), jnp.float32)
    ctx = jnp.asarray(rng.normal(size=(S, MB * bs, r + dr)), jnp.float32)
    rows = jnp.concatenate([ctx, jnp.zeros((S, MB * bs, W - r - dr))], -1)
    pool = pool.at[tables].set(rows.reshape(S, MB, 1, bs, W))
    return dict(pool=pool, tables=tables, seen=seen, q_len=q_len, ctx=ctx, bs=bs, W=W,
                q_nope=jnp.asarray(rng.normal(size=(S, Q, H, dn)), jnp.float32),
                q_pe=jnp.asarray(rng.normal(size=(S, Q, H, dr)), jnp.float32),
                w_uk=n(r, H, dn), w_uv=n(r, H, dv), r=r, dr=dr, scale=(dn + dr) ** -0.5)


def _first_form(c):
    """Every head's keys and values up-projected from the latent, a plain
    masked softmax a row: what the absorbed read has to equal."""
    k_nope = jnp.einsum("stc,chd->sthd", c["ctx"][..., :c["r"]], c["w_uk"])
    v = jnp.einsum("stc,chd->sthd", c["ctx"][..., :c["r"]], c["w_uv"])
    s = (jnp.einsum("sqhd,sthd->shqt", c["q_nope"], k_nope)
         + jnp.einsum("sqhr,str->shqt", c["q_pe"], c["ctx"][..., c["r"]:])) * c["scale"]
    Q, T = s.shape[2], s.shape[3]
    qpos = c["seen"][:, None] + jnp.arange(Q)[None, :]
    s = jnp.where(jnp.arange(T)[None, None, None, :] <= qpos[:, None, :, None], s, -jnp.inf)
    return jnp.einsum("shqt,sthd->sqhd", jax.nn.softmax(s, -1), v)


def _absorbed(c, read):
    q_lat = jnp.einsum("sqhd,chd->sqhc", c["q_nope"], c["w_uk"])
    S, Q, H, _ = q_lat.shape
    q_row = jnp.concatenate(
        [q_lat, c["q_pe"], jnp.zeros((S, Q, H, c["W"] - c["r"] - c["dr"]))], -1)
    o_lat = read(q_row, c["pool"], c["tables"], c["seen"], c["bs"], c["q_len"], c["r"],
                 c["scale"])
    return jnp.einsum("sqhc,chd->sqhd", o_lat, c["w_uv"])


def _real(x, q_len):
    return np.asarray(x)[np.arange(x.shape[1])[None, :] < np.asarray(q_len)[:, None]]


@pytest.mark.parametrize("Q", [4, 1], ids=["chunk", "decode"])
@pytest.mark.parametrize("disable,record", [
    (False, None), (True, ("paged_mla", "fallback", "no_tpu"))],
    ids=["kernel", "pallas-disabled"])
def test_the_absorbed_read_is_the_first_form(dispatch, monkeypatch, disable, record, Q):
    """Absorbed through the kernel and through its dense twin, a chunk's
    rows and decode rows alike, equals the first form."""
    if disable:
        monkeypatch.setenv("DS_TPU_DISABLE_PALLAS", "1")
    c = _latent_case(Q=Q)
    want = _real(_first_form(c), c["q_len"])
    assert _traces_kernel(lambda p: _absorbed(dict(c, pool=p), paged_layer._latent_attention),
                          c["pool"]) == (not disable)
    got = _absorbed(c, paged_layer._latent_attention)
    np.testing.assert_allclose(_real(got, c["q_len"]), want, atol=2e-5)
    assert (record in dispatch()) == disable


def test_a_latent_read_the_kernel_cannot_tile_falls_back_with_a_record(dispatch):
    c = _latent_case(bs=12)
    got = _absorbed(c, paged_layer._latent_attention)
    np.testing.assert_allclose(_real(got, c["q_len"]), _real(_first_form(c), c["q_len"]),
                               atol=2e-5)
    assert ("paged_mla", "fallback", "unsupported_shape") in dispatch()


def test_the_latent_write_is_one_row_a_token_and_padding_goes_to_the_trash_page():
    rng = np.random.default_rng(0)
    S, Q, W, bs, NB = 2, 4, 256, 4, 6
    pool = jnp.zeros((NB + 1, 1, bs, W), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(S, Q, W)), jnp.float32)
    tables = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    seen, q_len = jnp.asarray([3, 0], jnp.int32), jnp.asarray([4, 2], jnp.int32)
    out = np.asarray(paged_layer._scatter_latent(pool, rows, tables, seen, q_len, bs, NB))
    np.testing.assert_array_equal(out[0, 0, 3], np.asarray(rows[0, 0]))      # token 3
    np.testing.assert_array_equal(out[1, 0, :3], np.asarray(rows[0, 1:]))    # tokens 4-6
    np.testing.assert_array_equal(out[3, 0, :2], np.asarray(rows[1, :2]))
    written = np.zeros((NB + 1, bs), bool)
    written[0, 3] = written[1, :3] = written[3, :2] = written[NB, 0] = True
    assert not out[:, 0][~written].any(), "a padded slot wrote outside the trash page"


# -- the logits gather --------------------------------------------------------

def test_a_row_of_no_tokens_reads_position_zero():
    x = jnp.arange(2 * 4 * 3, dtype=jnp.float32).reshape(2, 4, 3)
    got = paged_layer.last_token(x, jnp.asarray([0, 3], jnp.int32))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(x)[[0, 1], [0, 2]])


# -- a family is one row ------------------------------------------------------

@pytest.mark.parametrize("name,module,verify", [
    ("mixtral", "mixtral", False), ("opt-scan", "opt", False),
    ("falcon", "parallel_block", False), ("phi", "parallel_block", False)])
def test_the_factory_resolves_a_family_to_its_module(name, module, verify):
    from deepspeed_tpu.inference.v2 import engine_factory as ef
    _, model = FAMILIES[name]()
    assert ef.resolve_forward_fn(model).__module__.endswith(
        "model_implementations." + module)
    assert (ef.resolve_verify_fn(model) is not None) == verify
    by_name = ef.resolve_forward_fn(model, family="mistral")
    assert by_name.__module__.endswith("model_implementations.llama")
    assert ef.resolve_verify_fn(model, family="qwen2") is not None


def test_the_llama_forward_refuses_unstacked_layers():
    from deepspeed_tpu.inference.v2 import engine_factory as ef
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    model = LlamaForCausalLM(LlamaConfig.tiny(scan_layers=False, remat=False))
    with pytest.raises(ValueError, match="scan_layers=True"):
        ef.resolve_forward_fn(model)
    stacked = LlamaForCausalLM(dataclasses.replace(model.config,
                                                   scan_layers=True))
    assert ef.resolve_verify_fn(stacked).__name__ == "ragged_forward_verify"


# -- learned sparse attention: an indexer's key beside K and V -------------------

def _dsa_case(S=3, Q=8, H=4, KV=2, Dh=128, Hi=16, Di=16, W=128, bs=8, MB=16, ctx=None,
              seed=0):
    """Pools as a forward leaves them and a dispatch's queries: row 0 short
    (it sees at most ``Q + 2`` tokens), the others deep into their tables."""
    rng = np.random.default_rng(seed)
    NB = S * MB + 1
    r = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    tables = jnp.asarray(rng.permutation(NB - 1).reshape(S, MB), jnp.int32)
    ctx = ctx or [Q + 2, MB * bs - 3, MB * bs // 2 + 1][:S]
    return dict(
        k_pool=r(NB, KV, bs, Dh), v_pool=r(NB, KV, bs, Dh),
        i_pool=r(NB, 1, bs, W).at[..., Di:].set(0), tables=tables, bs=bs,
        q_len=jnp.full((S,), Q, jnp.int32), seen=jnp.asarray(ctx, jnp.int32) - Q,
        q=r(S, Q, H, Dh), q_idx=r(S, Q, Hi, Di), w_idx=r(S, Q, Hi))


def _chosen(scores, topk):
    """{(row, query): the set ``jax.lax.top_k`` picks of the visible keys}."""
    scores = np.asarray(scores)
    _, idx = jax.lax.top_k(jnp.asarray(scores), topk)
    idx = np.asarray(idx)
    return {(s, t): {int(n) for n in idx[s, t] if np.isfinite(scores[s, t, n])}
            for s in range(scores.shape[0]) for t in range(scores.shape[1])}


@pytest.mark.parametrize("Q", [8, 1], ids=["chunk", "decode"])
@pytest.mark.parametrize("disable", [False, True], ids=["kernel", "pallas-disabled"])
def test_the_selected_set_is_top_ks_on_the_same_scores(dispatch, monkeypatch, disable, Q):
    """Scores through the kernel and through the twin agree; the threshold
    (settled bit by bit in the kernel, the last of ``top_k`` in the twin) is
    the SAME float32, and ``scores >= tau`` among the visible keys is exactly
    the set ``jax.lax.top_k`` picks: ``topk`` tokens a query, all it sees
    where it sees fewer."""
    if disable:
        monkeypatch.setenv("DS_TPU_DISABLE_PALLAS", "1")
    c, topk = _dsa_case(Q=Q), 12
    args = (c["q_idx"], c["w_idx"], c["i_pool"], c["tables"], c["seen"], c["bs"], c["q_len"])
    assert _traces_kernel(lambda p: paged_layer._index_scores(*args[:2], p, *args[3:]),
                          c["i_pool"]) == (not disable)
    scores = paged_layer._index_scores(*args)
    twin = paged_layer._index_scores_dense(*args[:6])
    seen_keys = np.isfinite(np.asarray(twin))
    assert (np.isfinite(np.asarray(scores)) == seen_keys).all()
    visible = np.asarray(c["seen"])[:, None] + np.arange(Q)[None, :] + 1
    assert (seen_keys.sum(-1) == visible).all()                      # the causal rule
    np.testing.assert_allclose(np.asarray(scores)[seen_keys], np.asarray(twin)[seen_keys],
                               atol=1e-5)
    tau = paged_layer._select(twin, topk, c["seen"])
    assert _traces_kernel(lambda x: paged_layer._select(x, topk, c["seen"]), twin) \
        == (not disable)
    assert (np.asarray(tau) == np.asarray(paged_layer._select_dense(twin, topk))).all()
    keep = np.asarray(twin >= tau[..., None]) & seen_keys
    want = _chosen(twin, topk)
    for (s, t), members in want.items():
        assert set(np.flatnonzero(keep[s, t])) == members
        assert len(members) == min(topk, visible[s, t])
    records = dispatch()
    for kernel in ("paged_index_scores", "topk_threshold"):
        assert ((kernel, "fallback", "no_tpu") in records) == disable


@pytest.mark.parametrize("Q", [8, 1], ids=["chunk", "decode"])
@pytest.mark.parametrize("disable", [False, True], ids=["kernel", "pallas-disabled"])
def test_the_sparse_read_is_a_softmax_over_the_selected_set(dispatch, monkeypatch, disable, Q):
    """The masked walk and its dense twin against a plain softmax over the
    tokens ``top_k`` picks, heads of one KV group sharing the set."""
    if disable:
        monkeypatch.setenv("DS_TPU_DISABLE_PALLAS", "1")
    c, topk = _dsa_case(Q=Q), 12
    S, _, H, Dh = c["q"].shape
    KV, MB, bs = c["k_pool"].shape[1], c["tables"].shape[1], c["bs"]
    scores = paged_layer._index_scores_dense(c["q_idx"], c["w_idx"], c["i_pool"], c["tables"],
                                             c["seen"], bs)
    tau = paged_layer._select_dense(scores, topk)
    got = paged_layer._sparse_attention(c["q"], c["k_pool"], c["v_pool"], scores, tau,
                                        c["tables"], c["seen"], bs, c["q_len"])
    flat = lambda pool: np.asarray(pool[c["tables"]]).transpose(0, 1, 3, 2, 4) \
        .reshape(S, MB * bs, KV, Dh)
    keys, vals = flat(c["k_pool"]), flat(c["v_pool"])
    for (s, t), members in _chosen(scores, topk).items():
        at = sorted(members)
        for h in range(H):
            logits = keys[s, at, h // (H // KV)] @ np.asarray(c["q"])[s, t, h] / np.sqrt(Dh)
            p = np.exp(logits - logits.max())
            want = (p / p.sum()) @ vals[s, at, h // (H // KV)]
            np.testing.assert_allclose(np.asarray(got)[s, t, h], want, atol=3e-5)
    assert (("paged_mha", "fallback", "no_tpu") in dispatch()) == disable


def test_a_dispatch_none_of_whose_rows_passes_topk_is_the_plain_paged_read(dispatch):
    """``seen + new <= topk`` on every row: the selection is the identity and
    is not computed; the output is ``_paged_attention``'s, bit for bit. One
    row past ``topk`` and the dispatch scores, selects and reads sparsely, its
    short rows reading all they see."""
    c = _dsa_case(ctx=[10, 37, 29])
    args = (c["q"], c["q_idx"], c["w_idx"], c["k_pool"], c["v_pool"], c["i_pool"], c["tables"],
            c["seen"], c["bs"], c["q_len"])
    plain = paged_layer._paged_attention(c["q"], c["k_pool"], c["v_pool"], c["tables"],
                                         c["seen"], c["bs"], c["q_len"])
    short = paged_layer.dsa_attention(*args, 40)
    assert (np.asarray(short) == np.asarray(plain)).all()
    # a table that holds at most topk tokens never traces the indexer at all
    text = str(jax.make_jaxpr(lambda q: paged_layer.dsa_attention(q, *args[1:], 128))(c["q"]))
    assert "paged_index_scores" not in text and "topk_threshold" not in text
    sparse = paged_layer.dsa_attention(*args, 20)
    assert np.abs(np.asarray(sparse)[1:] - np.asarray(plain)[1:]).max() > 1e-3
    np.testing.assert_allclose(np.asarray(sparse)[0], np.asarray(plain)[0], atol=2e-5)


def test_sparse_reads_the_kernels_cannot_tile_fall_back_with_a_record(dispatch):
    c = _dsa_case(Q=4, bs=12, MB=6, W=128)                 # a chunk of 4, a block of 12
    c["i_pool"] = c["i_pool"][..., :64]                    # a row that fills no lane tile
    got = paged_layer.dsa_attention(c["q"], c["q_idx"], c["w_idx"], c["k_pool"], c["v_pool"],
                                    c["i_pool"], c["tables"], c["seen"], c["bs"], c["q_len"], 12)
    scores = paged_layer._index_scores_dense(c["q_idx"], c["w_idx"], c["i_pool"], c["tables"],
                                             c["seen"], c["bs"])
    want = paged_layer._paged_attention_dense(
        c["q"], c["k_pool"], c["v_pool"], c["tables"], c["seen"], c["bs"],
        keep=scores >= paged_layer._select_dense(scores, 12)[..., None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    records = dispatch()
    assert ("paged_index_scores", "fallback", "unsupported_shape") in records
    assert ("topk_threshold", "fallback", "unsupported_shape") in records
    assert ("paged_mha", "fallback", "unsupported_shape") in records


def test_the_index_write_is_one_padded_row_a_token_beside_its_k_and_v():
    bs, W, Di = 4, 128, 16
    pool = jnp.zeros((7, 1, bs, W), jnp.float32)
    keys = jnp.arange(2 * 3 * Di, dtype=jnp.float32).reshape(2, 3, Di) + 1
    tables = jnp.asarray([[2, 5], [4, 1]], jnp.int32)
    out = paged_layer._scatter_index(pool, keys, tables, jnp.asarray([3, 0]),
                                     jnp.asarray([3, 1]), bs, trash=6)
    out = np.asarray(out)
    np.testing.assert_array_equal(out[2, 0, 3, :Di], np.asarray(keys)[0, 0])   # token 3
    np.testing.assert_array_equal(out[5, 0, :2, :Di], np.asarray(keys)[0, 1:])  # tokens 4, 5
    np.testing.assert_array_equal(out[4, 0, 0, :Di], np.asarray(keys)[1, 0])
    assert not out[..., Di:].any() and not out[1].any() and not out[4, 0, 1:].any()
    assert out[6, 0, 0].any() and not out[6, 0, 1:].any()         # padding: the trash page


# -- the llama family's tree, as trained and as prepared ---------------------------
# ``llama.prepare_params`` stores q, k and v's kernels as their product reads
# them; the forward takes either tree and tells them apart by the kernel's rank

def _llama_mha():
    from deepspeed_tpu.models.llama import LlamaConfig
    return dataclasses.replace(LlamaConfig.tiny(),       # llama2's: KV heads = heads
                               num_key_value_heads=4)


def _mistral():
    from deepspeed_tpu.models.mistral import tiny_mistral_config
    return tiny_mistral_config(sliding_window=6)         # GQA under a window


def _qwen2():
    from deepspeed_tpu.models.qwen2 import tiny_qwen2_config
    return tiny_qwen2_config()                           # q, k and v biases


LLAMA_TREES = {"llama-mha": _llama_mha, "mistral-gqa-window": _mistral,
               "qwen2-bias": _qwen2}


@pytest.fixture(scope="module", params=sorted(LLAMA_TREES))
def llama_tree(request):
    """(model, the tree as trained): float32, seeded biases where it has any."""
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    cfg = dataclasses.replace(LLAMA_TREES[request.param](), scan_layers=True,
                              remat=False, dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: 0.1 * jax.random.normal(next(keys), leaf.shape, leaf.dtype)
        if path[-1].key == "bias" else leaf, params)
    return model, params


@pytest.mark.parametrize("verify", [False, True], ids=["plain", "verify"])
def test_the_llama_forward_reads_the_tree_as_trained_and_as_prepared(llama_tree, verify):
    """The same logits and the same pages from both trees, over a mixed
    batch (a chunk across a page boundary, a decode row, a first chunk, a
    padded row), through the plain forward and the verify forward (one
    trunk); the caller's tree is left as it was, and a prepared tree
    prepared again is itself."""
    from deepspeed_tpu.inference.v2.model_implementations import llama
    model, params = llama_tree
    cfg = model.config
    H, KV, Dh, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim, cfg.hidden_size)
    prepared = llama.prepare_params(cfg, params)
    attn, was = prepared["layers"]["block"]["self_attn"], params["layers"]["block"]["self_attn"]
    for name, heads in (("q_proj", H), ("k_proj", KV), ("v_proj", KV)):
        assert was[name]["kernel"].shape == (cfg.num_hidden_layers, D, heads * Dh)
        assert attn[name]["kernel"].shape == (cfg.num_hidden_layers, heads, Dh, D)
        np.testing.assert_array_equal(                   # a permutation, value for value
            np.asarray(attn[name]["kernel"]).transpose(0, 3, 1, 2).reshape(
                was[name]["kernel"].shape), np.asarray(was[name]["kernel"]))
        assert attn[name].get("bias") is was[name].get("bias")
        assert ("bias" in attn[name]) == cfg.attention_bias
    assert attn["o_proj"] is was["o_proj"] and prepared["norm"] is params["norm"]
    again = llama.prepare_params(cfg, prepared)
    assert all(a is b for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(prepared)))

    tokens, q_len, seen, tables = _mixed_batch(cfg)
    forward = (lambda *a: llama.ragged_forward_verify(*a, 3)) if verify \
        else llama.ragged_forward
    got = [forward(cfg, tree, {"kv": _pools(cfg, False)}, jnp.asarray(tokens),
                   jnp.asarray(q_len), jnp.asarray(seen), {"kv": jnp.asarray(tables)})
           for tree in (params, prepared)]
    (logits, cache), (logits_p, cache_p) = got
    assert logits.shape == ((4, 3) if verify else (4,)) + (cfg.vocab_size,)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits), rtol=1e-5, atol=1e-5)
    for pool, pool_p in zip(cache["kv"], cache_p["kv"]):
        real = np.ones(pool.shape[1], bool)
        real[NB] = False                                 # the trash page: any value
        assert (np.asarray(pool)[:, real] != SENTINEL).any()
        np.testing.assert_allclose(np.asarray(pool_p)[:, real], np.asarray(pool)[:, real],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "fp32"])
def test_the_forwards_rotary_is_the_models_value_for_value(dtype):
    """``llama.rotary_embed`` (no strided pair split: rolls and a select)
    against ``models.llama.rotary_embed``: the same bits, jitted as the
    forward runs them, at positions up to a Mistral context."""
    from deepspeed_tpu.inference.v2.model_implementations import llama
    from deepspeed_tpu.models.llama import rotary_embed
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 4, 128), jnp.float32).astype(dtype)
    positions = jnp.asarray(np.random.default_rng(0).integers(0, 4096, (3, 5)))
    for theta in (10000.0, 1000000.0):
        got = jax.jit(llama.rotary_embed)(x, positions, theta)
        want = jax.jit(rotary_embed)(x, positions, theta)
        assert got.dtype == want.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


ENGINE_LIMITS = {"state_manager": {"max_ragged_sequence_count": 4, "max_ragged_batch_size": 32,
                                   "max_context": 64, "num_kv_blocks": 16},
                 "kv_cache": {"block_size": 8, "cache_dtype": "fp32"}}


@pytest.fixture
def prepare_spans():
    """``spans()``: the attributes of this test's ``serving/prepare_params`` spans."""
    telemetry.reset()
    telemetry.configure(enabled=True)
    yield lambda: [e["args"] for e in telemetry.get_telemetry().trace_events
                   if e["name"] == "serving/prepare_params"]
    telemetry.configure(enabled=False)
    telemetry.reset()


def test_an_engine_prepares_its_tree_once_however_it_is_built(llama_tree, prepare_spans):
    """``InferenceEngineV2(...)`` alone and ``build_engine`` hold the same
    prepared leaves, each says so in ONE ``serving/prepare_params`` span
    (three leaves re-laid, their bytes), the caller's tree is the one it
    handed over, and both serve the same logits."""
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    model, params = llama_tree
    cfg = model.config
    leaves = jax.tree.leaves(params)
    alone = InferenceEngineV2(model, params, ENGINE_LIMITS)
    built = build_engine(model, params, ENGINE_LIMITS, family="mistral")
    assert all(a is b for a, b in zip(jax.tree.leaves(params), leaves))
    qkv = cfg.num_hidden_layers * cfg.hidden_size * cfg.head_dim * 4 * (
        cfg.num_attention_heads + 2 * cfg.num_key_value_heads)
    assert prepare_spans() == [{"family": "llama", "leaves": 3, "bytes": qkv}] * 2
    for a, b in zip(jax.tree.leaves(alone._params), jax.tree.leaves(built._params)):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert alone._params["layers"]["block"]["self_attn"]["q_proj"]["kernel"].ndim == 4
    prompt = np.arange(1, 12, dtype=np.int32)
    np.testing.assert_array_equal(alone.put([0], [prompt]), built.put([0], [prompt]))


def test_a_family_without_the_hook_says_it_prepared_nothing(prepare_spans):
    from deepspeed_tpu.inference.v2 import InferenceEngineV2
    _, model = FAMILIES["opt-scan"]()
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": np.zeros((1, 8), np.int32)})["params"]
    engine = InferenceEngineV2(model, params, ENGINE_LIMITS)
    assert prepare_spans() == [{"family": "opt", "leaves": 0, "bytes": 0}]
    assert all(a is b for a, b in zip(jax.tree.leaves(engine._params),
                                      jax.tree.leaves(params)))


def test_a_replica_over_tp_keeps_the_prepared_kernels_split_over_heads(llama_tree):
    """``build_replica`` shards the tree as trained (q, k and v's kernels by
    their columns: by heads) and the engine re-lays it: the prepared kernel
    is still split over its heads, and over nothing else."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.inference.v2.replica_group import build_replica
    model, params = llama_tree
    mesh, sched = build_replica(model, params, jax.devices()[:2], tp_size=2,
                                engine_config=ENGINE_LIMITS)
    attn = sched.engine._params["layers"]["block"]["self_attn"]
    for name in ("q_proj", "k_proj", "v_proj"):
        kernel = attn[name]["kernel"]
        assert kernel.sharding.is_equivalent_to(
            jax.sharding.NamedSharding(mesh, P(None, "tp", None, None)), 4), name
