"""The main path's Pallas kernels, compiled for a described TPU v5e at the
widths ``chip_smoke.py`` runs — a couple of seconds each, no chip needed.

Interpret-mode tests cannot see what the chip's compiler refuses (a slice off
the tiling, too much fast memory, a kernel that cannot be partitioned); these
compiles can, and they guard every later PR at no chip time. A compile that
passes is a compile, not a run: ``python chip_smoke.py`` is the run.

This is the ONLY test file that loads the TPU's library: one process at a
time may hold it, so the topology is described inside a module-scoped fixture
(never while a module is imported) and every compile happens in this process.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

# the serve phase's geometry: Mistral-7B heads, pages of chip_smoke.REAL's size
H, KV, DH, PAGE, MAX_BLOCKS = 32, 8, 128, 64, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_tpu(monkeypatch):
    """Steer kernel dispatch as on the chip (kernels on, v5e tuning table)
    and keep the persistent compile cache out of it: an entry written by a
    compile-only client cannot be read back and would only warn."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setenv("DS_TPU_ASSUME_TPU", "1")
    monkeypatch.setenv("DS_TPU_KERNEL_TABLE_DEVICE", "tpu_v5e")
    monkeypatch.delenv("DS_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("DS_TPU_DISABLE_PALLAS", raising=False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "no Mosaic kernel in the compiled program"
    return compiled


def _flash_args(one_chip, b, t, h, kv, dh):
    sds = lambda n: jax.ShapeDtypeStruct((b, t, n, dh), jnp.bfloat16,
                                         sharding=one_chip)
    return sds(h), sds(kv), sds(kv)


def test_flash_forward_gpt2_step_shape(for_tpu, one_chip):
    from deepspeed_tpu.ops.flash_attention import mha
    _compile(lambda q, k, v: mha(q, k, v, causal=True),
             _flash_args(one_chip, 32, 1024, 12, 12, 64))


def test_flash_forward_backward_gpt2_step_shape(for_tpu, one_chip):
    from deepspeed_tpu.ops.flash_attention import mha

    def loss(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True).astype(jnp.float32) ** 2)

    _compile(jax.grad(loss, argnums=(0, 1, 2)),
             _flash_args(one_chip, 32, 1024, 12, 12, 64))


def test_flash_windowed_gqa_mistral_shape(for_tpu, one_chip):
    from deepspeed_tpu.ops.flash_attention import mha
    _compile(lambda q, k, v: mha(q, k, v, causal=True, window=4096),
             _flash_args(one_chip, 2, 4096, H, KV, DH))


def _paged_args(one_chip, seqs, q_tokens, int8, table=MAX_BLOCKS):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    nb = 2048 + 1
    pool = sds((nb, KV, PAGE, DH), jnp.int8 if int8 else jnp.bfloat16)
    args = [sds((seqs, q_tokens, H, DH), jnp.bfloat16), pool, pool,
            sds((seqs, table), jnp.int32), sds((seqs,), jnp.int32),
            sds((seqs,), jnp.int32)]
    if int8:
        scale = sds((nb, KV, 1, PAGE), jnp.float32)
        args += [scale, scale]
    return args


@pytest.mark.parametrize("seqs,q_tokens,int8,table", [
    (8, 8, False, MAX_BLOCKS),      # decode round, bf16 pages
    (8, 8, True, MAX_BLOCKS),       # decode round, int8 pages + fp32 scales
    (8, 512, False, MAX_BLOCKS),    # a multi-token SplitFuse chunk
    # what the benchmark's Mistral cells dispatch, over their 64-slot table:
    # every KV head a grid step at [D, 1] (4 query rows a head, under a
    # sublane tile) and at a verify round's [D, 8], one a step in row tiles
    # at [1, 512]
    (64, 1, False, 64), (4, 1, False, 64), (64, 1, True, 64),
    (64, 8, False, 64), (4, 8, False, 64), (1, 512, False, 64),
    (1, 512, True, 64),
], ids=["decode_fp", "decode_int8", "splitfuse_chunk", "cell_decode64",
        "cell_decode4", "cell_decode64_int8", "cell_verify64", "cell_verify4",
        "cell_chunk512", "cell_chunk512_int8"])
def test_paged_attention_mistral_geometry(for_tpu, one_chip, seqs, q_tokens,
                                          int8, table):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_mha

    def fn(q, kp, vp, bt, seen, q_len, ks=None, vs=None):
        return paged_mha(q, kp, vp, bt, seen, q_len, k_scale=ks, v_scale=vs,
                         window=4096)

    _compile(fn, _paged_args(one_chip, seqs, q_tokens, int8, table))


@pytest.mark.parametrize("seqs,q_tokens,heads,table", [
    (64, 1, 32, 320), (1, 512, 32, 320), (1, 16, 32, 320), (1, 128, 32, 320),
    (64, 1, 64, 144), (1, 512, 64, 144), (1, 8, 64, 144)],
    ids=["decode64", "chunk512", "chunk16", "chunk128",
         "longcat-decode64", "longcat-chunk512", "longcat-chunk8"])
def test_paged_mla_kanana2_geometry(for_tpu, one_chip, seqs, q_tokens, heads, table):
    """The latent walk at the benchmark's Kanana-2 cell: 32 query heads on a
    row of 640 columns (512 latent + 64 rotated + padding) whose first 512
    are the values, over a 320-slot table; a chunk's 16,384 query rows in
    tiles of 512 on the grid. And at the LongCat-Flash cell's: 64 heads on the
    same row over a 144-slot table (9,216 tokens), a chunk's 32,768 rows."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = sds((2049, 1, PAGE, 640), jnp.bfloat16)
    q = sds((seqs, q_tokens, heads, 640), jnp.bfloat16)
    assert pa.mla_is_supported(q.shape, pool.shape, 512)

    def fn(q, bt, seen, q_len, pool):
        return pa.paged_mla(q, pool, bt, seen, q_len, value_dim=512,
                            softmax_scale=192 ** -0.5)

    compiled = _compile(fn, (q, sds((seqs, table), jnp.int32), sds((seqs,), jnp.int32),
                             sds((seqs,), jnp.int32), pool))
    assert "paged_mla" in compiled.as_text()


@pytest.mark.parametrize("q_tokens,heads,table", [
    (512, 32, 320), (256, 32, 320), (512, 64, 144), (256, 64, 144), (1024, 32, 320)],
    ids=["chunk512", "chunk256", "longcat-chunk512", "longcat-chunk256", "chunk1024"])
def test_paged_mla_up_projecting_walk_kanana2_geometry(for_tpu, one_chip, q_tokens, heads,
                                                       table):
    """The latent walk that up-projects a trip's keys and values for the
    tile's head in VMEM, at the chunks the rule gives it: the Kanana-2 cell's
    ``[1, 512]`` and ``[1, 256]`` (32 heads of 128 + 64 query columns against
    the 640-column row, ``W_UK_h`` and ``W_UV_h`` [512, 128] a grid step, one
    head's queries a step) and the LongCat-Flash cell's (64 heads); a chunk
    of 1,024 in two tiles a head."""
    from deepspeed_tpu.inference.v2.model_implementations import paged_layer
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = sds((2049, 1, PAGE, 640), jnp.bfloat16)
    q = sds((1, q_tokens, heads, 128 + 64), jnp.bfloat16)
    w = sds((512, heads, 128), jnp.bfloat16)
    assert paged_layer.up_projects_in_walk(q_tokens, 512, 128, 128)
    assert pa.mla_is_supported((1, q_tokens, heads, 256), pool.shape, 512, up_dims=(128, 128))

    def fn(q, w_uk, w_uv, bt, seen, q_len, pool):
        return paged_layer._latent_attention_up(q, w_uk, w_uv, pool, bt, seen, PAGE, q_len,
                                                192 ** -0.5)

    compiled = _compile(fn, (q, w, w, sds((1, table), jnp.int32), sds((1,), jnp.int32),
                             sds((1,), jnp.int32), pool))
    assert _latent_walks(compiled.as_text()) == (0, 1)


def test_paged_mla_refuses_a_row_that_does_not_fill_its_lane_tiles(for_tpu, one_chip):
    """A 576-wide row (the latent's 512 + 64 as they are) is not copied by
    hand: HBM's (8, 128) tiles hold it in 640 lanes and Mosaic refuses the
    slice, so ``mla_is_supported`` sends it to the dense twin and the model
    pads its row to 640."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool, q = sds((2049, 1, PAGE, 576), jnp.bfloat16), sds((64, 1, 32, 576), jnp.bfloat16)
    assert not pa.mla_is_supported(q.shape, pool.shape, 512)
    with pytest.raises(Exception, match="aligned to tiling"):
        jax.jit(lambda q, bt, sn, ql, p: pa._paged_mla_local(
            q, p, bt, sn, ql, value_dim=512, softmax_scale=0.07)).lower(
                q, sds((64, 320), jnp.int32), sds((64,), jnp.int32),
                sds((64,), jnp.int32), pool).compile()


@pytest.mark.parametrize("seqs,q_tokens", [
    (32, 1), (4, 1), (1, 512), (1, 16), (1, 128)],
    ids=["decode32", "decode4", "chunk512", "chunk16", "chunk128"])
def test_learned_sparse_attention_keye_vl2_geometry(for_tpu, one_chip, seqs, q_tokens):
    """The three kernels of learned sparse attention at the benchmark's
    Keye-VL-2.0 cell: 16 indexer heads of 64 over index rows of 128 columns,
    a 704-slot table (45,056 tokens), the 2,048th largest of each query's
    scores, and 32 query heads of 128 walking K and V pages under that
    selection; a [D, 1] decode dispatch and a [1, C] chunk."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    from deepspeed_tpu.ops.pallas import sparse_index as si
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    table, context = 704, 704 * PAGE
    pool = sds((2049, 4, PAGE, 128), jnp.bfloat16)
    index_pool = sds((2049, 1, PAGE, 128), jnp.bfloat16)
    q = sds((seqs, q_tokens, 32, 128), jnp.bfloat16)
    q_idx = sds((seqs, q_tokens, 16, 64), jnp.bfloat16)
    w_idx = sds((seqs, q_tokens, 16), jnp.float32)
    scores = sds((seqs, q_tokens, context), jnp.float32)
    bt, row = sds((seqs, table), jnp.int32), sds((seqs,), jnp.int32)
    assert si.scores_is_supported(q_idx.shape, index_pool.shape)
    assert si.threshold_is_supported(scores.shape)
    assert pa.is_supported(q.shape, pool.shape) and pa.select_is_supported(q.shape, pool.shape)

    compiled = _compile(lambda q_, w_, bt_, sn, ql, p: si.paged_index_scores(
        q_, w_, p, bt_, sn, ql), (q_idx, w_idx, bt, row, row, index_pool))
    assert "paged_index_scores" in compiled.as_text()
    compiled = _compile(lambda x, vis: si.topk_threshold(x, vis, 2048),
                        (scores, sds((seqs, q_tokens), jnp.int32)))
    assert "topk_threshold" in compiled.as_text()
    compiled = _compile(lambda q_, k, v, bt_, sn, ql, x, tau: pa.paged_mha(
        q_, k, v, bt_, sn, ql, select=(x, tau)),
        (q, pool, pool, bt, row, row, scores, sds((seqs, q_tokens), jnp.float32)))
    assert "paged_attention" in compiled.as_text()


def test_paged_attention_walk_under_dp2_tp2(for_tpu, topo):
    """The cells' [64, 8] decode dispatch across four chips: rows over dp,
    KV heads (the pools' second dim) over tp, so each kernel walks 32 rows'
    pages for its 4 heads out of its shard of every page."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_mha
    from deepspeed_tpu.parallel.topology import use_kernel_mesh
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
    sds = lambda shape, dt, *spec: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, P(*spec)))
    pool = sds((2049, KV, PAGE, DH), jnp.bfloat16, None, "tp")
    args = (sds((64, 8, H, DH), jnp.bfloat16, "dp", None, "tp"), pool, pool,
            sds((64, 64), jnp.int32, "dp"), sds((64,), jnp.int32, "dp"),
            sds((64,), jnp.int32, "dp"))
    with use_kernel_mesh(mesh):
        _compile(lambda *a: paged_mha(*a, window=4096), args)


def test_paged_attention_narrow_head_dim_keeps_the_grid(for_tpu, one_chip):
    """Heads of 64 (OPT, Falcon, GPT-2): Mosaic cannot copy by hand out of
    a pool whose rows do not fill a lane tile, so such a pool takes the
    grid kernel, its pages fetched by the pipeline's index maps."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_mha
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = sds((512, 12, PAGE, 64), jnp.bfloat16)
    for q_tokens in (1, 8):                 # a decode round, a verify round
        _compile(paged_mha, (sds((8, q_tokens, 12, 64), jnp.bfloat16), pool,
                             pool, sds((8, 32), jnp.int32),
                             sds((8,), jnp.int32), sds((8,), jnp.int32)))


@pytest.mark.parametrize("rows,tokens", [(64, 1), (4, 1), (64, 8), (1, 16), (1, 512)],
                         ids=["decode64", "decode4", "rows64x8", "chunk16", "chunk512"])
def test_selective_scan_phi4flash_widths(for_tpu, one_chip, rows, tokens):
    """The scan at Phi-4-mini-flash's d_inner 5120 x d_state 16, at the
    shapes the engine dispatches, [D, 1] decode rows and [1, C] chunks, and
    at [64, 8]."""
    from deepspeed_tpu.ops.pallas.selective_scan import selective_scan
    di, n = 5120, 16
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    f32 = jnp.float32
    args = (sds((rows, tokens, di), jnp.bfloat16), sds((rows, tokens, di), f32),
            sds((n, di), f32), sds((rows, tokens, n), f32), sds((rows, tokens, n), f32),
            sds((di,), f32), sds((rows, n, di), f32), sds((rows,), jnp.int32))
    _compile(selective_scan, args)


@pytest.mark.parametrize("seqs,q_tokens,table", [
    (64, 1, 256), (1, 512, 17), (64, 1, 17), (1, 512, 256), (64, 8, 256),
    (64, 8, 17)],
    ids=["decode_full_layer", "chunk_window_ring", "decode_window_ring",
         "chunk_full_layer", "rows64x8_full_layer", "rows64x8_window_ring"])
def test_paged_attention_differential_pairs_geometry(for_tpu, one_chip, seqs,
                                                     q_tokens, table):
    """Phi-4-mini-flash's differential attention through the paged kernel:
    40 zero-padded query heads of 128 over 10 page rows (a PAIR of K or V
    heads each), scale 1/sqrt(64), the window layers' 17-entry ring."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_mha
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = sds((640, 10, 64, 128), jnp.bfloat16)
    args = (sds((seqs, q_tokens, 40, 128), jnp.bfloat16), pool, pool,
            sds((seqs, table), jnp.int32), sds((seqs,), jnp.int32),
            sds((seqs,), jnp.int32))

    def fn(q, kp, vp, bt, seen, q_len):
        return paged_mha(q, kp, vp, bt, seen, q_len, softmax_scale=0.125,
                         window=512 if table == 17 else None)

    _compile(fn, args)


class _Captured(Exception):
    pass


def _cell_program(name, rows, one_chip, monkeypatch, tokens=1, num_kv_blocks=256):
    """(layout, the lowered program) of the dispatch ``rows`` decoding
    sequences (or one row of ``tokens`` prompt tokens) make in the engine of
    the benchmark's configuration ``name``,
    as the engine enqueues it (``engine_v2.packed_forward``: the packed
    buffer sliced, then the family's forward), its arguments as shapes on
    ``one_chip``: the configuration's widths, layers and dtypes (weights as
    shapes only), its engine limits, a page pool of ``num_kv_blocks`` pages
    (small unless a test asks for the cell's own: the engine's pools are
    zeros on the HOST here, 7 GB at Kimi-Linear's 16,384 pages, freed with
    the test)."""
    import json
    import os
    from benchmark import harness, weights
    from benchmark.drivers import serve_phi4flash
    from deepspeed_tpu.inference.v2 import engine_v2
    from deepspeed_tpu.inference.v2.engine_factory import build_engine
    from deepspeed_tpu.models.kanana2 import Kanana2Config, Kanana2ForCausalLM
    from deepspeed_tpu.models.kimi_linear import (KimiLinearConfig,
                                                  KimiLinearForCausalLM)
    from deepspeed_tpu.models.longcat_flash import (LongcatFlashConfig,
                                                    LongcatFlashForCausalLM)
    from deepspeed_tpu.models.mellum2 import Mellum2Config, Mellum2ForCausalLM
    from deepspeed_tpu.models.mistral import MistralForCausalLM, mistral_config
    from deepspeed_tpu.models.phi4flash import (Phi4FlashConfig,
                                                Phi4FlashForCausalLM)
    with open(os.path.join(harness.ROOT, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    if cfg["driver"] == "serve_phi4flash":
        model = Phi4FlashForCausalLM(Phi4FlashConfig(
            dtype=jnp.bfloat16, **{k: cfg[k] for k in serve_phi4flash.MODEL_KEYS},
            **cfg["assumed"]["sizes"]))
    elif cfg["driver"] == "serve_mellum2":
        model = Mellum2ForCausalLM(Mellum2Config.from_hf(cfg, dtype=jnp.bfloat16))
    elif cfg["driver"] == "serve_kanana2":
        share = cfg["experts_held"]
        model = Kanana2ForCausalLM(Kanana2Config.from_hf(
            cfg, dtype=jnp.bfloat16, n_routed_experts=cfg["n_routed_experts_published"],
            experts_held=(share["first"], share["count"])))
    elif cfg["driver"] == "serve_longcat_flash":
        share = cfg["experts_held"]
        model = LongcatFlashForCausalLM(LongcatFlashConfig.from_hf(
            cfg, dtype=jnp.bfloat16, n_routed_experts=cfg["n_routed_experts_published"],
            experts_held=(share["first"], share["count"])))
    elif cfg["driver"] == "serve_kimi_linear":
        share = cfg["experts_held"]
        model = KimiLinearForCausalLM(KimiLinearConfig.from_hf(
            cfg, dtype=jnp.bfloat16, num_experts=cfg["num_experts_published"],
            experts_held=(share["first"], share["count"])))
    else:
        model = MistralForCausalLM(mistral_config(dtype=jnp.bfloat16, **{
            k: cfg[k] for k in (
                "vocab_size", "hidden_size", "intermediate_size",
                "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "max_position_embeddings",
                "sliding_window", "rms_norm_eps", "rope_theta")}))
    spec = harness.load("references", cfg["reference"]).param_spec(cfg)
    params = jax.eval_shape(lambda: weights.make_params(0, spec))
    engine = build_engine(model, params, dict(
        cfg["engine"], state_manager=dict(cfg["engine"]["state_manager"],
                                          num_kv_blocks=num_kv_blocks)))
    program, got = engine_v2.packed_forward, []

    def spy(*args):
        got.extend(args)
        raise _Captured

    monkeypatch.setattr(engine_v2, "packed_forward", spy)
    with pytest.raises(_Captured):
        engine.put(list(range(rows)), [np.zeros(tokens, np.int32)] * rows)
    forward, cfg, layout, *arrays, verify_k = got
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), tuple(arrays))
    return layout, program.lower(forward, cfg, layout, *shapes, verify_k)


@pytest.mark.parametrize("name,rows,bucket,kernels", [
    ("mistral-7b-l16", 64, 64, 1), ("mistral-7b-l16", 3, 4, 1),
    ("phi4-mini-flash", 64, 64, 5), ("phi4-mini-flash", 3, 4, 5),
    ("mellum2-l12", 64, 64, 48), ("kanana2-l12-ep8", 64, 64, 45),
    ("longcat-flash-l4-ep32", 64, 64, 20), ("kimi-linear-l16-ep16", 64, 64, 61)],
    ids=["mistral64", "mistral4", "phi4flash64", "phi4flash4", "mellum2-64",
         "kanana2-64", "longcat-flash-64", "kimi-linear-64"])
def test_a_cells_decode_round_program_lowers(for_tpu, one_chip, monkeypatch,
                                             name, rows, bucket, kernels):
    """The WHOLE ragged forward of a decode round, [64, 1] and [4, 1], as the
    benchmark's serving cells dispatch it: every layer at the published
    widths, the paged kernel (and phi4flash's scan; for mellum2 the paged
    kernel and the three grouped GEMMs in each of 12 layers, over 512 expert
    rows of which a padded row takes none; for kanana2 the ABSORBED latent
    walk in 12 layers and the grouped GEMMs over the 16 experts held in 11;
    for longcat-flash the absorbed latent walk twice and the grouped GEMMs
    once in each of 4 double layers, the zero experts' rows past every group;
    for kimi-linear the one-step KDA kernel in 12 layers, the absorbed latent
    walk in 4 and the grouped GEMMs in 15; the walk that up-projects is a
    prompt chunk's of 256 tokens or more, below) at one token
    a row, read from the host's buffer or, by the row's source, from the ids
    the round before left on the device (the program's last array, one
    place a row of the engine's 64)."""
    layout, lowered = _cell_program(name, rows, one_chip, monkeypatch)
    assert dict(layout)["tokens"] == (bucket, 1)
    assert dict(layout)["src"] == (bucket,)
    assert lowered.in_avals[0][-1].shape == (64,)
    text = lowered.compile().as_text()
    assert text.count("tpu_custom_call") >= kernels
    # a decode round's latent walks are the absorbed ones, every one
    assert _latent_walks(text)[1] == 0


@pytest.mark.parametrize("name,rows,tokens,shape", [
    ("mistral-7b-l16", 1, 449, (1, 512)), ("mistral-7b-l16", 64, 1, (64, 1)),
    ("kanana2-l12-ep8", 1, 449, (1, 512))],
    ids=["mistral-chunk", "mistral-decode", "kanana2-chunk"])
def test_a_cells_program_writes_its_pool_in_place(for_tpu, one_chip, monkeypatch,
                                                  name, rows, tokens, shape):
    """The cache write of a prompt chunk (page-wise: the pages a row fills
    read, chosen by slot and written whole) and of a decode round (row-wise)
    in the WHOLE program of a cell, compiled for the described chip: the
    merged pool is updated where it lies. Every pool leaf comes back in the
    buffer it came in (donated, aliased) and the program's scratch is
    smaller than ONE leaf, so it holds no copy and no other layout of a
    pool-shaped array."""
    layout, lowered = _cell_program(name, rows, one_chip, monkeypatch, tokens)
    assert dict(layout)["tokens"] == shape
    pools = jax.tree.leaves(lowered.in_avals[0][1]["kv"])      # the cache argument
    leaf = min(p.size * p.dtype.itemsize for p in pools)
    assert leaf > 2 ** 27, "a pool too small to tell a copy from scratch"
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < leaf, (memory.temp_size_in_bytes, leaf)
    assert memory.alias_size_in_bytes >= sum(p.size * p.dtype.itemsize for p in pools)
    text = compiled.as_text()
    for p in pools:
        dims = ",".join(map(str, (p.shape[0] * p.shape[1],) + p.shape[2:]))
        assert f"[{dims}]" in text, dims       # the merged pool, on the loop's carry
        assert not re.search(rf"= \w+\[{dims}\]\S* (copy|transpose)\(", text)


@pytest.mark.parametrize("rows,tokens,shape,kernels", [
    (64, 1, (64, 1), 61), (1, 449, (1, 512), 61)], ids=["decode64", "chunk512"])
def test_kimi_linears_programs_update_slots_and_pages_where_they_lie(
        for_tpu, one_chip, monkeypatch, rows, tokens, shape, kernels):
    """Kimi-Linear's WHOLE programs at the published widths, compiled for the
    described chip: 12 KDA kernels (``kda_step`` at one token a row,
    ``kda_chunk`` for a chunk), 4 latent walks (absorbed at one token a row,
    up-projecting in the walk for the chunk of 512), 15 x 3 grouped GEMMs. The
    1.6 GB of matrix states, the convolution tails and the latent pages come
    back in the buffers they came in (donated, aliased through the kernels'
    own ``input_output_aliases``), and the program's scratch is smaller than
    a QUARTER of the state pool: it holds no copy of it, gathered or whole."""
    layout, lowered = _cell_program("kimi-linear-l16-ep16", rows, one_chip, monkeypatch, tokens)
    assert dict(layout)["tokens"] == shape and "state" in dict(layout)
    cache = lowered.in_avals[0][1]
    assert set(cache) == {"kv", "state", "counters"}
    state = cache["state"]["kda"]
    assert state.shape == (12, 65, 32, 128, 128) and state.dtype == jnp.float32
    assert cache["state"]["conv"].shape == (12, 65, 4, 12288)
    assert cache["kv"][0].shape[0] == 4 and cache["kv"][0].shape[-1] == 640
    pools = jax.tree.leaves((cache["kv"], cache["state"]))
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= sum(p.size * p.dtype.itemsize for p in pools)
    assert memory.temp_size_in_bytes < state.size * 4 // 4, memory.temp_size_in_bytes
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= kernels
    assert _latent_walks(text) == ((4, 0) if tokens == 1 else (0, 4))
    merged = ",".join(map(str, (12 * 65, 32, 128, 128)))
    assert f"f32[{merged}]" in text
    assert not re.search(rf"= f32\[{merged}\]\S* (copy|transpose)\(", text)


def _latent_walks(text):
    """(absorbed, up-projecting) latent walks among a compiled program's
    kernels, by the ``pallas_call``'s name."""
    names = re.findall(r"%(paged_mla\w*?)(?:\.\d+)? = \S+ custom-call\(", text)
    return names.count("paged_mla"), names.count("paged_mla_up")


def _equations(closed):
    """Every equation of a traced function, through every inner jaxpr (a
    kernel's body among them): its primitive, its name stack (the device
    scopes the metrics read) and its output types, in order. What a program's
    text is made from, without the source locations the text carries."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            out.append((eqn.primitive.name, str(eqn.source_info.name_stack),
                        tuple(str(v.aval) for v in eqn.outvars)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(closed.jaxpr)
    return out


@pytest.mark.parametrize("name,tokens,shape,walks", [
    ("kanana2-l12-ep8", 449, (1, 512), (0, 12)), ("kanana2-l12-ep8", 200, (1, 256), (0, 12)),
    ("kanana2-l12-ep8", 100, (1, 128), (12, 0)), ("longcat-flash-l4-ep32", 449, (1, 512), (0, 8))],
    ids=["kanana2-512", "kanana2-256", "kanana2-128", "longcat-flash-512"])
def test_a_latent_cells_chunk_program_takes_the_form_the_rule_picks(
        for_tpu, one_chip, monkeypatch, name, tokens, shape, walks):
    """The WHOLE program of a prompt chunk in the two latent cells, compiled
    for the described chip: every latent walk of a ``[1, 512]`` and a ``[1,
    256]`` dispatch up-projects in the walk (one a layer in Kanana-2's 12, two
    a double layer in LongCat-Flash's 4) and none is absorbed; a ``[1, 128]``
    dispatch's are all absorbed."""
    layout, lowered = _cell_program(name, 1, one_chip, monkeypatch, tokens)
    assert dict(layout)["tokens"] == shape
    assert _latent_walks(lowered.compile().as_text()) == walks


@pytest.mark.parametrize("seqs,q_tokens,rope", [(64, 1, True), (1, 128, True), (64, 1, False)],
                         ids=["decode64", "chunk128", "decode64-nope"])
def test_the_absorbed_read_is_the_parents_program(for_tpu, one_chip, seqs, q_tokens, rope):
    """Where the rule keeps the absorbed walk, ``kanana2.latent_mla`` traces
    equation for equation, the kernel's body included, what the function it
    replaced traces (PR 57's ``absorbed_mla``, kept here word for word), so
    that the program's text differs by source locations alone: nothing of a
    decode round's or a short chunk's attention changed with the second form.
    (Against the parent's own checkout the WHOLE ``[64, 1]`` programs of the
    three latent cells, Kanana-2's ``[1, 128]`` and Mistral's and Mellum2's
    ``[64, 1]`` and chunk programs were compared once, compiled for the
    described chip, kernels' bodies decoded: equal. CHANGES.md, PR 58.)"""
    from deepspeed_tpu.inference.v2.model_implementations import kanana2
    from deepspeed_tpu.inference.v2.model_implementations.llama import _rmsnorm
    from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
        _latent_attention, _scatter_latent)
    from deepspeed_tpu.models.kanana2 import Kanana2Config
    from deepspeed_tpu.models.llama import rotary_apply
    cfg = Kanana2Config(dtype=jnp.bfloat16)
    H, r, d = cfg.num_attention_heads, cfg.kv_lora_rank, cfg.hidden_size

    def absorbed_mla(cfg, scope, attn, project_q, h, x, pool, tables, seen, q_len,
                     rope, trash):
        S, Q, _ = x.shape
        H, r = cfg.num_attention_heads, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        W, bs = pool.shape[-1], pool.shape[2]
        eps, dt = cfg.rms_norm_eps, cfg.dtype
        w_uk, w_uv = attn["w_uk"].astype(dt), attn["w_uv"].astype(dt)
        rotate = (lambda t: t) if rope is None else (lambda t: rotary_apply(t, *rope))
        with jax.named_scope(scope):
            with jax.named_scope("mla_q"):
                q = project_q(h)
                q_lat = jnp.einsum("sqhd,chd->sqhc", q[..., :dn], w_uk)
                q_row = jnp.concatenate(
                    [q_lat, rotate(q[..., dn:]),
                     jnp.zeros((S, Q, H, W - r - dr), dt)], -1)
            with jax.named_scope("mla_latent_write"):
                ckv = h @ attn["kv_a_proj"]["kernel"].astype(dt)
                c = _rmsnorm(ckv[..., :r], attn["kv_a_layernorm"]["scale"], eps)
                k_pe = rotate(ckv[..., None, r:])[..., 0, :]
                row = jnp.concatenate(
                    [c, k_pe, jnp.zeros((S, Q, W - r - dr), dt)], -1)
                pool = _scatter_latent(pool, row, tables, seen, q_len, bs, trash)
            with jax.named_scope("mla_read"):
                o_lat = _latent_attention(q_row, pool, tables, seen, bs, q_len,
                                          r, cfg.softmax_scale)
            with jax.named_scope("mla_out"):
                o = jnp.einsum("sqhc,chd->sqhd", o_lat, w_uv)
                x = x + o.reshape(S, Q, H * dv) @ attn["o_proj"]["kernel"].astype(dt)
        return x, pool

    def program(form):
        def fn(attn, h, x, pool, tables, seen, q_len, cos, sin):
            project_q = lambda h: (h @ attn["q_proj"]["kernel"]).reshape(
                seqs, q_tokens, H, cfg.qk_head_dim)
            return form(cfg, "mla_attn", attn, project_q, h, x, pool, tables, seen, q_len,
                        (cos, sin) if rope else None, 2048)
        return fn

    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    attn = {"q_proj": {"kernel": sds((d, H * cfg.qk_head_dim))},
            "kv_a_proj": {"kernel": sds((d, r + cfg.qk_rope_head_dim))},
            "kv_a_layernorm": {"scale": sds((r,), jnp.float32)},
            "w_uk": sds((r, H, cfg.qk_nope_head_dim)), "w_uv": sds((r, H, cfg.v_head_dim)),
            "o_proj": {"kernel": sds((H * cfg.v_head_dim, d))}}
    x = sds((seqs, q_tokens, d))
    table = sds((seqs, q_tokens, 1, cfg.qk_rope_head_dim // 2), jnp.float32)
    args = (attn, x, x, sds((2049, 1, PAGE, 640)), sds((seqs, 320), jnp.int32),
            sds((seqs,), jnp.int32), sds((seqs,), jnp.int32), table, table)
    assert not kanana2.up_projects(cfg, q_tokens)
    now, then = (_equations(jax.make_jaxpr(program(form))(*args))
                 for form in (kanana2.latent_mla, absorbed_mla))
    assert sum(name == "pallas_call" for name, _, _ in now) == 1 and now == then
    assert "paged_mla" in _compile(program(kanana2.latent_mla), args).as_text()


_MOVES = {"parameter", "constant", "dynamic-slice", "slice", "bitcast", "reshape",
          "copy", "transpose"}
# one instruction of a compiled program's text: its name, its array (shape and
# layout) and what it is
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%?(?P<name>\S+) = "
                          r"(?P<array>\w+\[(?P<dims>[\d,]*)\]\S*) (?P<op>[\w\-]+)\(")


def _elements(m):
    return int(np.prod([int(d) for d in m["dims"].split(",") if d]))


def _moved_whole(text, sizes):
    """The instructions of a compiled program, outside its fusions, that only
    MOVE an array of one of ``sizes`` elements: a ``copy``, a ``transpose``,
    or a fusion of nothing but slices, bitcasts, copies and transposes (a
    kernel cut out of its stack into a buffer of its own, as
    ``constant_dynamic-slice_fusion`` is). A slice, copy or transpose INSIDE
    a fusion that multiplies is that product reading its operand in place,
    and is not listed. ``[(what, shape and layout)]``, what = ``copy`` |
    ``transpose`` | ``slice``."""
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?(\S+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name is not None and not line.startswith("}"):
            bodies[name].append(line)
    fused = set(re.findall(r"calls=%?([\w.\-]+)", text))
    found = []
    for name, body in bodies.items():
        if name in fused:
            continue
        for line in body:
            m = _INSTRUCTION.match(line)
            if not m or _elements(m) not in sizes:
                continue
            what = m["op"]
            if what == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", line).group(1)
                inside = {i["op"] for i in map(_INSTRUCTION.match, bodies[called]) if i}
                what = "slice" if inside <= _MOVES else None
            if what in ("copy", "transpose", "slice"):
                found.append((what, m["array"]))
    return found


@pytest.mark.parametrize("rows,tokens,shape", [
    (64, 1, (64, 1)), (3, 1, (4, 1)), (1, 449, (1, 512)), (1, 100, (1, 128))],
    ids=["decode64", "decode4", "chunk512", "chunk128"])
def test_kimi_linears_programs_gather_and_scatter_tails_without_moving_their_pool(
        for_tpu, one_chip, monkeypatch, rows, tokens, shape):
    """The convolution tails are stored in whole tiles of four rows a slot
    (``KimiLinearForCausalLM.cache_groups``: three tails and a row of zeros),
    and no program of the cell, compiled for the described chip at the cell's
    OWN pool (16,384 pages: 13.5 GB of arguments, where the compiler starts
    to trade time for memory), moves their pool: outside its multiplying
    fusions it holds no ``copy``, ``transpose`` or fusion of slices and
    bitcasts of an array of the pool's size (with four rows a slot or with
    three), no ``remat_compressed`` / ``remat_uncompressed`` twin of it, and
    the pool stays in HBM (no ``copy-done``, no ``S(1)``). Stored
    ``[12, 65, 3, 12288]`` every program held two copies of the whole pool
    (the argument, kept compact, re-laid to the padded tiles on the way in
    and back on the way out) at any page pool, and at the cell's ten
    compressed and ten uncompressed twins besides, around every KDA layer's
    gather and scatter of 64 rows: 17 % of the cell's busy time on the chip.
    Stored one FLAT row a slot, ``[12, 65, 36864]``, the programs hold none
    of those either, but the compiler keeps that pool in VMEM (``S(1)``) and
    expands each scatter into a loop of 64 ``dynamic-update-slice``s on it,
    and on the chip the ``[64, 1]`` program never ended its first dispatch
    (PERF.md, PR 56)."""
    pages = 16384
    layout, lowered = _cell_program("kimi-linear-l16-ep16", rows, one_chip, monkeypatch,
                                    tokens, num_kv_blocks=pages)
    assert dict(layout)["tokens"] == shape
    cache = lowered.in_avals[0][1]
    assert cache["kv"][0].shape[:2] == (4, pages + 1)
    conv = cache["state"]["conv"]
    assert conv.shape == (12, 65, 4, 12288) and conv.dtype == jnp.bfloat16
    slots = conv.shape[0] * conv.shape[1]
    sizes = {slots * 4 * 12288, slots * 3 * 12288}
    text = lowered.compile().as_text()
    assert _moved_whole(text, sizes) == []
    pool = [m for m in map(_INSTRUCTION.match, text.splitlines())
            if m and _elements(m) in sizes]
    assert any(m["array"].startswith(f"bf16[{slots},4,12288]") for m in pool)   # the merged pool
    twins = [m["name"] for m in pool if "remat_" in m["name"]]
    assert not twins, twins
    elsewhere = [m[0] for m in pool if "S(1)" in m["array"] or m["op"] == "copy-done"]
    assert not elsewhere, elsewhere[:3]


@pytest.mark.parametrize("rows,tokens,shape,moved", [
    (64, 1, (64, 1), []), (3, 1, (4, 1), []),
    (1, 449, (1, 512), []), (1, 200, (1, 256), []), (1, 100, (1, 128), [])],
    ids=["decode64", "decode4", "chunk512", "chunk256", "chunk128"])
def test_mistrals_programs_read_q_k_and_v_kernels_where_they_lie(
        for_tpu, one_chip, monkeypatch, rows, tokens, shape, moved):
    """The tree ``llama.prepare_params`` makes (q, k and v's kernels stored
    ``[L, heads, head_dim, hidden]``; the engine applies it), in a Mistral
    cell's WHOLE programs compiled for the described chip. No program holds
    a copy, a transpose or a standalone slice of an array the size of one
    layer's q, k or v kernel (``moved``, the count, so that a later change
    sees it move): each is one fused product that reads its kernel out of
    the stack in HBM once, as ``o_proj`` and the MLP are. From ``[L, hidden,
    heads * head_dim]`` every program held six (each kernel cut into VMEM
    and transposed there before its product); from the prepared tree under
    ``models.llama.rotary_embed``'s strided pair split a chunk program of
    128 tokens and more still held two (q's and k's kernel cut into VMEM,
    their products' results laid tokens-minor for the split), which
    ``llama.rotary_embed``'s form without the split removed."""
    layout, lowered = _cell_program("mistral-7b-l16", rows, one_chip, monkeypatch, tokens)
    assert dict(layout)["tokens"] == shape
    attn = lowered.in_avals[0][0]["layers"]["block"]["self_attn"]
    kernels = [attn[n]["kernel"] for n in ("q_proj", "k_proj", "v_proj")]
    assert [k.shape for k in kernels] == [
        (16, 32, 128, 4096), (16, 8, 128, 4096), (16, 8, 128, 4096)]
    sizes = {int(np.prod(k.shape[1:])) for k in kernels}
    found = _moved_whole(lowered.compile().as_text(), sizes)
    assert sorted(what for what, _ in found) == moved, found


@pytest.mark.parametrize("tokens,k,experts,d,f,dtype,grad", [
    (512, 8, 64, 2304, 896, jnp.bfloat16, False),
    (64, 2, 8, 4096, 14336, jnp.bfloat16, False),
    (512, 2, 8, 4096, 14336, jnp.float32, True),
    (512, 12, 16, 6144, 2048, jnp.bfloat16, False),
    (64, 12, 16, 6144, 2048, jnp.bfloat16, False)],
    ids=["mellum2-chunk512", "mixtral-decode64", "mixtral-f32-train",
         "longcat-chunk512", "longcat-decode64"])
def test_grouped_gemm_ffn_at_the_rules_tiles(for_tpu, one_chip, tokens, k,
                                             experts, d, f, dtype, grad):
    """The expert FFN alone at the tiles ``grouped_gemm.gmm_tiling`` picks for
    each GEMM's own widths: Mosaic's count of the blocks' VMEM has the last
    word over the rule's reckoning. The training call compiles its backward
    too (megablox's 128^3 there)."""
    from deepspeed_tpu.ops.pallas import grouped_gemm as gg
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = (sds((tokens, d), dtype), sds((tokens, k), jnp.float32),
            sds((tokens, k), jnp.int32), sds((experts, d, f), dtype),
            sds((experts, f, d), dtype), sds((experts, d, f), dtype))

    def ffn(x, tv, ti, w1, w2, w3):
        return gg.moe_ffn_gmm(x, tv, ti, w1, w2, w3, n_experts=experts,
                              dtype=dtype)

    def loss(x, tv, ti, w1, w2, w3):
        return jnp.sum(ffn(x, tv, ti, w1, w2, w3).astype(jnp.float32) ** 2)

    compiled = _compile(jax.grad(loss, argnums=(0, 3, 4, 5)) if grad else ffn,
                        args)
    assert compiled.as_text().count("tpu_custom_call") >= (9 if grad else 3)


def test_quantized_matmul_4096_wide(for_tpu, one_chip):
    from deepspeed_tpu.ops.pallas.quantized_matmul import (is_supported,
                                                           quantized_matmul)
    m, k, n, g = 512, 4096, 4096, 128
    assert is_supported(m, k, n, g, 8)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(lambda x, q, s: quantized_matmul(x, q, s, g),
             (sds((m, k), jnp.bfloat16), sds((k, n), jnp.int8),
              sds((k, n // g), jnp.float32)))


@pytest.mark.parametrize("bits", [8, 4])
def test_block_quantize_4096_wide(for_tpu, one_chip, bits):
    from deepspeed_tpu.ops.pallas.quant_collective import block_quantize
    x = jax.ShapeDtypeStruct((64, 4096), jnp.float32, sharding=one_chip)
    _compile(lambda v: block_quantize(v, num_bits=bits, group_size=2048),
             (x,))


def test_block_dequantize_reduce_4096_wide(for_tpu, one_chip):
    from deepspeed_tpu.ops.pallas.quant_collective import (
        block_dequantize_reduce)
    peers, groups, g = 4, 128, 2048          # 128 groups = 64 rows x 4096
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(lambda q, s: block_dequantize_reduce(q, s, num_bits=8,
                                                  group_size=g),
             (sds((peers, groups * g), jnp.int8),
              sds((peers, groups), jnp.float32)))


@pytest.mark.filterwarnings(
    "ignore:Error reading persistent compilation cache entry")
def test_same_program_same_topology_same_cache_key(topo, tmp_path,
                                                   monkeypatch):
    """What a warm compile cache rests on: the same program lowered afresh
    for the same described topology lands on the SAME cache entry, and a
    different device assignment on another. Read off the cache directory —
    the public behaviour — not off jax's private key function."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def chip_smoke_cache_probe(v):
        return jnp.sin(v) @ jnp.cos(v).T

    def entries(mesh_shape):
        mesh = Mesh(np.array(topo.devices).reshape(*mesh_shape), ("dp", "tp"))
        jax.clear_caches()               # a fresh lowering, a fresh key
        jax.jit(chip_smoke_cache_probe,
                in_shardings=NamedSharding(mesh, P("dp", "tp"))
                ).lower(x).compile()
        return {p.name for p in tmp_path.iterdir()
                if p.name.endswith("-cache")}

    try:
        first = entries((2, 2))
        assert len(first) == 1, first
        assert entries((2, 2)) == first          # same key: no second entry
        assert len(entries((4, 1))) == 2         # another assignment: new key
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


# the smallest and the largest vocabulary of the serving cells (16 s each:
# the sorting arm is what takes the compiler long)
@pytest.mark.parametrize("vocab", [32000, 200064])
def test_the_sampler_keeps_its_branch_and_sorts_in_one_arm_only(for_tpu, one_chip, vocab):
    """``sample_rows_packed`` at a serving cell's ``[64, vocabulary]``, with
    the ids it keeps on the device for the next round's forward (one place
    a row of the engine's 64): the chip's compiler keeps the dispatch's
    ``conditional`` (it could have flattened it to a select, which runs
    both arms), and the sort lies in an arm's computation, not in the
    entry's."""
    from deepspeed_tpu.inference.v2.sampling import sample_rows_packed
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = sample_rows_packed.lower(
        sds((64, vocab), jnp.float32), sds((2, 64), jnp.float32),
        sds((4, 64), jnp.int32), sds((64,), jnp.int32)).compile().as_text()
    entry = text[text.index("\nENTRY "):]
    assert len(re.findall(r" conditional\(", entry)) == 1
    assert " sort(" in text and " sort(" not in entry
