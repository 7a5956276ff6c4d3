"""Pallas paged (blocked-flash) attention kernel vs the dense gather path.

Mirrors the reference's ragged-ops kernel tests
(``tests/unit/inference/v2/kernels/ragged_ops/test_blocked_flash.py``):
same numerics as the dense path across decode (Q=1), chunked prefill (Q>1),
GQA, and ragged ``seen`` lengths.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
    _paged_attention_dense)
from deepspeed_tpu.ops.pallas.paged_attention import is_supported, paged_mha


def make_case(S=3, Q=1, H=4, KV=2, Dh=64, NB=10, bs=16, MB=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (S, Q, H, Dh), jnp.float32)
    k_pool = jax.random.normal(ks[1], (NB, KV, bs, Dh), jnp.float32)
    v_pool = jax.random.normal(ks[2], (NB, KV, bs, Dh), jnp.float32)
    rng = np.random.default_rng(seed)
    # distinct blocks per sequence (last pool block is the trash block)
    bt = rng.permutation((NB - 1) * MB)[: S * MB].reshape(S, MB) % (NB - 1)
    block_tables = jnp.asarray(bt, jnp.int32)
    seen = jnp.asarray(rng.integers(0, MB * bs - Q, size=S), jnp.int32)
    q_len = jnp.full((S,), Q, jnp.int32)
    return q, k_pool, v_pool, block_tables, seen, q_len


def run_both(case):
    q, kp, vp, bt, seen, q_len = case
    bs = kp.shape[2]
    out_k = paged_mha(q, kp, vp, bt, seen, q_len, interpret=True)
    out_d = _paged_attention_dense(q, kp, vp, bt, seen, bs)
    return out_k, out_d


def valid_rows(out, q_len):
    # rows past q_len are padding; compare only live ones
    S, Q = out.shape[:2]
    mask = np.arange(Q)[None, :] < np.asarray(q_len)[:, None]
    return np.asarray(out)[mask]


@pytest.mark.parametrize("Q", [1, 4])
def test_matches_dense(Q):
    case = make_case(Q=Q)
    out_k, out_d = run_both(case)
    np.testing.assert_allclose(valid_rows(out_k, case[5]),
                               valid_rows(out_d, case[5]), atol=2e-4, rtol=1e-3)


def test_mha_no_gqa():
    case = make_case(H=4, KV=4)
    out_k, out_d = run_both(case)
    np.testing.assert_allclose(valid_rows(out_k, case[5]),
                               valid_rows(out_d, case[5]), atol=2e-4, rtol=1e-3)


def test_zero_seen_decode_first_token():
    q, kp, vp, bt, seen, q_len = make_case(S=2, Q=1)
    seen = jnp.zeros_like(seen)
    out_k = paged_mha(q, kp, vp, bt, seen, q_len, interpret=True)
    out_d = _paged_attention_dense(q, kp, vp, bt, seen, kp.shape[2])
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_d),
                               atol=2e-4, rtol=1e-3)


def test_bf16():
    q, kp, vp, bt, seen, q_len = make_case(Dh=128)
    q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
    out_k = paged_mha(q, kp, vp, bt, seen, q_len, interpret=True)
    out_d = _paged_attention_dense(q, kp, vp, bt, seen, kp.shape[2])
    assert out_k.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        valid_rows(out_k, q_len).astype(np.float32),
        valid_rows(out_d, q_len).astype(np.float32), atol=3e-2, rtol=3e-2)


def test_is_supported():
    assert is_supported((2, 1, 8, 64), (8, 2, 16, 64))
    assert not is_supported((2, 1, 8, 64), (8, 3, 16, 64))   # H % KV
    assert not is_supported((2, 1, 8, 512), (8, 2, 16, 512))  # Dh
    assert not is_supported((2, 1, 8, 64), (8, 2, 12, 64))   # bs % 8


@pytest.mark.parametrize("Q", [1, 2])
@pytest.mark.parametrize("window", [8, 24])
def test_sliding_window_matches_dense(window, Q):
    """Mistral-style windowed masking in the kernel (the only path serving
    windowed models on real TPU) vs the dense twin; heads of 64 on the grid,
    a [D, 1] decode dispatch among them."""
    q, kp, vp, bt, seen, q_len = make_case(S=3, Q=Q, seed=7)
    out_k = paged_mha(q, kp, vp, bt, seen, q_len, window=window, interpret=True)
    out_d = _paged_attention_dense(q, kp, vp, bt, seen, kp.shape[2],
                                   window=window)
    np.testing.assert_allclose(valid_rows(out_k, q_len),
                               valid_rows(out_d, q_len), atol=2e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# The walk over a sequence's live pages (pools whose rows fill a lane tile,
# Dh % 128 == 0): manual copies, a trip count read from ``seen + q_len``.
# Every slot of a table past its row's live count points at a page of NaN
# (of NaN scales for int8 pages): a kernel that reads past the live count
# returns NaN; the dense twin reads the same pools with that page zeroed.
# ---------------------------------------------------------------------------

from deepspeed_tpu.ops.pallas.paged_attention import LANES, _walk_plan

# 128 KB a page in float32, as Mistral's bf16 pages are
WALK = dict(KV=4, Dh=256, bs=32)


def _pages_a_trip(Q, rep, MB):
    """Of a float32 call over float32 pages, as ``make_walk_case`` builds."""
    return _walk_plan(rep * Q, WALK["KV"], WALK["bs"], WALK["Dh"], 4, 4, MB)[2]


def make_walk_case(live, Q=1, rep=4, MB=256, q_len=None, dtype=jnp.float32,
                   seed=0):
    """One row a ``live`` entry: its context ends in its ``live``-th page."""
    KV, Dh, bs = WALK["KV"], WALK["Dh"], WALK["bs"]
    S, NB = len(live), sum(live) + 2
    poison, trash = NB - 2, NB - 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (S, Q, KV * rep, Dh), dtype)
    k_pool = jax.random.normal(ks[1], (NB, KV, bs, Dh), dtype)
    v_pool = jax.random.normal(ks[2], (NB, KV, bs, Dh), dtype)
    rng = np.random.default_rng(seed)
    q_len = np.full((S,), Q, np.int32) if q_len is None else np.asarray(q_len, np.int32)
    pages = rng.permutation(NB - 2)
    bt = np.full((S, MB), poison, np.int32)
    seen = np.zeros((S,), np.int32)
    for i, n in enumerate(live):
        if q_len[i] == 0:                 # a padded row: the trash block
            bt[i] = trash
            continue
        bt[i, :n], pages = pages[:n], pages[n:]
        # the last token lands in page n: seen + q_len in ((n-1)*bs, n*bs]
        seen[i] = rng.integers(max((n - 1) * bs + 1 - q_len[i], 0),
                               n * bs - q_len[i] + 1)
    return (q, k_pool, v_pool, jnp.asarray(bt), jnp.asarray(seen),
            jnp.asarray(q_len)), poison


def check_walk(case, poison, atol=2e-4, rtol=1e-3, **kw):
    q, kp, vp, bt, seen, q_len = case
    assert kp.shape[-1] % LANES == 0, "this geometry takes the grid kernel"
    nan = lambda pool: pool.at[poison].set(jnp.nan)
    out_k = paged_mha(q, nan(kp), nan(vp), bt, seen, q_len, interpret=True, **kw)
    zero = lambda pool: pool.at[poison].set(0)
    out_d = _paged_attention_dense(q, zero(kp), zero(vp), bt, seen,
                                   kp.shape[2], **kw)
    assert np.isfinite(np.asarray(out_k, np.float32)).all(), \
        "the walk read past a row's live pages, or left a padded row undefined"
    assert valid_rows(out_k, q_len).size
    np.testing.assert_allclose(valid_rows(out_k, q_len).astype(np.float32),
                               valid_rows(out_d, q_len).astype(np.float32),
                               atol=atol, rtol=rtol)


@pytest.mark.parametrize("live", ["one", "a_trip", "a_trip_and_one", "table"])
def test_walk_live_pages_at_the_trip_boundaries(live):
    MB = 256
    P = _pages_a_trip(1, 4, MB)
    assert 1 < P < MB - 1, "the table has to be wider than a trip"
    n = {"one": 1, "a_trip": P, "a_trip_and_one": P + 1, "table": MB}[live]
    check_walk(*make_walk_case([n, 2 * P + 3, n], MB=MB, seed=n))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_walk_wide_table_few_live_pages(seed):
    """A 256-slot table of which 3-50 slots are live, as a decode round of
    short contexts under a long ``max_context`` has it."""
    live = np.random.default_rng(seed).integers(3, 51, size=6)
    check_walk(*make_walk_case([int(n) for n in live], Q=8, seed=seed))


def test_walk_first_token_seen_zero():
    case, poison = make_walk_case([1, 1], Q=1)
    q, kp, vp, bt, seen, q_len = case
    check_walk((q, kp, vp, bt, jnp.zeros_like(seen), q_len), poison)


def test_walk_padded_rows_cost_one_finite_page():
    """Rows with ``q_len`` 0 (a sequence bucket's padding: ``seen`` 0, the
    trash block) between real ones: finite, and the real rows unmoved."""
    check_walk(*make_walk_case([5, 1, 70, 1, 9], Q=8,
                               q_len=[8, 0, 3, 0, 1]))


@pytest.mark.parametrize("Q", [1, 8, 64, 384])
def test_walk_query_tokens(Q):
    """A [D, 1] decode dispatch, a verify round's [D, 8], a chunk, and a chunk whose
    ``rep * Q`` rows (1536) are walked in row tiles, one KV head a step."""
    check_walk(*make_walk_case([-(-Q // WALK["bs"]) + 2, 41], Q=Q, MB=64, seed=Q))


@pytest.mark.parametrize("Q", [1, 8])
@pytest.mark.parametrize("rep", [1, 4])
def test_walk_query_heads_a_kv_head(rep, Q):
    """``rep x Q`` query rows a KV head: 1 and 4 rows (under a sublane tile)
    in a [D, 1] decode dispatch."""
    check_walk(*make_walk_case([2, 37, 11], Q=Q, rep=rep, seed=rep))


@pytest.mark.parametrize("Q", [1, 8, 64])
def test_walk_ring_table_with_window(Q):
    """A window group's table: a ring of at most 17 pages, positions
    relative to its first page, the window cutting into the oldest."""
    check_walk(*make_walk_case([17, 9, 3], Q=Q, MB=17, seed=5),
               window=8 * WALK["bs"])


@pytest.mark.parametrize("Q", [1, 8])
def test_walk_softmax_scale(Q):
    check_walk(*make_walk_case([4, 30], Q=Q), softmax_scale=0.125)


@pytest.mark.parametrize("Q", [1, 8])
def test_walk_bf16(Q):
    check_walk(*make_walk_case([1, 40, 9], Q=Q, dtype=jnp.bfloat16),
               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("Q", [1, 8])
def test_walk_int8_pages_with_scales(Q):
    """int8 pages take the walk; their scale rows are gathered through the
    table clamped to the live pages, so the poisoned page's NaN scales are
    never read either."""
    from test_kv_tiering import _quantize_pool
    (q, kp, vp, bt, seen, q_len), poison = make_walk_case([1, 33, 6], Q=Q,
                                                          MB=64, seed=Q)
    (kq, ks), (vq, vs) = _quantize_pool(kp), _quantize_pool(vp)
    nan = lambda scale: scale.at[poison].set(jnp.nan)
    out_k = paged_mha(q, kq, vq, bt, seen, q_len, k_scale=nan(ks),
                      v_scale=nan(vs), interpret=True)
    zero = lambda scale: scale.at[poison].set(0)
    out_d = _paged_attention_dense(q, (kq, zero(ks)), (vq, zero(vs)), bt,
                                   seen, kq.shape[2])
    assert np.isfinite(np.asarray(out_k)).all()
    np.testing.assert_allclose(valid_rows(out_k, q_len),
                               valid_rows(out_d, q_len), atol=2e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# ``paged_mla``: the walk's mode for a page of ONE leaf (a latent row a token:
# read once, keys by its whole width, values by its first ``value_dim``
# columns, every query head on the one row). Poisoned as above.
# ---------------------------------------------------------------------------

from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (
    _latent_attention_dense)
from deepspeed_tpu.ops.pallas.paged_attention import mla_is_supported, paged_mla

MLA = dict(W=256, value_dim=128, bs=16, scale=0.09)


def make_mla_case(live, Q=1, H=4, MB=64, q_len=None, dtype=jnp.float32, seed=0):
    """One row a ``live`` entry: its context ends in its ``live``-th page;
    a ``q_len`` of 0 is a padded row on the trash page."""
    W, bs = MLA["W"], MLA["bs"]
    S, NB = len(live), sum(live) + 2
    poison, trash = NB - 2, NB - 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    q = jax.random.normal(ks[0], (S, Q, H, W), dtype)
    pool = jax.random.normal(ks[1], (NB, 1, bs, W), dtype)
    rng = np.random.default_rng(seed)
    q_len = np.full((S,), Q, np.int32) if q_len is None else np.asarray(q_len, np.int32)
    pages = rng.permutation(NB - 2)
    bt = np.full((S, MB), poison, np.int32)
    seen = np.zeros((S,), np.int32)
    for i, n in enumerate(live):
        if q_len[i] == 0:
            bt[i] = trash
            continue
        bt[i, :n], pages = pages[:n], pages[n:]
        seen[i] = rng.integers(max((n - 1) * bs + 1 - q_len[i], 0), n * bs - q_len[i] + 1)
    return (q, pool, jnp.asarray(bt), jnp.asarray(seen), jnp.asarray(q_len)), poison


def check_mla(case, poison, atol=2e-4, rtol=1e-3):
    q, pool, bt, seen, q_len = case
    out_k = paged_mla(q, pool.at[poison].set(jnp.nan), bt, seen, q_len,
                      value_dim=MLA["value_dim"],
                      softmax_scale=MLA["scale"], interpret=True)
    out_d = _latent_attention_dense(q, pool.at[poison].set(0), bt, seen, MLA["bs"],
                                    MLA["value_dim"], MLA["scale"])
    assert out_k.shape == q.shape[:3] + (MLA["value_dim"],)
    assert np.isfinite(np.asarray(out_k, np.float32)).all(), \
        "the walk read past a row's live pages, or left a padded row undefined"
    assert valid_rows(out_k, q_len).size
    np.testing.assert_allclose(valid_rows(out_k, q_len).astype(np.float32),
                               valid_rows(out_d, q_len).astype(np.float32),
                               atol=atol, rtol=rtol)


@pytest.mark.parametrize("Q", [1, 8])
def test_mla_ragged_lengths_a_trash_page_and_padded_slots(Q):
    """Rows of 1..40 live pages beside a padded row (``q_len`` 0, the trash
    page) and, for a chunk, rows shorter than the chunk bucket."""
    q_len = [Q, max(Q - 3, 1), 0, Q, 1]
    check_mla(*make_mla_case([1, 7, 1, 40, 13], Q=Q, q_len=q_len))


def test_mla_first_token_and_a_table_of_one_live_page():
    case, poison = make_mla_case([1, 1], Q=1)
    q, pool, bt, seen, q_len = case
    check_mla((q, pool, bt, jnp.zeros_like(seen), q_len), poison)


def test_mla_row_tiles_on_the_grid():
    """A chunk whose ``heads x Q`` query rows pass one tile (512): the second
    grid axis steps over tiles of them, each walking the pages anew."""
    from deepspeed_tpu.ops.pallas.paged_attention import _walk_plan
    rows = 8 * 128
    assert _walk_plan(rows, 1, MLA["bs"], MLA["W"], 4, 4, 64)[1] == 512 < rows
    check_mla(*make_mla_case([9], Q=128, H=8, q_len=[100]))


def test_mla_a_chunk_past_its_rows_live_pages_sees_its_own_keys_only():
    """A chunk that starts a sequence (``seen`` 0): a query sees the keys up
    to itself and none of the rest of the chunk's page."""
    case, poison = make_mla_case([1, 2], Q=8, q_len=[8, 5])
    q, pool, bt, seen, q_len = case
    check_mla((q, pool, bt, jnp.zeros_like(seen), q_len), poison)


def test_mla_bf16():
    check_mla(*make_mla_case([5, 30, 2], Q=1, dtype=jnp.bfloat16), atol=3e-2, rtol=3e-2)


def test_mla_is_supported():
    ok = lambda q, pool, v=128: mla_is_supported(q, pool, v)
    assert ok((64, 1, 32, 640), (10, 1, 64, 640), 512)
    assert ok((1, 512, 32, 640), (10, 1, 64, 640), 512)
    assert not ok((64, 1, 32, 576), (10, 1, 64, 576), 512)        # 4.5 lane tiles
    assert not ok((4, 1, 4, 256), (10, 2, 16, 256))               # one row, not heads
    assert not ok((4, 1, 4, 256), (10, 1, 12, 256))               # block size
    assert not ok((4, 1, 4, 256), (10, 1, 16, 256), 192)          # values off the lanes
    assert not ok((4, 1, 4, 384), (10, 1, 16, 256))               # q is not the row's width


# ---------------------------------------------------------------------------
# ``paged_mla`` with ``up``: the latent walk that up-projects a trip's keys
# and values for the tile's ONE head in VMEM (a prompt chunk's read), against
# its dense twin AND against the absorbed walk taken through ``W_UV``; and
# the rule by static shapes that picks between the two forms.
# ---------------------------------------------------------------------------

from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (  # noqa: E402
    _latent_attention_up, _latent_attention_up_dense, up_projects_in_walk)
from deepspeed_tpu.ops.pallas.paged_attention import query_row_tile  # noqa: E402

#: a row of 128 latent columns + 16 position columns in 256; heads of 128 + 16
UP = dict(r=128, dn=128, dr=16, dv=128, W=256, bs=16, scale=0.05)


def make_up_case(Q, H, rotated, seed=0, trips=True):
    """Four rows of one dispatch (``S > 1``) over a table wider than a trip
    of ``P`` pages: one whose context ends inside a page in its second trip's
    FIRST half (that trip takes the half-size update), one that starts its
    sequence (``seen`` 0) with fewer tokens than the bucket, a padded row
    (``q_len`` 0, the trash page) and one that ends in its second trip's
    second half. Slots past a row's live pages point at a page of NaN.
    ``trips`` False: short rows, one trip each."""
    from deepspeed_tpu.models.llama import rope_frequencies, rotary_apply, rotary_tables
    from deepspeed_tpu.ops.pallas.paged_attention import _up_pages
    r, dn, dr, dv, W, bs = (UP[k] for k in ("r", "dn", "dr", "dv", "W", "bs"))
    P = _up_pages(query_row_tile(Q), bs, 4 * W, 10 ** 6) if trips else 0
    k = Q // (P * bs) + 1 if trips else 0           # whole trips under the chunk's end
    ends = [(k * P + P // 2 - 1) * bs - 11, Q - 7, 0, (k * P + P // 2 + 2) * bs] if trips else \
        [Q + 2 * bs + 5, Q - 7, 0, Q + 3 * bs]
    q_len = np.asarray([Q, Q - 7, 0, Q], np.int32)
    seen = np.asarray(ends, np.int32) - q_len
    live = [-(-int(e) // bs) for e in ends]
    S, MB, NB = len(live), max(live) + 3, sum(live) + 2
    poison, trash = NB - 2, NB - 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    pool = jax.random.normal(ks[0], (NB, 1, bs, W), jnp.float32).at[..., r + dr:].set(0)
    q = jax.random.normal(ks[1], (S, Q, H, dn + dr), jnp.float32)
    w_uk = jax.random.normal(ks[2], (r, H, dn), jnp.float32) * r ** -0.5
    w_uv = jax.random.normal(ks[3], (r, H, dv), jnp.float32) * r ** -0.5
    pages = np.random.default_rng(seed).permutation(NB - 2)
    bt = np.full((S, MB), poison, np.int32)
    for i, n in enumerate(live):
        bt[i, :max(n, 1)], pages = (pages[:n], pages[n:]) if n else (trash, pages)
    if rotated:
        # what the model does to the position part of q and of a written row;
        # Kimi-Linear (``rope=None``) leaves both as projected
        freqs = rope_frequencies(dr, 10000.0)
        at = lambda pos: rotary_tables(jnp.asarray(pos), *freqs)
        q = q.at[..., dn:].set(rotary_apply(
            q[..., dn:], *at(seen[:, None] + np.arange(Q)[None, :])))
        k_pe = pool[:, 0, :, None, r:r + dr]                       # [NB, bs, 1, dr]
        pool = pool.at[:, 0, :, r:r + dr].set(rotary_apply(
            k_pe, *at(np.arange(NB * bs).reshape(NB, bs) % 977))[:, :, 0])
    return (q, w_uk, w_uv, pool, jnp.asarray(bt), jnp.asarray(seen), jnp.asarray(q_len)), poison


@pytest.mark.parametrize("Q,H,rotated", [
    (256, 32, True), (256, 64, False), (512, 32, False), (512, 64, True),
    # past ``_MAX_ROW_TILE`` a head's queries are TILED inside the head (each
    # tile up-projects the trip anew); the rule sees the tile
    (1024, 4, True)])
def test_mla_up_projecting_walk_agrees_with_its_twin_and_the_absorbed_walk(Q, H, rotated,
                                                                           monkeypatch):
    # a score tile an eighth of the chip's, so that contexts an eighth as long
    # walk as many trips (and a half-size last one)
    from deepspeed_tpu.ops.pallas import paged_attention
    monkeypatch.setattr(paged_attention, "_UP_SCORE_BYTES", 1 << 18)
    case, poison = make_up_case(Q, H, rotated, seed=Q + H)
    assert case[4].shape[1] > 2 * paged_attention._up_pages(query_row_tile(Q), UP["bs"], 4 * UP["W"],
                                                            10 ** 6)
    q, w_uk, w_uv, pool, bt, seen, q_len = case
    r, dn, bs, scale = UP["r"], UP["dn"], UP["bs"], UP["scale"]
    assert query_row_tile(Q) == min(Q, 512) and up_projects_in_walk(Q, 512, 128, 128)
    nan, zero = pool.at[poison].set(jnp.nan), pool.at[poison].set(0)
    q_row = jnp.pad(q, ((0, 0),) * 3 + ((0, dn + UP["W"] - r - q.shape[-1]),))
    assert mla_is_supported(q_row.shape, pool.shape, r, up_dims=(dn, UP["dv"]))
    out_k = paged_mla(q_row, nan, bt, seen, q_len, value_dim=r, softmax_scale=scale,
                      up=(w_uk, w_uv), interpret=True)
    assert out_k.shape == (4, Q, H, UP["dv"])
    assert np.isfinite(np.asarray(out_k)).all(), \
        "the walk read past a row's live pages, or left a padded row undefined"
    twin = _latent_attention_up_dense(q, w_uk, w_uv, zero, bt, seen, bs, scale)
    # the absorbed walk on the same pages, taken through W_UV
    q_abs = jnp.concatenate([jnp.einsum("sqhd,chd->sqhc", q[..., :dn], w_uk),
                             q_row[..., dn:]], -1)
    absorbed = jnp.einsum("sqhc,chd->sqhd", paged_mla(
        q_abs, nan, bt, seen, q_len, value_dim=r, softmax_scale=scale, interpret=True), w_uv)
    got = valid_rows(out_k, q_len)
    assert got.size == (3 * Q - 7) * H * UP["dv"]
    for want in (twin, absorbed):
        np.testing.assert_allclose(got, valid_rows(want, q_len), atol=2e-4, rtol=1e-3)


def test_mla_up_read_takes_the_twin_where_the_walk_does_not_tile(monkeypatch):
    """``_latent_attention_up`` hands the kernel q padded to the row's
    columns behind the latent; heads of 32 + 32 columns (a tiny model's) are
    not lane tiles, and the read is the dense twin's."""
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("DS_TPU_DISABLE_PALLAS", raising=False)
    case, poison = make_up_case(64, 4, True, seed=3, trips=False)
    q, w_uk, w_uv, pool, bt, seen, q_len = case
    pool = pool.at[poison].set(0)
    got = _latent_attention_up(q, w_uk, w_uv, pool, bt, seen, UP["bs"], q_len, UP["scale"])
    twin = _latent_attention_up_dense(q, w_uk, w_uv, pool, bt, seen, UP["bs"], UP["scale"])
    np.testing.assert_allclose(valid_rows(got, q_len), valid_rows(twin, q_len),
                               atol=2e-4, rtol=1e-3)
    narrow = (w_uk[..., :32], w_uv[..., :32])
    q32 = jnp.concatenate([q[..., :32], q[..., UP["dn"]:]], -1)
    assert not mla_is_supported((4, 64, 4, 32 + 128), pool.shape, 128, up_dims=(32, 32))
    got = _latent_attention_up(q32, *narrow, pool, bt, seen, UP["bs"], q_len, UP["scale"])
    twin = _latent_attention_up_dense(q32, *narrow, pool, bt, seen, UP["bs"], UP["scale"])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(twin))


#: every chunk bucket the engines warm (``[1, 16]`` .. ``[1, 512]``), a decode
#: dispatch's 1 and a verify round's 8
BUCKETS = (1, 8, 16, 32, 64, 128, 256, 512)


@pytest.mark.parametrize("family,widths", [
    ("kanana2", (512, 128, 128)), ("longcat_flash", (512, 128, 128)),
    ("kimi_linear", (512, 128, 128))])
def test_the_rule_of_the_latent_reads_form(family, widths):
    """By count the up-projecting walk is the lesser from 171 queries a head
    at the three families' widths (``kv_lora_rank``, ``qk_nope_head_dim``,
    ``v_head_dim`` of their published configs): the 256 and 512 buckets take
    it, every other dispatch stays absorbed; a chunk past the row tile is
    judged by its tile."""
    import importlib
    cfg_cls = {"kanana2": "Kanana2Config", "longcat_flash": "LongcatFlashConfig",
               "kimi_linear": "KimiLinearConfig"}[family]
    fields = getattr(importlib.import_module(f"deepspeed_tpu.models.{family}"),
                     cfg_cls).__dataclass_fields__
    published = tuple(fields[k].default for k in
                      ("kv_lora_rank", "qk_nope_head_dim", "v_head_dim"))
    assert published == widths
    assert [Q for Q in BUCKETS if up_projects_in_walk(Q, *widths)] == [256, 512]
    assert up_projects_in_walk(1024, *widths) and up_projects_in_walk(176, *widths)
    assert not up_projects_in_walk(168, *widths)
    # a model whose heads are as wide as its latent gains nothing by it
    assert not any(up_projects_in_walk(Q, 128, 128, 128) for Q in BUCKETS)


# ---------------------------------------------------------------------------
# Learned sparse attention: the walk under a selection, the index scores over
# paged keys, and the threshold that stands for the selection
# (``ops/pallas/sparse_index.py``). Slots past a row's live count point at a
# page of NaN, as above.
# ---------------------------------------------------------------------------

from deepspeed_tpu.inference.v2.model_implementations.paged_layer import (  # noqa: E402
    _index_scores_dense, _select_dense)
from deepspeed_tpu.ops.pallas import sparse_index  # noqa: E402
from deepspeed_tpu.ops.pallas.paged_attention import select_is_supported  # noqa: E402


def _selection(case, topk, seed=0):
    """Random scores of every (query, key) and the threshold ``top_k`` gives,
    ``-inf`` behind each query as the index scores have it."""
    q, kp, _, bt, seen, _ = case
    S, Q, N = q.shape[0], q.shape[1], bt.shape[1] * kp.shape[2]
    scores = jax.random.normal(jax.random.PRNGKey(100 + seed), (S, Q, N), jnp.float32)
    behind = jnp.arange(N)[None, None, :] > (seen[:, None] + jnp.arange(Q)[None, :])[..., None]
    scores = jnp.where(behind, -jnp.inf, scores)
    return scores, _select_dense(scores, topk)


def check_select_walk(case, poison, topk, atol=2e-4, rtol=1e-3):
    q, kp, vp, bt, seen, q_len = case
    scores, tau = _selection(case, topk)
    nan = lambda pool: pool.at[poison].set(jnp.nan)
    out_k = paged_mha(q, nan(kp), nan(vp), bt, seen, q_len, select=(scores, tau),
                      interpret=True)
    zero = lambda pool: pool.at[poison].set(0)
    out_d = _paged_attention_dense(q, zero(kp), zero(vp), bt, seen, kp.shape[2],
                                   keep=scores >= tau[..., None])
    plain = _paged_attention_dense(q, zero(kp), zero(vp), bt, seen, kp.shape[2])
    assert np.isfinite(np.asarray(out_k, np.float32)).all()
    got, want = valid_rows(out_k, q_len), valid_rows(out_d, q_len)
    np.testing.assert_allclose(got.astype(np.float32), want.astype(np.float32),
                               atol=atol, rtol=rtol)
    return float(np.abs(want - valid_rows(plain, q_len)).max())


@pytest.mark.parametrize("live", ["one", "a_trip", "a_trip_and_one", "table"])
def test_select_walk_live_pages_at_the_trip_boundaries(live):
    MB = 256
    P = _pages_a_trip(1, 4, MB)
    n = {"one": 1, "a_trip": P, "a_trip_and_one": P + 1, "table": MB}[live]
    moved = check_select_walk(*make_walk_case([n, 2 * P + 3, n], MB=MB, seed=n), topk=96)
    assert moved > 1e-2            # the selection is not the identity on the long row


@pytest.mark.parametrize("Q", [1, 8, 64, 384])
def test_select_walk_query_tokens(Q):
    """A [D, 1] dispatch (a row's whole scores through the pipeline), chunks
    whose tiles hold several heads' queries, and a chunk walked in row tiles
    (a slab of scores copied beside the pages, shared by the tiles)."""
    assert select_is_supported((2, Q, 16, 256), (9, 4, 32, 256))
    check_select_walk(*make_walk_case([max(3, Q // 32), 40], Q=Q, seed=Q), topk=200)


def test_select_walk_padded_rows_and_a_row_that_reads_all_it_sees():
    case, poison = make_walk_case([5, 1, 70, 1, 9], Q=8, q_len=[8, 0, 3, 0, 1])
    check_select_walk(case, poison, topk=100)      # rows 0 and 4 see fewer than 100
    assert not select_is_supported((2, 4, 16, 256), (9, 4, 32, 256))   # a chunk of 4
    assert not select_is_supported((2, 8, 16, 64), (9, 4, 32, 64))     # narrow heads


def make_index_case(live, Q=1, Hi=4, Di=64, W=128, bs=32, MB=64, seed=0, dtype=jnp.float32):
    S, NB = len(live), sum(live) + 2
    poison = NB - 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = jax.random.normal(ks[0], (NB, 1, bs, W), dtype).at[..., Di:].set(0)
    q = jax.random.normal(ks[1], (S, Q, Hi, Di), dtype)
    w = jax.random.normal(ks[2], (S, Q, Hi), jnp.float32)
    rng = np.random.default_rng(seed)
    pages = rng.permutation(NB - 2)
    bt = np.full((S, MB), poison, np.int32)
    seen = np.zeros((S,), np.int32)
    for i, n in enumerate(live):
        bt[i, :n], pages = pages[:n], pages[n:]
        seen[i] = rng.integers(max((n - 1) * bs + 1 - Q, 0), n * bs - Q + 1)
    return (q, w, pool, jnp.asarray(bt), jnp.asarray(seen),
            jnp.full((S,), Q, jnp.int32)), poison


def check_index(case, poison, atol=2e-4):
    q, w, pool, bt, seen, q_len = case
    got = sparse_index.paged_index_scores(q, w, pool.at[poison].set(jnp.nan), bt, seen, q_len,
                                          interpret=True)
    want = _index_scores_dense(q, w, pool.at[poison].set(0), bt, seen, pool.shape[2])
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert not np.isnan(got).any(), "the walk read past a row's live pages"
    assert (np.isfinite(got) == np.isfinite(want)).all()
    np.testing.assert_allclose(got[np.isfinite(want)], want[np.isfinite(want)], atol=atol,
                               rtol=1e-3)


@pytest.mark.parametrize("Q", [1, 8, 64])
def test_index_scores_live_pages_at_the_step_boundaries(Q):
    """Rows that end in a step's first page, its last, the next step's first
    and the table's last; steps past a row's live pages copy nothing and
    read ``-inf``."""
    MB, bs, W = 512, 32, 128
    P = sparse_index._pages_a_step(MB, bs, W, 4, Q)
    assert 1 < P < MB
    check_index(*make_index_case([max(1, Q // bs), P, P + 1, MB, 2 * P + 1], Q=Q, MB=MB,
                                 seed=Q))


def test_index_scores_bf16_products_accumulate_in_float32():
    case, poison = make_index_case([3, 20], Q=8, dtype=jnp.bfloat16, seed=5)
    q, w, pool, bt, seen, q_len = case
    got = sparse_index.paged_index_scores(q, w, pool, bt, seen, q_len, interpret=True)
    assert got.dtype == jnp.float32
    f32 = lambda a: a.astype(jnp.float32)
    want = _index_scores_dense(f32(q), w, f32(pool), bt, seen, pool.shape[2])
    fin = np.isfinite(np.asarray(want))
    # bfloat16 x bfloat16 is exact in float32: only the order of the sums differs
    np.testing.assert_allclose(np.asarray(got)[fin], np.asarray(want)[fin], atol=1e-4)
    assert sparse_index.scores_is_supported(q.shape, pool.shape)
    assert not sparse_index.scores_is_supported((2, 4, 4, 64), pool.shape)     # a chunk of 4
    assert not sparse_index.scores_is_supported(q.shape, (9, 1, 32, 64))       # half a tile


@pytest.mark.parametrize("rows,N,topk", [((4, 1), 1024, 100), ((1, 16), 2048, 700),
                                         ((3, 8), 384, 5), ((32, 1), 4096, 2048)])
def test_the_threshold_is_the_topk_th_largest_bit_for_bit(rows, N, topk):
    """Against ``jax.lax.top_k`` on the same float32 scores: rows of unequal
    visible length (``-inf`` behind), negative and positive scores, zeros
    that tie, a row that sees fewer than ``topk``."""
    S, Q = rows
    rng = np.random.default_rng(N + topk)
    x = rng.normal(size=(S, Q, N)).astype(np.float32) * 10 ** rng.uniform(-3, 3, (S, Q, 1))
    x[..., ::7] = 0.0                                   # exact ties, some at the threshold
    x[0, 0, ::3] = -0.0
    visible = rng.integers(1, N + 1, (S, Q))
    visible[0, 0], visible[-1, -1] = min(topk - 1, N), N
    x[np.arange(N)[None, None, :] >= visible[..., None]] = -np.inf
    got = sparse_index.topk_threshold(jnp.asarray(x), jnp.asarray(visible, jnp.int32), topk,
                                      interpret=True)
    want = np.asarray(_select_dense(jnp.asarray(x), topk))
    assert (np.asarray(got) == want).all()
    assert np.isneginf(np.asarray(got)[0, 0]) and np.isfinite(np.asarray(got)[-1, -1])
    assert sparse_index.threshold_is_supported(x.shape)
    assert not sparse_index.threshold_is_supported((1, 1, 200))


def test_the_float_keys_keep_the_order_of_the_floats():
    x = jnp.asarray([-np.inf, -3e38, -1.5, -1e-30, -0.0, 0.0, 1e-30, 2.5, 3e38, np.inf],
                    jnp.float32)
    keys = np.asarray(sparse_index._to_key(x))
    assert (np.diff(keys.astype(np.int64)) > 0).all()
    assert keys[0] == sparse_index._KEY_NEG_INF
    back = np.asarray(sparse_index._from_key(jnp.asarray(keys)))
    assert (back == np.asarray(x)).all() and np.signbit(back[4]) and not np.signbit(back[5])
