"""Pallas kernels for gated delta-rule linear attention (Kimi Delta Attention,
arXiv:2510.26692) in the ragged engine: a matrix state a head, decayed by a
vector (a factor a key channel), corrected by a rank-one term that reads the
decayed state, read with q.

For a row ``r``, a head ``j`` and a real position ``t``, with ``S`` the head's
``[d_k, d_v]`` float32 state (key x value)::

    S' = Diag(exp(g_t)) S_{t-1}
    u_t = b_t (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

``g <= 0`` is the log of the decay and ``b`` in (0, 1) the correction's
strength. The caller hands ``g = 0`` and ``b = 0`` at a position that holds no
token (``>= q_len``): such a position leaves the state as it was, and what it
reads is not used. The state lives in a pool of slots ``[N, H, d_k, d_v]``
(the merged slot pool of the layer loop, ``paged_layer.merge_layers``); a row
reads and writes slot ``slots[r]``, in place (the pool is aliased to the
output), and a row whose ``keep`` is 0 (a sequence's first chunk) starts from
zero whatever its slot held.

``kda_step`` (one token a row): grid ``(rows, head blocks)``; a block of
``heads`` states (``heads x 64 KB``) comes in, is decayed, corrected, read and
goes out: one read and one write of the state, which is all a decode round's
KDA layers are (bandwidth). The vectors that scale the state's ROWS (the decay,
k, b k and q) arrive as columns of one ``[d_k, 4 heads]`` tile a block, which
the caller lays out (``_columns``), so the kernel is element-wise multiplies
and sublane reductions and no transpose.

``kda_chunk`` (a chunk of a prompt a row): grid ``(rows, heads)``; the row's
``T`` positions are walked in chunks of 64 with the state in VMEM scratch, a
chunk wholly past ``q_len`` skipped. A chunk from state ``S_0``, with ``G_t =
sum_{i <= t} g_i`` and ``Gam = exp(G)``::

    A[t, i] = b_t sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])         i < t
    (I + A) U = Diag(b) (V - (K * Gam) S_0)
    o_t = S_0^T (q_t * Gam_t) + sum_{i <= t} u_i sum_c q_t[c] k_i[c] exp(G_t[c] - G_i[c])
    S_C = Diag(Gam_C) S_0 + sum_i (k_i * exp(G_C - G_i)) u_i^T

Every exponent is ``<= 0`` and stays so in the way the products are formed:
``(K * Gam)(K / Gam)^T`` overflows float32 once ``-G`` passes ~88 inside a
chunk, which strong gates reach in a few tokens. So a pair ``(t, i)`` of
DIFFERENT sub-chunks of 16 is factored against the query's sub-chunk's start
``R`` (``exp(G_t - R) <= 1`` times ``exp(R - G_i) <= 1``: if a factor
underflows, so does the product), on the MXU; a pair of the SAME sub-chunk is
computed pairwise, ``exp(G_t - G_i)`` itself, a key at a time. ``(I + A)`` is
unit lower triangular: its diagonal blocks of 16 are inverted by doubling
(``(I + B)^-1 = (I - B)(I + B^2)(I + B^4)(I + B^8)``, B nilpotent of index 16),
the blocks below them by forward substitution (``_solve_unit_lower`` says why
not doubling over the whole chunk).

``kda_step_ref`` and ``kda_chunk_ref`` are the ``jax.numpy`` twins (the chunk
form too: the same sub-chunks, ``solve_triangular`` for the system): the CPU
path and the tests' oracle. ``model_implementations/kimi_linear.py`` chooses.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
CHUNK = 64            # tokens a chunk of the chunk form
SUB = 16              # tokens a sub-chunk: pairs inside one are computed pairwise
STEP_HEADS = 16       # heads a block of the step kernel (16 x 64 KB of state)
HIGHEST = jax.lax.Precision.HIGHEST


# -- the jax.numpy twins ------------------------------------------------------

def kda_step_ref(q, k, v, g, beta, pool, slots, keep):
    """One token a row. q, k, g [R, H, dk]; v [R, H, dv]; beta [R, H]; pool
    [N, H, dk, dv] float32; slots [R] int32; keep [R] (0: start from zero).
    Returns (o [R, H, dv] float32, the pool with the rows' slots updated)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    S = jnp.where((keep != 0)[:, None, None, None], pool[slots], 0.0)
    S = S * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(S * k[..., None], axis=2))
    S = S + k[..., None] * u[:, :, None, :]
    o = jnp.sum(S * q[..., None], axis=2)
    return o, pool.at[slots].set(S)


def _chunk_ref(S0, xs):
    """One chunk of ``CHUNK`` positions from state S0 [R, H, dk, dv]; ``xs``
    are q, k, g [R, H, C, dk], v [R, H, C, dv], beta [R, H, C]."""
    q, k, v, g, beta = xs
    R, H, C, dk = q.shape
    n = C // SUB
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    G = jnp.cumsum(g, axis=2)                                  # [R, H, C, dk]
    sub = lambda a: a.reshape(R, H, n, SUB, a.shape[-1])
    Gs = sub(G)
    # the start of a query's sub-chunk: G at the last position before it
    ref = jnp.concatenate([jnp.zeros_like(Gs[:, :, :1, 0]), Gs[:, :, :-1, -1]], 2)
    q_dec = jnp.exp(Gs - ref[:, :, :, None, :])                # <= 1
    k_dec = jnp.exp(jnp.minimum(ref[:, :, :, None, :] - G[:, :, None], 0.0))
    keys = k[:, :, None] * k_dec                               # [R, H, n, C, dk]
    pair = jnp.exp(jnp.minimum(Gs[:, :, :, :, None] - Gs[:, :, :, None, :], 0.0))
    t = jnp.arange(C)
    other = (t[:, None] // SUB) > (t[None, :] // SUB)          # an earlier sub-chunk
    eye = jnp.eye(n, dtype=G.dtype)

    def scores(x):
        """sum_c x_t[c] k_i[c] exp(G_t[c] - G_i[c]) for i <= t -> [R, H, C, C]"""
        far = mm("rhItc,rhIic->rhIti", sub(x) * q_dec, keys).reshape(R, H, C, C)
        near = mm("rhItc,rhIic,rhItic->rhIti", sub(x), sub(k), pair)
        near = mm("rhIti,IJ->rhItJi", near, eye).reshape(R, H, C, C)
        return jnp.where(other, far, near)

    A = jnp.where(t[:, None] > t[None, :], scores(k), 0.0) * beta[..., None]
    gam = jnp.exp(G)
    rhs = beta[..., None] * (v - mm("rhtc,rhcd->rhtd", k * gam, S0))
    U = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(C, dtype=A.dtype), rhs, lower=True, unit_diagonal=True)
    reads = jnp.where(t[:, None] >= t[None, :], scores(q), 0.0)
    o = mm("rhtc,rhcd->rhtd", q * gam, S0) + mm("rhti,rhid->rhtd", reads, U)
    last = G[:, :, -1:]                                        # [R, H, 1, dk]
    S = gam[:, :, -1, :, None] * S0 \
        + mm("rhic,rhid->rhcd", k * jnp.exp(last - G), U)
    return S, o


def kda_chunk_ref(q, k, v, g, beta, pool, slots, keep, q_len=None):
    """A chunk of a prompt a row, in the chunk form (module docstring). q, k,
    g [R, T, H, dk]; v [R, T, H, dv]; beta [R, T, H]; pool, slots, keep as
    ``kda_step_ref``'s; ``q_len`` is not read (g and beta are 0 past it).
    Returns (o [R, T, H, dv] float32, the pool updated)."""
    del q_len
    f32 = jnp.float32
    R, T, H, _ = q.shape
    pad = (-T) % CHUNK
    n = (T + pad) // CHUNK

    def chunks(a):
        a = a.astype(f32)
        if a.ndim == 3:
            a = a[..., None]
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        # [R, T, H, x] -> [n, R, H, C, x]
        return a.reshape(R, n, CHUNK, H, a.shape[-1]).transpose(1, 0, 3, 2, 4)

    xs = (chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)[..., 0])
    S0 = jnp.where((keep != 0)[:, None, None, None], pool[slots], 0.0)
    S, o = jax.lax.scan(_chunk_ref, S0, xs)
    o = o.transpose(1, 0, 3, 2, 4).reshape(R, n * CHUNK, H, o.shape[-1])
    return o[:, :T], pool.at[slots].set(S)


# -- the step kernel ----------------------------------------------------------

def _step_kernel(slots_ref, keep_ref, cols_ref, v_ref, s_ref, o_ref, out_ref,
                 *, heads):
    del slots_ref
    keep = keep_ref[pl.program_id(0)] != 0
    cols = cols_ref[0, 0]                                      # [dk, 4 heads]
    col = lambda part, h: cols[:, part * heads + h:part * heads + h + 1]
    for h in range(heads):
        S = jnp.where(keep, s_ref[0, h], 0.0) * col(3, h)      # decayed
        u = v_ref[0, h:h + 1, :] - jnp.sum(S * col(2, h), axis=0, keepdims=True)
        S = S + col(1, h) * u
        out_ref[0, h] = S
        o_ref[0, h:h + 1, :] = jnp.sum(S * col(0, h), axis=0, keepdims=True)


def _columns(q, k, g, beta, heads):
    """[R, H / heads, dk, 4 heads] float32: a block of heads' q, k, b k and
    exp(g) as COLUMNS (what scales the state's rows), laid out once by XLA."""
    parts = jnp.stack([q, k, k * beta[..., None], jnp.exp(g)], axis=1)
    R, _, H, dk = parts.shape                                  # [R, 4, H, dk]
    parts = parts.reshape(R, 4, H // heads, heads, dk)
    return parts.transpose(0, 2, 4, 1, 3).reshape(R, H // heads, dk, 4 * heads)


def _step_heads(H, want):
    """Heads a block: the most, up to ``want``, that divide ``H`` in whole
    sublane tiles of 8 (the block of ``[heads, dv]`` rows a step reads and
    writes), else all of them."""
    for heads in range(min(want, H), 7, -1):
        if H % heads == 0 and heads % 8 == 0:
            return heads
    return H


def step_is_supported(heads, dk, dv):
    return dk % 8 == 0 and dv % LANES == 0


def kda_step(q, k, v, g, beta, pool, slots, keep, *, heads=STEP_HEADS,
             interpret=False):
    """``kda_step_ref`` by the kernel (module docstring); the pool is updated
    in place."""
    f32 = jnp.float32
    R, H, dk = q.shape
    dv = v.shape[-1]
    heads = _step_heads(H, heads)
    assert step_is_supported(H, dk, dv)
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, H // heads),
        in_specs=[
            pl.BlockSpec((1, 1, dk, 4 * heads), lambda r, h, sl, kp: (r, h, 0, 0)),
            pl.BlockSpec((1, heads, dv), lambda r, h, sl, kp: (r, h, 0)),
            pl.BlockSpec((1, heads, dk, dv), lambda r, h, sl, kp: (sl[r], h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, heads, dv), lambda r, h, sl, kp: (r, h, 0)),
            pl.BlockSpec((1, heads, dk, dv), lambda r, h, sl, kp: (sl[r], h, 0, 0)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_step_kernel, heads=heads),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, H, dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, f32)],
        # operands: slots, keep, columns, b v, the pool
        input_output_aliases={4: 1},
        name="kda_step",
        interpret=interpret,
    )(slots.astype(jnp.int32), keep.astype(jnp.int32),
      _columns(q, k, g, beta, heads), v * beta[..., None], pool)


# -- the chunk kernel ---------------------------------------------------------

def _solve_unit_lower(A, rhs):
    """``(I + A)^-1 rhs`` for a strictly lower triangular A [C, C], rhs [C,
    dv]: the diagonal blocks of ``SUB`` inverted together by doubling (``(I +
    B)^-1 = (I - B)(I + B^2)(I + B^4)(I + B^8)`` for the block-diagonal part
    B, nilpotent of index ``SUB``), then forward substitution over the blocks.
    Doubling over the WHOLE chunk is not stable: with correlated keys (A's
    entries near b) ``A^32`` holds entries of 1e17 that cancel to an inverse
    of entries under 1, and float32 keeps none of it; within 16 the powers
    stay under 1e4."""
    C = A.shape[0]
    dot = functools.partial(jnp.dot, precision=HIGHEST,
                            preferred_element_type=jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    same = (row // SUB) == (lane // SUB)
    P = jnp.where(same, -A, 0.0)
    off = jnp.where(same, 0.0, A)
    T = jnp.where(row == lane, 1.0, 0.0) + P
    span = 2
    while span < SUB:
        P = dot(P, P)
        T = T + dot(T, P)
        span *= 2
    n, blocks, done = C // SUB, [], None
    for I in range(n):
        # rhs less what the blocks solved so far take from every row: this
        # block's rows are final, and T's rows of this block are zero outside
        # the block's own columns, so the other rows are not read
        resid = rhs if done is None else rhs - dot(off, done)
        blocks.append(dot(T[I * SUB:(I + 1) * SUB], resid))
        if I + 1 < n:
            done = jnp.concatenate(
                blocks + [jnp.zeros((C - (I + 1) * SUB, rhs.shape[1]), rhs.dtype)], 0)
    return jnp.concatenate(blocks, 0)


def _chunk_kernel(slots_ref, keep_ref, qlen_ref, q_ref, k_ref, kb_ref, vb_ref,
                  g_ref, s_ref, o_ref, out_ref, s_scr, *, n_chunks):
    del slots_ref
    C, n_sub = CHUNK, CHUNK // SUB
    f32 = jnp.float32
    r = pl.program_id(0)
    n = qlen_ref[r]
    s_scr[...] = jnp.where(keep_ref[r] != 0, s_ref[0, 0], 0.0)
    o_ref[...] = jnp.zeros_like(o_ref)
    dot = functools.partial(jnp.dot, precision=HIGHEST, preferred_element_type=f32)
    # a @ b^T
    dot_t = lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=HIGHEST, preferred_element_type=f32)
    # a^T @ b
    t_dot = lambda a, b: jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), precision=HIGHEST, preferred_element_type=f32)
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    lower = (row >= lane).astype(f32)                          # the running sum
    sub_lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, C), 1)

    def chunk(c, carry):
        @pl.when(c * C < n)
        def _run():
            t0 = pl.multiple_of(c * C, C)
            at = lambda ref: ref[0, pl.ds(t0, C), :]
            q, k, kb, vb, g = at(q_ref), at(k_ref), at(kb_ref), at(vb_ref), at(g_ref)
            S0 = s_scr[...]
            G = dot(lower, g)                                  # [C, dk], <= 0
            kk_rows, qk_rows = [], []
            for I in range(n_sub):
                lo = I * SUB
                G_I = G[lo:lo + SUB]
                ref = G[lo - 1:lo] if I else jnp.zeros_like(G[:1])
                # another sub-chunk's keys: factored against this one's start
                keys = k * jnp.exp(jnp.minimum(ref - G, 0.0))  # [C, dk]
                dec = jnp.exp(G_I - ref)                       # [SUB, dk]
                kk = dot_t(kb[lo:lo + SUB] * dec, keys)        # [SUB, C]
                qk = dot_t(q[lo:lo + SUB] * dec, keys)
                far = sub_lane < lo
                kk, qk = jnp.where(far, kk, 0.0), jnp.where(far, qk, 0.0)
                # this sub-chunk's keys: pairwise, a key at a time
                for i in range(SUB):
                    e = jnp.exp(jnp.minimum(G_I - G_I[i:i + 1], 0.0)) \
                        * k[lo + i:lo + i + 1]                 # [SUB, dk]
                    here = sub_lane == lo + i
                    kk = jnp.where(here, jnp.sum(kb[lo:lo + SUB] * e, 1, keepdims=True), kk)
                    qk = jnp.where(here, jnp.sum(q[lo:lo + SUB] * e, 1, keepdims=True), qk)
                kk_rows.append(kk)
                qk_rows.append(qk)
            A = jnp.where(row > lane, jnp.concatenate(kk_rows, 0), 0.0)
            reads = jnp.where(row >= lane, jnp.concatenate(qk_rows, 0), 0.0)
            gam = jnp.exp(G)
            U = _solve_unit_lower(A, vb - dot(kb * gam, S0))
            o_ref[0, pl.ds(t0, C), :] = dot(q * gam, S0) + dot(reads, U)
            last = G[C - 1:C]                                  # [1, dk]
            # Diag(Gam_C) S0: the decay laid along the state's rows, every
            # column alike, by a product of eight rows (the first the decay,
            # the others zero) with ones: a [1, dk] row has no cheaper way up
            first = jax.lax.broadcasted_iota(jnp.int32, (8, G.shape[1]), 0) == 0
            down = t_dot(jnp.where(first, jnp.exp(last), 0.0),
                         jnp.ones((8, S0.shape[1]), f32))      # [dk, dv]
            s_scr[...] = down * S0 + t_dot(k * jnp.exp(last - G), U)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk, 0)
    out_ref[0, 0] = s_scr[...]


def chunk_is_supported(heads, dk, dv):
    return dk % LANES == 0 and dv % LANES == 0


def kda_chunk(q, k, v, g, beta, pool, slots, keep, q_len, *, interpret=False):
    """``kda_chunk_ref`` by the kernel (module docstring); the pool is
    updated in place. A chunk wholly past ``q_len`` reads zero; what a
    position past it inside a row's last chunk reads is not used."""
    f32 = jnp.float32
    R, T, H, dk = q.shape
    dv = v.shape[-1]
    assert chunk_is_supported(H, dk, dv)
    pad = (-T) % CHUNK
    Tp = T + pad

    def flat(a):
        a = a.astype(f32)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return a.reshape(R, Tp, H * a.shape[-1])

    b = beta.astype(f32)[..., None]
    seq = lambda w: pl.BlockSpec((1, Tp, w), lambda r, h, sl, kp, ql: (r, 0, h))
    state = pl.BlockSpec((1, 1, dk, dv), lambda r, h, sl, kp, ql: (sl[r], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R, H),
        in_specs=[seq(dk), seq(dk), seq(dk), seq(dv), seq(dk), state],
        out_specs=[seq(dv), state],
        scratch_shapes=[pltpu.VMEM((dk, dv), f32)],
    )
    o, pool = pl.pallas_call(
        functools.partial(_chunk_kernel, n_chunks=Tp // CHUNK),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, Tp, H * dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, f32)],
        # operands: slots, keep, q_len, q, k, b k, b v, g, the pool
        input_output_aliases={8: 1},
        name="kda_chunk",
        interpret=interpret,
    )(slots.astype(jnp.int32), keep.astype(jnp.int32), q_len.astype(jnp.int32),
      flat(q), flat(k), flat(k.astype(f32) * b), flat(v.astype(f32) * b), flat(g),
      pool)
    return o.reshape(R, Tp, H, dv)[:, :T], pool
