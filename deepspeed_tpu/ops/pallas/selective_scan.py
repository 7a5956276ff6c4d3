"""Pallas selective scan (the Mamba-1 recurrence) for the ragged engine.

For every row ``r`` and real position ``t < q_len[r]``::

    h_t = exp(Delta_t (x) A) * h_{t-1} + (Delta_t * c_t) (x) B_t
    y_t = h_t C_t + D * c_t

with ``h`` a ``[d_state, d_inner]`` float32 state per row that enters as
``h0`` and leaves as ``h_T`` (the state after position ``q_len - 1``).
Positions ``>= q_len`` are not run: their ``y`` is zero and they leave ``h``
untouched, so a padded dispatch (a ``[1, C]`` chunk of fewer than ``C`` tokens,
a ``[D, 1]`` decode dispatch of fewer than ``D`` rows, whose one time step is
padded to a chunk of eight here) advances each row by its own count of real
tokens and a row of no tokens hands its state back as it came.

TPU design: grid ``(rows, d_inner blocks)``; ``q_len`` is scalar-prefetched;
the state block lives in VMEM scratch across the time loop; ``d_inner`` is the
lane dimension everywhere (the state is kept ``[d_state, d_inner]``, the
transpose of the published ``[d_inner, d_state]``), so ``Delta_t`` and ``c_t``
broadcast over sublanes and ``B_t``/``C_t`` over lanes. ``B`` and ``C`` come in
as ``[rows, T/8, d_state, 8]``: a time chunk is picked on an untiled leading
dimension and its eight columns are static lane slices (a dynamic lane slice
per step is what this layout avoids). Time is walked in chunks of eight, a
chunk wholly past ``q_len`` is skipped.

``selective_scan_ref`` is the jnp twin (a ``lax.scan``): the CPU path and the
tests' oracle.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TIME_CHUNK = 8


def selective_scan_ref(c, delta, A, B, C, D, h0, q_len):
    """jnp twin. c, delta [R, T, Di]; A [N, Di]; B, C [R, T, N]; D [Di];
    h0 [R, N, Di] float32; q_len [R]. Returns (y [R, T, Di] in c's dtype,
    h_T [R, N, Di] float32)."""
    f32 = jnp.float32
    A, D = A.astype(f32), D.astype(f32)
    T = c.shape[1]

    def step(h, xs):
        c_t, d_t, b_t, c_out, t = xs
        valid = (t < q_len)[:, None]                          # [R, 1]
        d_t = jnp.where(valid, d_t.astype(f32), 0.0)
        x_t = c_t.astype(f32)
        h = jnp.exp(d_t[:, None, :] * A[None]) * h \
            + (d_t * x_t)[:, None, :] * b_t.astype(f32)[:, :, None]
        y = jnp.sum(h * c_out.astype(f32)[:, :, None], axis=1) + D * x_t
        return h, jnp.where(valid, y, 0.0)

    tm = lambda a: jnp.swapaxes(a, 0, 1)                      # time-major
    h, y = jax.lax.scan(step, h0.astype(f32),
                        (tm(c), tm(delta), tm(B), tm(C), jnp.arange(T)))
    return tm(y).astype(c.dtype), h


def _kernel(qlen_ref, c_ref, dt_ref, a_ref, b_ref, cc_ref, d_ref, h0_ref,
            y_ref, ht_ref, h_scr, *, n_chunks):
    n = qlen_ref[pl.program_id(0)]
    h_scr[...] = h0_ref[0]
    y_ref[...] = jnp.zeros_like(y_ref)
    A = a_ref[...]                                            # [N, d]
    skip = d_ref[...]                                         # [1, d]

    def chunk(k, carry):
        @pl.when(k * TIME_CHUNK < n)
        def _run():
            t0 = pl.multiple_of(k * TIME_CHUNK, TIME_CHUNK)
            x = c_ref[0, pl.ds(t0, TIME_CHUNK), :].astype(jnp.float32)
            dt = dt_ref[0, pl.ds(t0, TIME_CHUNK), :].astype(jnp.float32)
            bk = b_ref[0, k]                                  # [N, 8]
            ck = cc_ref[0, k]
            h = h_scr[...]
            rows = []
            for i in range(TIME_CHUNK):
                valid = t0 + i < n
                # Delta = 0 leaves h as it is: exp(0) * h + 0
                d_i = jnp.where(valid, dt[i:i + 1, :], 0.0)   # [1, d]
                x_i = x[i:i + 1, :]
                h = jnp.exp(d_i * A) * h + (d_i * x_i) * bk[:, i:i + 1]
                y_i = jnp.sum(h * ck[:, i:i + 1], axis=0, keepdims=True) \
                    + skip * x_i
                rows.append(jnp.where(valid, y_i, 0.0))
            h_scr[...] = h
            y_ref[0, pl.ds(t0, TIME_CHUNK), :] = \
                jnp.concatenate(rows, axis=0).astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n_chunks, chunk, 0)
    ht_ref[0] = h_scr[...]


def _d_block(d_inner):
    """Largest multiple of 128 lanes dividing ``d_inner``, at most 640 (ten
    vregs of float32 state a sublane tile)."""
    best = None
    for m in range(1, 6):
        if d_inner % (LANES * m) == 0:
            best = LANES * m
    return best or d_inner


def _selective_scan_local(c, delta, A, B, C, D, h0, q_len, *, interpret=False):
    R, T, Di = c.shape
    N = A.shape[0]
    pad = (-T) % TIME_CHUNK
    if pad:
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        c, delta, B, C = widen(c), widen(delta), widen(B), widen(C)
    Tp = T + pad
    n_chunks = Tp // TIME_CHUNK
    # [R, T, N] -> [R, T/8, N, 8]: chunk on a leading dim, time in lanes
    chunked = lambda a: a.astype(jnp.float32).reshape(
        R, n_chunks, TIME_CHUNK, N).transpose(0, 1, 3, 2)
    db = _d_block(Di)
    row_d = lambda r, j, ql: (r, 0, j)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R, Di // db),
        in_specs=[
            pl.BlockSpec((1, Tp, db), row_d),                          # c
            pl.BlockSpec((1, Tp, db), row_d),                          # Delta
            pl.BlockSpec((N, db), lambda r, j, ql: (0, j)),            # A
            pl.BlockSpec((1, n_chunks, N, TIME_CHUNK),
                         lambda r, j, ql: (r, 0, 0, 0)),               # B
            pl.BlockSpec((1, n_chunks, N, TIME_CHUNK),
                         lambda r, j, ql: (r, 0, 0, 0)),               # C
            pl.BlockSpec((1, db), lambda r, j, ql: (0, j)),            # D
            pl.BlockSpec((1, N, db), row_d),                           # h0
        ],
        out_specs=[pl.BlockSpec((1, Tp, db), row_d),
                   pl.BlockSpec((1, N, db), row_d)],
        scratch_shapes=[pltpu.VMEM((N, db), jnp.float32)],
    )
    with jax.named_scope("selective_scan"):
        y, h_t = pl.pallas_call(
            functools.partial(_kernel, n_chunks=n_chunks),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((R, Tp, Di), c.dtype),
                       jax.ShapeDtypeStruct((R, N, Di), jnp.float32)],
            name="selective_scan",
            interpret=interpret,
        )(q_len.astype(jnp.int32), c, delta, A.astype(jnp.float32), chunked(B),
          chunked(C), D.astype(jnp.float32).reshape(1, Di),
          h0.astype(jnp.float32))
    return (y[:, :T] if pad else y), h_t


def is_supported(d_inner, d_state):
    return d_inner % LANES == 0 and d_state % 8 == 0


def selective_scan(c, delta, A, B, C, D, h0, q_len, *, interpret=False):
    """The scan over ``[rows, T, d_inner]`` (module docstring). Through the
    kernel dispatcher: rows shard over the mesh's data axes, ``d_inner`` (the
    channels are independent) over the TP axis."""
    from deepspeed_tpu.ops.registry import sharded_kernel_call

    call = functools.partial(_selective_scan_local, interpret=interpret)

    def accept(shard_shapes):
        return is_supported(shard_shapes[0][2], shard_shapes[2][0])

    roles = [("data", None, "head"), ("data", None, "head"), (None, "head"),
             ("data", None, None), ("data", None, None), ("head",),
             ("data", None, "head"), ("data",)]
    return sharded_kernel_call(
        call, [c, delta, A, B, C, D, h0, q_len], roles,
        [("data", None, "head"), ("data", None, "head")], accept=accept,
        name="selective_scan")
