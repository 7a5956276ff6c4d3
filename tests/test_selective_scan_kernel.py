"""The selective-scan kernel (``ops/pallas/selective_scan.py``): interpret
mode against its jnp twin at the shapes the engine dispatches (``[1, C]``
prompt chunks, ``[D, 1]`` decode rows) and at ragged ``[D, 8]`` rows, and the
twin against the recurrence written out in numpy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import selective_scan as ss


def _data(R, T, Di, N, seed=0, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    c = jax.random.normal(ks[0], (R, T, Di), jnp.float32).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (R, T, Di)) - 2.0)
    A = -jnp.exp(0.3 * jax.random.normal(ks[2], (N, Di)))
    B = jax.random.normal(ks[3], (R, T, N))
    C = jax.random.normal(ks[4], (R, T, N))
    D = jax.random.normal(ks[5], (Di,))
    h0 = jax.random.normal(ks[6], (R, N, Di))
    return c, delta, A, B, C, D, h0


@pytest.mark.parametrize("R,T,Di,N,q_len", [
    (1, 16, 256, 16, [13]),                 # a [1, C] chunk, not full
    (1, 64, 128, 8, [64]),                  # a full one
    (4, 8, 128, 16, [1, 8, 0, 3]),          # [D, 8]: decode, full, padded row, a tail
    (4, 1, 128, 16, [1, 1, 0, 1]),          # [D, 1]: a decode dispatch, a padded row
    (8, 1, 1280, 16, [1] * 8),              # the same over two blocks of d_inner
    (8, 8, 1280, 16, [1] * 8),              # two blocks of d_inner
    (2, 12, 128, 8, [12, 5]),               # T no multiple of the time chunk
])
def test_interpret_mode_equals_the_twin(R, T, Di, N, q_len):
    args = _data(R, T, Di, N)
    q = jnp.asarray(q_len, jnp.int32)
    y_ref, h_ref = ss.selective_scan_ref(*args, q)
    y, h = ss.selective_scan(*args, q, interpret=True)
    assert y.dtype == args[0].dtype and h.dtype == jnp.float32
    # y is rounded to bf16 once on each side from float32 sums whose order
    # differs (a sublane reduce against jnp.sum): one bf16 ulp of |y| <~ 8
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
                               atol=2 ** -4, rtol=2 ** -7)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kernel", [False, True])
def test_positions_past_q_len_leave_state_and_output_alone(kernel):
    """A padded row hands its state back bit for bit; a row of k real tokens
    ends where the same row cut to k tokens ends; y is zero past q_len."""
    R, T, Di, N = 4, 8, 128, 8
    args = _data(R, T, Di, N, seed=1, dtype=jnp.float32)
    q = jnp.asarray([0, 3, 8, 1], jnp.int32)
    fn = (lambda *a: ss.selective_scan(*a, interpret=True)) if kernel else ss.selective_scan_ref
    y, h = fn(*args, q)
    np.testing.assert_array_equal(np.asarray(h[0]), np.asarray(args[6][0]))
    for r, k in enumerate([0, 3, 8, 1]):
        assert not np.any(np.asarray(y[r, k:]))
        if k:
            cut = [a[r:r + 1, :k] if a.ndim == 3 and a.shape[1] == T else a for a in args]
            cut[6] = args[6][r:r + 1]
            y_k, h_k = ss.selective_scan_ref(*cut, jnp.asarray([k], jnp.int32))
            np.testing.assert_allclose(np.asarray(h[r]), np.asarray(h_k[0]), atol=1e-6, rtol=1e-6)
            np.testing.assert_allclose(np.asarray(y[r, :k]), np.asarray(y_k[0]),
                                       atol=1e-5, rtol=1e-5)


def test_twin_is_the_recurrence():
    R, T, Di, N = 2, 6, 8, 4
    c, delta, A, B, C, D, h0 = (np.asarray(a, np.float64) for a in
                                _data(R, T, Di, N, seed=2, dtype=jnp.float32))
    y_want = np.zeros((R, T, Di))
    h = h0.copy()
    for t in range(T):
        h = np.exp(delta[:, t, None, :] * A[None]) * h \
            + (delta[:, t] * c[:, t])[:, None, :] * B[:, t, :, None]
        y_want[:, t] = (h * C[:, t, :, None]).sum(1) + D * c[:, t]
    y, h_t = ss.selective_scan_ref(*(jnp.asarray(a, jnp.float32) for a in
                                     (c, delta, A, B, C, D, h0)),
                                   jnp.full((R,), T, jnp.int32))
    np.testing.assert_allclose(np.asarray(y), y_want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_t), h, atol=1e-5, rtol=1e-5)


def test_shapes_the_kernel_takes():
    assert ss.is_supported(5120, 16) and ss.is_supported(128, 8)
    assert not ss.is_supported(96, 16) and not ss.is_supported(128, 4)
    assert ss._d_block(5120) == 640 and ss._d_block(128) == 128 and ss._d_block(1280) == 640
