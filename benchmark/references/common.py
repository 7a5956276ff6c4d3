"""Precision modes shared by the plain references.

``f32``: float32 at ``highest`` matmul precision, the reference proper.
``int8``: the control, the nearest precision below the bfloat16 the
configurations state and the one this chip tempts with (393 TOP/s int8):
every matmul, in the forward AND the backward pass, has both operands
fake-quantised to symmetric int8 along the contracted axis (one scale per row
of the left operand and per column of the right) and multiplied exactly.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _q(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


@jax.custom_vjp
def _int8_matmul(x, w):
    return _mm(_q(x, -1), _q(w, 0))


def _int8_fwd(x, w):
    return _int8_matmul(x, w), (x, w)


def _int8_bwd(res, g):
    x, w = res
    x2, g2 = x.reshape(-1, x.shape[-1]), g.reshape(-1, g.shape[-1])
    dx = _mm(_q(g, -1), _q(w, 1).T)                 # contracts the output axis
    dw = _mm(_q(x2, 0).T, _q(g2, 0))                # contracts the rows
    return dx, dw


_int8_matmul.defvjp(_int8_fwd, _int8_bwd)


def matmul(x, w, precision):
    """x [..., K] @ w [K, N] in float32; ``precision`` is "f32" or "int8"."""
    if precision == "int8":
        return _int8_matmul(x, w)
    if precision != "f32":
        raise ValueError(f"unknown reference precision {precision!r}")
    return _mm(x, w)
