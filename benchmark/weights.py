"""Seeded weights, made on the device from integer random bits.

Every leaf is ``uniform(-a, a)`` with ``a = sqrt(3) * std`` (or a constant),
computed as ``(bits - 32768) * (a / 32768)`` from 16 random bits: integer
arithmetic and one float32 multiply, so the value of an element depends on
(seed, leaf name, layer, index) alone and not on which program computes it.
The system under test gets whole stacked leaves from ONE jitted call; the
plain references regenerate one layer at a time from the same keys and never
see an array the program has held.

A spec is a list of ``(path, shape, fill, dtype, stacked)``: ``path`` a tuple
of dict keys, ``fill`` a std (float) or ``("const", value)``, ``stacked`` True
when ``shape[0]`` is the layer axis.
"""

import functools
import math
import zlib

import jax
import jax.numpy as jnp


def base_key(seed):
    """A PRNG key for any non-negative whole-number seed (the driver's are
    larger than int32 holds)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf_key(key, path):
    return jax.random.fold_in(key, zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF)


def _fill(key, shape, fill, dtype):
    if isinstance(fill, tuple):
        return jnp.full(shape, fill[1], dtype)
    bits = jax.random.bits(key, shape, jnp.uint16).astype(jnp.int32) - 32768
    scale = jnp.float32(math.sqrt(3.0) * fill / 32768.0)
    return (bits.astype(jnp.float32) * scale).astype(dtype)


def leaf(key, path, shape, fill, dtype, stacked, layer=None):
    """One leaf; with ``layer`` given (an int or a traced scalar), that
    layer's slice of a stacked leaf."""
    k = _leaf_key(key, path)
    if not stacked:
        return _fill(k, shape, fill, dtype)
    one = lambda i: _fill(jax.random.fold_in(k, i), shape[1:], fill, dtype)
    if layer is not None:
        return one(layer)
    return jax.vmap(one)(jnp.arange(shape[0]))


def _nest(flat):
    tree = {}
    for path, value in flat:
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = value
    return tree


def layer_tree(key, spec, layer):
    """The stacked leaves' slices for one layer, as a nested dict."""
    return _nest([(p, leaf(key, p, s, f, d, True, layer))
                  for p, s, f, d, stacked in spec if stacked])


def resident_tree(key, spec):
    """The leaves that are not stacked by layer, as a nested dict."""
    return _nest([(p, leaf(key, p, s, f, d, False))
                  for p, s, f, d, stacked in spec if not stacked])


def full_tree(key, spec):
    return _nest([(p, leaf(key, p, s, f, d, stacked))
                  for p, s, f, d, stacked in spec])


def one_leaf(key, spec, path):
    """The leaf of ``spec`` at ``path``, alone."""
    return leaf(key, *next(s for s in spec if s[0] == path))


def leaf_norms(tree):
    """{"a/b/c": L2 norm in float32} over the leaves of a nested dict."""
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def make_params(seed, spec):
    """The whole parameter tree in one jitted call on the default device."""
    spec = tuple((tuple(p), tuple(s), f, d, st) for p, s, f, d, st in spec)
    return _make(base_key(seed), spec)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec):
    return full_tree(key, spec)
