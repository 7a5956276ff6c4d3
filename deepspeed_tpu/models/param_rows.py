"""A parameter tree listed as rows ``(path, shape, fill, dtype, stacked)``:
the form ``benchmark/weights.py`` fills on the device and the in-tree serving
models (``phi4flash.py``, ``mellum2.py``) list themselves in. ``path`` is a
tuple of dict keys, ``fill`` a std or ``("const", value)``."""

import jax
import jax.numpy as jnp


def init_tree(rows, rng):
    """A random nested dict of the rows: normal with each row's std,
    constants as given."""
    tree = {}
    for i, (path, shape, fill, dtype, _) in enumerate(rows):
        if isinstance(fill, tuple):
            leaf = jnp.full(shape, fill[1], dtype)
        else:
            leaf = (jax.random.normal(jax.random.fold_in(rng, i), shape,
                                      jnp.float32) * fill).astype(dtype)
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = leaf
    return tree
