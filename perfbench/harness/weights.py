"""Weights from the seed, made on the device in one jitted call.

What every family shares. A family (``perfbench/families/<family>.py``)
states its tree as a *layout*, ``{path: (shape, kind, std)}`` with ``path``
a tuple of dict keys, ``kind`` ``"normal"`` (``std`` x a standard normal)
or ``"scale"`` (1 + that), and the type its leaves are held in; this
module makes the tree, one leaf of it alone, and the distance of a trained
tree from what the seed gave.

Every leaf has a key of its own (``fold_in(key, index)`` over the leaves in
``jax.tree`` order), so a single leaf can be made again alone: a family
whose float32 reference does not fit beside anything builds its weights a
layer at a time through ``make_leaf``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds run past 2**31)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _nest(flat: dict):
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf
    return tree


def param_shapes(layout: dict, dtype=jnp.float32):
    return _nest({p: jax.ShapeDtypeStruct(s, dtype)
                  for p, (s, _, _) in layout.items()})


def _make_leaf(key, index, shape, kind, std, dtype=jnp.float32):
    x = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32) * std
    return (1.0 + x if kind == "scale" else x).astype(dtype)


def _order(layout: dict) -> dict:
    """jax.tree order over nested dicts is sorted by key at every level;
    the leaves are numbered in that order so that a leaf's key never
    depends on how a layout happens to be written."""
    return {p: i for i, p in enumerate(sorted(layout))}


def make_leaf(layout: dict, seed: int, path: tuple, dtype=jnp.float32):
    """One leaf alone, the same values ``make_params`` gives it."""
    shape, kind, std = layout[path]
    return jax.jit(_make_leaf, static_argnums=(2, 3, 4, 5))(
        seed_key(seed), jnp.int32(_order(layout)[path]), shape, kind, std, dtype)


def make_params(layout: dict, seed: int, shardings=None, dtype=jnp.float32):
    """The whole tree in one jitted call; ``shardings`` (a matching tree
    of shardings, or None for the default device) places the leaves."""
    order = _order(layout)

    def build(key):
        return _nest({p: _make_leaf(key, order[p], s, kind, std, dtype)
                      for p, (s, kind, std) in layout.items()})

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


def change_norms(layout: dict, seed: int, params, dtype=jnp.float32) -> list:
    """Per leaf (``jax.tree`` order) the norm of ``params - make_params``:
    how far training has moved each leaf from what the seed gave. Every
    leaf is made again alone, so nothing the size of the model is held
    twice; one small program per distinct leaf shape."""
    key = seed_key(seed)
    progs = {}
    out = []
    leaves = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    by_path = {tuple(k.key for k in path): leaf for path, leaf in leaves.items()}
    for index, path in enumerate(sorted(layout)):
        shape, kind, std = layout[path]
        sig = (shape, kind, std)
        if sig not in progs:
            progs[sig] = jax.jit(
                lambda p, k, i, shape=shape, kind=kind, std=std: jnp.sqrt(jnp.sum(
                    (p.astype(jnp.float32)
                     - _make_leaf(k, i, shape, kind, std, dtype).astype(jnp.float32)) ** 2)))
        out.append(progs[sig](by_path[path], key, jnp.int32(index)))
    return [float(x) for x in out]


def row_shardings(layout: dict, devices):
    """For a cell on several chips: every leaf split over the chips along
    its first dimension that divides evenly, so that the weights are never
    whole on one chip (1.56 G float32 parameters with Adam's state do not
    fit one). One chip, or a leaf that does not divide, stays whole."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if len(devices) == 1:
        return None
    mesh = Mesh(np.array(devices), ("chips",))
    n = len(devices)

    def leaf(shape_dtype):
        for dim, size in enumerate(shape_dtype.shape):
            if size % n == 0:
                return NamedSharding(mesh, P(*([None] * dim + ["chips"])))
        return NamedSharding(mesh, P())

    return jax.tree.map(leaf, param_shapes(layout))
