"""Weights from the seed, made on the device in one jitted call.

The tree has the layout the program's transformer reads (``embed``,
``pos_embed``, ``layers_<i>`` with ``ln1 / attn.{wq,wk,wv,wo} / ln2 /
mlp.{fc1,fc2}``, ``ln_f``) and float32 leaves, the type the program holds
parameters in. Values follow GPT-2's initialisation (normal, 0.02; the two
projections into the residual stream scaled by 1/sqrt(2 L)), except that
LayerNorm scales, and every bias, are small random numbers and not ones and
zeros: a path that drops a bias or a scale then shows in the comparison.

Every leaf has a key of its own (``fold_in(key, index)`` over the leaves in
``jax.tree`` order), so a single leaf can be made again alone.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds run past 2**31)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _layout(model: dict):
    """``{path: (shape, kind, std)}`` with path a tuple of dict keys."""
    d, f, v = model["n_embd"], model["n_inner"], model["vocab_size"]
    resid = 0.02 / math.sqrt(2 * model["n_layer"])
    out = {("embed", "embedding"): ((v, d), "normal", 0.02),
           ("pos_embed", "embedding"): ((model["n_positions"], d), "normal", 0.02),
           ("ln_f", "scale"): ((d,), "scale", 0.1),
           ("ln_f", "bias"): ((d,), "normal", 0.02)}
    for i in range(model["n_layer"]):
        lay = f"layers_{i}"
        for ln in ("ln1", "ln2"):
            out[(lay, ln, "scale")] = ((d,), "scale", 0.1)
            out[(lay, ln, "bias")] = ((d,), "normal", 0.02)
        for w, (a, b, std) in {"wq": (d, d, 0.02), "wk": (d, d, 0.02),
                               "wv": (d, d, 0.02), "wo": (d, d, resid)}.items():
            out[(lay, "attn", w, "kernel")] = ((a, b), "normal", std)
            out[(lay, "attn", w, "bias")] = ((b,), "normal", 0.01)
        for w, (a, b, std) in {"fc1": (d, f, 0.02), "fc2": (f, d, resid)}.items():
            out[(lay, "mlp", w, "kernel")] = ((a, b), "normal", std)
            out[(lay, "mlp", w, "bias")] = ((b,), "normal", 0.01)
    return out


def _nest(flat: dict):
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf
    return tree


def param_shapes(model: dict):
    return _nest({p: jax.ShapeDtypeStruct(s, jnp.float32)
                  for p, (s, _, _) in _layout(model).items()})


def _make_leaf(key, index, shape, kind, std):
    x = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32) * std
    return 1.0 + x if kind == "scale" else x


def make_params(model: dict, seed: int, shardings=None):
    """The whole tree in one jitted call; ``shardings`` (a matching tree
    of shardings, or None for the default device) places the leaves."""
    layout = _layout(model)
    # jax.tree order over nested dicts is sorted by key at every level;
    # number the leaves in that order so that it never depends on how the
    # layout above happens to be written.
    order = {p: i for i, p in enumerate(sorted(layout))}

    def build(key):
        return _nest({p: _make_leaf(key, order[p], s, kind, std)
                      for p, (s, kind, std) in layout.items()})

    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


def change_norms(model: dict, seed: int, params) -> list:
    """Per leaf (``jax.tree`` order) the norm of ``params - make_params``:
    how far training has moved each leaf from what the seed gave. Every
    leaf is made again alone, so nothing the size of the model is held
    twice; one small program per distinct leaf shape."""
    layout = _layout(model)
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
                    (p.astype(jnp.float32) - _make_leaf(k, i, shape, kind, std)) ** 2)))
        out.append(progs[sig](by_path[path], key, jnp.int32(index)))
    return [float(x) for x in out]


def row_shardings(model: dict, devices):
    """For a cell on several chips: every leaf split over the chips along
    its first dimension that divides evenly, so that the weights are never
    whole on one chip (1.56 G float32 parameters with Adam's state do not
    fit one). One chip, or a leaf that does not divide, stays whole."""
    import numpy as np_
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    if len(devices) == 1:
        return None
    mesh = Mesh(np_.array(devices), ("chips",))
    n = len(devices)

    def leaf(shape_dtype):
        for dim, size in enumerate(shape_dtype.shape):
            if size % n == 0:
                return NamedSharding(mesh, P(*([None] * dim + ["chips"])))
        return NamedSharding(mesh, P())

    return jax.tree.map(leaf, param_shapes(model))
