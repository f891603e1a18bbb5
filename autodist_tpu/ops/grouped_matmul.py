"""Grouped matrix product for token-choice experts, on the Mosaic grouped
matmul that ships with jax (``jax.experimental.pallas.ops.tpu.megablox``,
whose call a profile shows as ``gmm``).

The rows are (token, expert) pairs sorted by expert, the pairs that fall on
the experts held first (:func:`group_rows`); ``gmm`` multiplies each
expert's run of rows by that expert's weights and visits only tiles that
hold rows of a group, so a step that hits 5 of 12 held experts streams 5
experts' weights and an expert nobody chose costs nothing. A tile that
straddles two experts is visited once for each, under a row mask. Nothing
is dropped and there is no capacity: the static row count is the most
pairs the call could hold. Rows past the last pair belong to no group:
``gmm`` leaves them as they were allocated, and :func:`combine_rows` reads
only the rows of pairs.

On the chip the repo's own kernel (each expert's run padded to whole
tiles, no mask) and ``gmm`` read within 5% of one another at the serving
shapes, and ``jax.lax.ragged_dot`` 37-85% slower (PERF.md section 6, PR
36), so the one that is not the repo's to keep is used.
:func:`grouped_matmul_reference` is the same product in plain ``jnp`` (a
loop over experts with a mask), for the CPU tests and for hosts without a
TPU.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm as _gmm


class RowGroups(NamedTuple):
    """(token, expert) pairs laid out for the product, by :func:`group_rows`."""

    row_token: jax.Array      # [M] the row's token; ``n_tokens`` past the pairs
    pair_row: jax.Array       # [T, K] the pair's row; ``M`` where not held
    sizes: jax.Array          # [count] rows of each held expert, in order
    n_pairs: jax.Array        # [] pairs that fell on held experts
    n_hit: jax.Array          # [] held experts with at least one pair
    tile_m: int


def group_rows(expert_ids, first: int, count: int,
               tile_m: Optional[int] = None) -> RowGroups:
    """Sort the pairs that fall on experts ``[first, first + count)`` by
    expert, ahead of every other pair.

    ``expert_ids [T, K]``: each token's chosen experts, over all experts
    (an id outside them all, as -1, is no pair). The row count is static:
    ``T * min(K, count)`` pairs at most (a token chooses distinct experts),
    in whole tiles of ``tile_m`` rows (128, or the whole where that is
    less)."""
    t, k = expert_ids.shape
    n_max = t * min(k, count)
    tile_m = tile_m or min(128, -(-n_max // 8) * 8)
    m = -(-n_max // tile_m) * tile_m
    local = expert_ids.reshape(-1) - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    n_pairs = sizes.sum().astype(jnp.int32)
    rank = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    pair_row = jnp.where(rank < n_pairs, rank, m).reshape(t, k)
    sorted_token = jnp.pad((order // k).astype(jnp.int32),
                           (0, max(0, m - t * k)))[:m]
    row_token = jnp.where(jnp.arange(m) < n_pairs, sorted_token, t)
    return RowGroups(row_token, pair_row, sizes, n_pairs,
                     (sizes > 0).sum().astype(jnp.int32), tile_m)


def gather_rows(x, groups: RowGroups):
    """``x [T, D]`` -> ``[M, D]``: each row its token's, a row past the
    pairs zero."""
    zero = jnp.zeros((1, x.shape[1]), x.dtype)
    return jnp.concatenate([x, zero], axis=0)[groups.row_token]


def combine_rows(y, groups: RowGroups, weights):
    """``y [M, D]`` -> float32 ``[T, D]``: each token's pairs' rows times
    their routing ``weights [T, K]``, added up. A gather a pair, not a
    scatter a row: a pair that fell on no held expert reads a zero row, and
    what the product left in the rows past the pairs is never read."""
    zero = jnp.zeros((1, y.shape[1]), y.dtype)
    picked = jnp.concatenate([y, zero], axis=0)[groups.pair_row]   # [T, K, D]
    return jnp.einsum("tkd,tk->td", picked.astype(jnp.float32),
                      weights.astype(jnp.float32))


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _divisor_tile(size: int, most: int) -> int:
    """The whole dimension where it is small, else its largest divisor
    that is a multiple of 128 and at most ``most``."""
    if size <= most:
        return size
    for tile in range(most - most % 128, 0, -128):
        if size % tile == 0:
            return tile
    return size


def grouped_matmul(x_rows, w, groups: RowGroups, *, impl: str = "kernel",
                   interpret: Optional[bool] = None):
    """``x_rows [M, K]`` (rows as :func:`group_rows` laid them) times ``w
    [E, K, N]``, each expert's run by its own weights: ``[M, N]`` in the
    rows' type. Rows past the pairs are left as they were allocated (not
    zeroed, not computed): :func:`combine_rows` never reads them."""
    if impl == "reference":
        return grouped_matmul_reference(x_rows, w, groups)
    if interpret is None:
        interpret = _should_interpret()
    # weight blocks of up to [2048, 512] (2 MB in bfloat16): eight blockings
    # from [512, 2048] to [7168, 256] read within 5% of one another on the
    # chip (PERF.md section 6, PR 36): the weights' stream bounds the call
    tiling = (groups.tile_m, _divisor_tile(w.shape[1], 2048),
              _divisor_tile(w.shape[2], 512))
    return _gmm(x_rows, w.astype(x_rows.dtype), groups.sizes,
                preferred_element_type=x_rows.dtype, tiling=tiling,
                interpret=interpret)


def grouped_matmul_reference(x_rows, w, groups: RowGroups):
    """The same product in plain ``jnp``: a loop over the experts, each
    taking the rows of its run by a mask."""
    ends = jnp.cumsum(groups.sizes)
    row = jnp.arange(x_rows.shape[0])
    out = jnp.zeros((x_rows.shape[0], w.shape[2]), jnp.float32)
    for e in range(w.shape[0]):
        y = jnp.matmul(x_rows, w[e].astype(x_rows.dtype),
                       preferred_element_type=jnp.float32)
        mine = (row >= ends[e] - groups.sizes[e]) & (row < ends[e])
        out = jnp.where(mine[:, None], y, out)
    return out.astype(x_rows.dtype)
