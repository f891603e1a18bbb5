"""The decode step's state update of a Mamba-2 layer (Dao and Gu, arXiv
2405.21060), over every row of the decode batch at once.

A row's state is ``S [H, P, N]`` (heads, head dim, state size), float32.
For head ``h`` in group ``g = h // (H / G)``, with ``dt`` already through
softplus and ``A = -exp(A_log)``::

    S <- exp(dt_h A_h) S + dt_h x_h (x) B_g
    y_h = S C_g + D_h x_h

The state is the serving engine's per-slot state, one leaf a layer ``[R, H,
P, N]``. :func:`ssm_state_update` updates it **in place**: the Mosaic kernel
aliases its output onto the state operand, so a program that donates the
state holds one copy of it. A row that is not live (an idle or prefilling
row of the decode batch) is skipped by a scalar-prefetched mask: its state
is neither read nor written, and its ``y`` is zero. The grid walks the rows
in order, one a step; a skipped row's blocks are those of the last live row
before it (the first live row, before any), which the pipeline holds
already, so no copy is issued for it and nothing is written back but what
a live row computed.

Memory-bound: each live row's state is read and written once (2 x 2.1 MB
at the published widths of Nemotron-H, 64 x 64 x 128 float32), against
about 5 operations an element. ``impl="reference"`` is the same update in
plain ``jnp``, for the CPU and the tests.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _by_head(v, heads: int):
    """``[R, G, N]`` -> ``[R, H, N]``: head ``h`` takes group ``h // (H /
    G)``."""
    return jnp.repeat(v, heads // v.shape[1], axis=1)


def ssm_state_update_reference(state, x, dt, a, d, b, c, live):
    """The update in plain ``jnp``; shapes as :func:`ssm_state_update`."""
    heads = state.shape[1]
    da = jnp.exp(dt * a[None, :])[..., None, None]                 # [R, H, 1, 1]
    upd = (dt[..., None] * x)[..., None] * _by_head(b, heads)[:, :, None, :]
    new = da * state + upd                                         # [R, H, P, N]
    y = jnp.sum(new * _by_head(c, heads)[:, :, None, :], axis=-1) + d[None, :, None] * x
    keep = live[:, None, None]
    return (jnp.where(keep, y, 0.0),
            jnp.where(keep[..., None], new, state))


def _kernel(src_ref, live_ref, n_live_ref, x_ref, dt_ref, a_ref, d_ref, b_ref,
            c_ref, s_ref, y_ref, so_ref):
    """One row a grid step, its heads one after another. The row's ``x``
    and ``y`` lie as ``[P, H]`` (a head a lane), so that a head's ``x`` is
    a column to spread over the state's lanes and its ``y`` a column
    written back in place: no array of the call has a minor dimension that
    the chip's tiles would pad a hundredfold."""
    r = pl.program_id(0)
    heads, groups = s_ref.shape[1], b_ref.shape[1]
    per_group = heads // groups

    @pl.when(live_ref[r] == 1)
    def _update():
        for h in range(heads):
            g = h // per_group
            dt = dt_ref[0, :, h:h + 1]                             # [1, 1]
            x = x_ref[0, :, h:h + 1]                               # [P, 1]
            s = (jnp.exp(dt * a_ref[:, h:h + 1]) * s_ref[0, h]
                 + (dt * x) * b_ref[0, g:g + 1, :])                # [P, N]
            so_ref[0, h] = s
            y_ref[0, :, h:h + 1] = (
                jnp.sum(s * c_ref[0, g:g + 1, :], axis=-1, keepdims=True)
                + d_ref[:, h:h + 1] * x)

    # no row live: every step maps to row 0, which goes back as it came
    @pl.when((n_live_ref[0] == 0) & (r == 0))
    def _keep():
        so_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kernel_call(state, x, dt, a, d, b, c, live, *, interpret: bool):
    rows, heads, p, n = state.shape
    groups = b.shape[1]
    live_i = live.astype(jnp.int32)
    idx = jnp.arange(rows, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(live, idx, -1))
    src = jnp.where(last >= 0, last, jnp.argmax(live).astype(jnp.int32))
    n_live = live_i.sum(keepdims=True)

    def row(shape):
        return pl.BlockSpec((1,) + shape, lambda r, src, *_: (src[r],) + (0,) * len(shape))

    def whole(shape):
        return pl.BlockSpec(shape, lambda r, *_: (0,) * len(shape))

    f32 = jnp.float32
    operands = [x.astype(f32).transpose(0, 2, 1), dt.astype(f32)[:, None, :],
                a.astype(f32)[None], d.astype(f32)[None],
                b.astype(f32), c.astype(f32), state]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(rows,),
        in_specs=[row((p, heads)), row((1, heads)), whole((1, heads)),
                  whole((1, heads)), row((groups, n)), row((groups, n)),
                  row((heads, p, n))],
        out_specs=[row((p, heads)), row((heads, p, n))])
    y, new = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, p, heads), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 9 (after the three prefetched scalars) is the state
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssm_state_update",
    )(src, live_i, n_live, *operands)
    # a skipped row's y block was never written
    return jnp.where(live[:, None, None], y.transpose(0, 2, 1), 0.0), new


def ssm_state_update(state, x, dt, a, d, b, c, live, *, impl: str = "auto",
                     interpret: Optional[bool] = None):
    """``state [R, H, P, N]`` float32, ``x [R, H, P]``, ``dt [R, H]`` (after
    softplus), ``a [H]`` (``-exp(A_log)``), ``d [H]``, ``b, c [R, G, N]``,
    ``live [R]`` bool -> ``(y [R, H, P] float32, state)``: live rows
    updated, the rest as they were and ``y`` zero there. ``impl``:
    ``kernel`` (Mosaic; interpreted off a TPU), ``reference``, or ``auto``,
    the kernel on a TPU and the reference off it."""
    if impl == "auto":
        impl = "reference" if _should_interpret() else "kernel"
    if impl == "reference":
        return ssm_state_update_reference(state, x.astype(jnp.float32),
                                          dt.astype(jnp.float32), a, d,
                                          b.astype(jnp.float32),
                                          c.astype(jnp.float32), live)
    if impl != "kernel":
        raise ValueError(f"unknown ssm_state_update impl {impl!r} "
                         "(auto|kernel|reference)")
    if interpret is None:
        interpret = _should_interpret()
    return _kernel_call(state, x, dt, a, d, b, c, live, interpret=interpret)
