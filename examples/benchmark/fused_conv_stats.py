"""Pallas vs XLA on the ResNet hot-kernel shape: 1x1 conv + BN statistics.

The r3 op profile shows ResNet-50's step dominated by XLA `reduce_fusion`
kernels that compute a conv and the BN batch statistics of its output in
one kernel — running ~5-6x slower than their HBM traffic at spec bandwidth
would cost. This micro-benchmark isolates that exact computation at
bottleneck-block shapes (a 1x1 conv is a [M,K]@[K,N] matmul over
M = B*H*W pixels) and races three renderings:

  xla     — jnp matmul + fp32 moments, one jit (XLA fuses stats into the
            matmul epilogue the way the full model shows)
  pallas  — a hand-tiled kernel: bf16 MXU matmul accumulating fp32,
            per-column sum / sum-of-squares accumulated in VMEM across the
            M-block grid, stats written on the last grid step
  matmul  — the matmul alone (no stats): the kernel-efficiency floor

If pallas lands near `matmul` while `xla` does not, the gap seen in the
model is Mosaic fusion scheduling (attackable with custom kernels); if all
three cluster, the shape itself is the ceiling on this chip.

On CPU the pallas path runs in interpret mode (correctness only —
`tests/test_ops.py::test_fused_matmul_stats_*` pins it); timings are only
meaningful on the TPU chip.

Usage::

    python examples/benchmark/fused_conv_stats.py            # full table
    python examples/benchmark/fused_conv_stats.py 401408 64 256   # one shape
"""
from __future__ import annotations

import functools
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..")))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bottleneck-block 1x1 convs at b128/224px: [M = B*56*56, K, N].
SHAPES = (
    (128 * 56 * 56, 64, 256),    # conv3 expand, stage 1
    (128 * 56 * 56, 256, 64),    # conv1 reduce, stage 1
    (128 * 28 * 28, 512, 128),   # conv1 reduce, stage 2
    (128 * 28 * 28, 128, 512),   # conv3 expand, stage 2
)
BLOCK_M = 1024


def _kernel(x_ref, w_ref, y_ref, s1_ref, s2_ref, acc1_ref, acc2_ref):
    """One M-block program: y = x @ w (bf16 in, fp32 accumulate), stats
    accumulated in fp32 VMEM scratch across the sequential M grid."""
    y32 = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                   # [bm, N] fp32
    y_ref[...] = y32.astype(y_ref.dtype)
    mi = pl.program_id(0)

    @pl.when(mi == 0)
    def _init():
        acc1_ref[...] = jnp.zeros_like(acc1_ref)
        acc2_ref[...] = jnp.zeros_like(acc2_ref)

    acc1_ref[...] += y32.sum(axis=0, keepdims=True)
    acc2_ref[...] += (y32 * y32).sum(axis=0, keepdims=True)

    @pl.when(mi == pl.num_programs(0) - 1)
    def _fin():
        s1_ref[...] = acc1_ref[...]
        s2_ref[...] = acc2_ref[...]


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def fused_matmul_stats(x, w, block_m: int = BLOCK_M, interpret: bool = False):
    """(y bf16 [M,N], sum fp32 [N], sumsq fp32 [N]) in one pallas kernel."""
    m, k = x.shape
    _, n = w.shape
    assert m % block_m == 0, (m, block_m)
    y, s1, s2 = pl.pallas_call(
        _kernel,
        grid=(m // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, n), jnp.float32),
            pltpu.VMEM((1, n), jnp.float32),
        ],
        interpret=interpret,
    )(x, w)
    return y, s1[0], s2[0]


def xla_matmul_stats(x, w):
    y32 = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    y = y32.astype(x.dtype)
    return y, y32.sum(0), (y32 * y32).sum(0)


def _sync(out):
    """Wait for ``out``: dispatch is asynchronous, and execution is
    in-order per device, so the LAST result syncs every queued call."""
    jax.block_until_ready(out)


def _time(fn, *args, repeats=30):
    out = fn(*args)
    _sync(out)
    trials = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fn(*args)
        _sync(out)
        trials.append((time.perf_counter() - t0) / repeats)
    return sorted(trials)[1]


def _time_scanned(fn, x, w, repeats=30):
    """Per-iter time with ALL repeats inside one dispatch (lax.scan).

    The per-dispatch loop above pays a host dispatch on every call, which
    at these sizes can dwarf the kernel itself. Here the body perturbs x by a y-derived scalar each
    iteration — a data dependence XLA cannot hoist or fold (the scalar is
    runtime data), so every iteration re-runs the matmul on a fresh tensor.
    The extra x-scaling pass is priced into the printed floor by the caller.
    """
    def body(carry, _):
        xc = carry
        out = fn(xc, w)
        y = jax.tree.leaves(out)[0]
        return xc * (1.0 + y[0, 0].astype(xc.dtype) * 1e-30), None

    run = jax.jit(lambda x0: jax.lax.scan(body, x0, None, length=repeats)[0])
    out = run(x)
    _sync((out,))
    trials = []
    for _ in range(3):
        t0 = time.perf_counter()
        out = run(x)
        _sync((out,))
        trials.append((time.perf_counter() - t0) / repeats)
    return sorted(trials)[1]


def main() -> None:
    shapes = SHAPES
    if len(sys.argv) == 4:
        shapes = ((int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])),)
    on_tpu = jax.devices()[0].platform != "cpu"
    print(f"device: {jax.devices()[0].device_kind if on_tpu else 'cpu'}")
    for m, k, n in shapes:
        x = jax.random.normal(jax.random.PRNGKey(0), (m, k)).astype(jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n)).astype(jnp.bfloat16)
        xla_j = jax.jit(xla_matmul_stats)
        mm_j = jax.jit(lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(a.dtype))
        t_xla = _time(xla_j, x, w)
        t_mm = _time(mm_j, x, w)
        t_pl = _time(functools.partial(
            fused_matmul_stats, interpret=not on_tpu), x, w)
        t_scan = _time_scanned(xla_j, x, w)
        traffic = (m * k + k * n + m * n) * 2          # bf16 bytes
        floor = traffic / 819e9
        # The scanned body additionally reads+writes x once per iteration.
        floor_scan = (3 * m * k + k * n + m * n) * 2 / 819e9
        print(f"[{m:>7d},{k:>3d}]@[{k:>3d},{n:>3d}]  "
              f"xla {t_xla * 1e6:7.1f}us  pallas {t_pl * 1e6:7.1f}us  "
              f"matmul-only {t_mm * 1e6:7.1f}us  "
              f"scanned {t_scan * 1e6:7.1f}us  "
              f"(bw floor {floor * 1e6:5.1f}us / scanned {floor_scan * 1e6:5.1f}us)")


if __name__ == "__main__":
    main()
