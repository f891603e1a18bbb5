"""Achieved-HBM-bandwidth microbenchmark for the bench chip.

The conv-net ceiling analysis in docs/performance.md prices kernels
against the v5e *spec* HBM bandwidth (819 GB/s). This measures what a
simple streaming kernel actually achieves through this runtime, at several
tensor sizes, for three access patterns:

  copy    y = (x+1)·k          (read N, write N)
  add3    a' = (a+b+c)·k       (read 3N, write N)
  reduce  s = max(x, s·eps).sum (read N, write ~0 — the BN-stats shape)

Methodology (two failure modes drove it here, both measured on-device):

1. A scanned window of an *affine* body is algebraically collapsible —
   XLA folded 50 iterations of ``x+1`` / ``a+b+c`` into one pass and an
   early version "measured" 740 TB/s on an 819 GB/s part. Every body
   below therefore carries a runtime-data dependence (a scalar ``k``
   derived from the carry, or a ``max`` against it) that XLA can neither
   hoist nor fold; the scalar multiply fuses into the streaming kernel so
   it adds no traffic.
2. Chained separate dispatches avoid the folding but pay a per-dispatch
   host cost on every call, which at these kernel sizes is not small
   against the kernels themselves.

So each (pattern, size) runs as a device-side ``lax.scan`` window at two
lengths and reports the differenced per-iteration time
``(T(K2) - T(K1)) / (K2 - K1)``, which cancels the fixed dispatch cost
exactly. A chained-dispatch control row reports that per-dispatch cost
itself. Artifacts self-flag ``suspect`` when a row exceeds 1.2x the
device-keyed HBM spec (known device kinds only).

Usage::

    python examples/benchmark/membw.py            # sweep
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..")))

import jax
import jax.numpy as jnp
from jax import lax

from autodist_tpu.resource_spec import HBM_BY_ACCELERATOR, hbm_spec_for_kind

# At small sizes the per-window work falls under the host's dispatch
# jitter and the differenced time degenerates to noise.
SIZES_MB = tuple(int(s) for s in
                 os.environ.get("MEMBW_SIZES_MB", "256,512").split(","))
K1 = int(os.environ.get("MEMBW_K1", "10"))
K2 = int(os.environ.get("MEMBW_K2", "60"))
DTYPE = jnp.bfloat16


def _sync(x):
    """Wait for ``x``: dispatch is asynchronous, so a timing without this
    measures the enqueue. In-order execution means the last result syncs
    all queued work."""
    jax.block_until_ready(x)


def _time_window(body, carry, length, trials=3):
    """Median wall time of one scanned window of ``length`` iterations.

    Three trials so the median is a true middle sample — with two, picking
    index 1 is the max, i.e. systematically the jitter-contaminated run.
    """
    run = jax.jit(lambda c: lax.scan(lambda c, _: (body(c), None),
                                     c, None, length=length)[0])
    _sync(run(carry))                    # compile + warmup
    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        out = run(carry)
        _sync(out)
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def _time_scanned(body, carry):
    """Differenced per-iteration seconds: fixed dispatch cost cancels.

    A non-positive difference means jitter swamped the window delta; the
    clamped sentinel keeps downstream math finite and the caller marks the
    row invalid (it must never become a headline number).
    """
    t1 = _time_window(body, carry, K1)
    t2 = _time_window(body, carry, K2)
    return max((t2 - t1) / (K2 - K1), 1e-9), t1, t2


def _dispatch_overhead(repeats=20):
    """Per-dispatch cost of a chained tiny call (platform control row)."""
    f = jax.jit(lambda x: x + jnp.asarray(1, x.dtype))
    y = f(jnp.ones((8, 128), DTYPE))
    _sync(y)
    t0 = time.perf_counter()
    for _ in range(repeats):
        y = f(y)
    _sync(y)
    return (time.perf_counter() - t0) / repeats


def _row(name, dt, moved_bytes, extra=None):
    gbs = moved_bytes / dt / 1e9
    r = {"pattern": name, "moved_mb": round(moved_bytes / 1e6, 1),
         "us_per_iter": round(dt * 1e6, 1), "achieved_gb_s": round(gbs, 1)}
    if extra:
        r.update(extra)
    return r


def main() -> None:
    dev = jax.devices()[0]
    kind = str(getattr(dev, "device_kind", dev.platform))
    spec_gb_s = hbm_spec_for_kind(kind)[1]
    spec_known = any(k in kind.lower() for k in HBM_BY_ACCELERATOR)
    bpe = jnp.dtype(DTYPE).itemsize
    rows = []

    overhead_s = _dispatch_overhead()
    overhead_us = round(overhead_s * 1e6, 1)
    rows.append(_row("dispatch_overhead", overhead_s, 0))

    # Non-uniform data everywhere: an all-ones tensor is a SPLAT constant
    # and XLA's simplifier exploits it (reduce-of-identical-rows rewrites to
    # a multiply, adds of splats fold) — a CPU smoke run "measured" 4 PB/s
    # on the reduce row that way. Tensors also travel through the scan
    # CARRY (runtime values, not closure constants) so nothing is
    # compile-time known; unchanged carry legs cost no traffic.
    key = jax.random.PRNGKey(0)

    for mb in SIZES_MB:
        n = mb * 1_000_000 // bpe
        shape = (n // 128, 128)  # 128-lane minor dim, like real activations

        # copy: the scalar k = 1 + x[0,0]·1e-30 fuses into the add kernel
        # (one read, one write) but makes the chain non-foldable.
        x = jax.random.uniform(key, shape, DTYPE, 0.5, 1.5)
        dt, t1, t2 = _time_scanned(
            lambda c: (c + jnp.asarray(1, c.dtype))
            * (jnp.asarray(1, c.dtype) + c[0, 0] * jnp.asarray(1e-30, c.dtype)),
            x)
        rows.append(_row(f"copy_{mb}mb", dt, 2 * n * bpe,
                         {"t_k1_ms": round(t1 * 1e3, 2),
                          "t_k2_ms": round(t2 * 1e3, 2)}))

        # BN-stats shape: read N, write one [1,128] row. ``max`` against the
        # carry-scaled row is nonlinear in x, so sum() cannot be factored
        # out of the loop (a linear coupling like (x+s·eps).sum distributes
        # to a hoistable sum(x)). f32 end-to-end so moved_bytes is exact.
        n32 = mb * 1_000_000 // 4
        x32 = jax.random.uniform(key, (n32 // 128, 128), jnp.float32, 0.5, 1.5)
        s0 = jnp.zeros((1, 128), jnp.float32)

        def reduce_body(carry):
            xc, s = carry
            return xc, jnp.maximum(xc, s * 1e-30).sum(0, keepdims=True)

        dt, t1, t2 = _time_scanned(reduce_body, (x32, s0))
        rows.append(_row(f"reduce_{mb}mb", dt, n32 * 4,
                         {"t_k1_ms": round(t1 * 1e3, 2),
                          "t_k2_ms": round(t2 * 1e3, 2)}))

        def add3(carry):
            a, b, c = carry
            y = a + b + c
            return (y * (jnp.asarray(1, y.dtype)
                         + y[0, 0] * jnp.asarray(1e-30, y.dtype)), b, c)

        dt, t1, t2 = _time_scanned(
            add3, (jax.random.uniform(key, shape, DTYPE, 0.5, 1.5),
                   jax.random.uniform(key, shape, DTYPE, -0.5, 0.5),
                   jax.random.uniform(key, shape, DTYPE, -0.5, 0.5)))
        rows.append(_row(f"add3_{mb}mb", dt, 4 * n * bpe,
                         {"t_k1_ms": round(t1 * 1e3, 2),
                          "t_k2_ms": round(t2 * 1e3, 2)}))
        del x, x32

    # Per-row validity: a differenced time can degenerate under host
    # jitter (t_k2 barely above t_k1 → absurd rate). Such rows are kept in
    # the artifact for audit but excluded from the headline; the artifact
    # is suspect only when NO physical row survives.
    bw_rows = [r for r in rows if r["pattern"] != "dispatch_overhead"]
    for r in bw_rows:
        degenerate = r["us_per_iter"] <= 0.5  # clamped / sub-jitter diff
        r["valid"] = (not degenerate
                      and ((not spec_known)
                           or r["achieved_gb_s"] <= 1.2 * spec_gb_s))
    for r in rows:
        flag = "" if r.get("valid", True) else "  [INVALID: jitter artifact]"
        print(f"{r['pattern']:>18s}: {r['achieved_gb_s']:8.1f} GB/s "
              f"({r['us_per_iter']:.0f} us/iter, {r['moved_mb']:.0f} MB moved)"
              f"{flag}")
    valid_rows = [r for r in bw_rows if r["valid"]]
    best = max((r["achieved_gb_s"] for r in valid_rows), default=0.0)
    suspect = spec_known and not valid_rows
    print(f"\nbest achieved: {best:.0f} GB/s "
          f"({kind} HBM spec {spec_gb_s:.0f} GB/s -> {best / spec_gb_s:.0%} of spec)"
          + ("  [SUSPECT: no physical row, artifact flagged]" if suspect else ""))
    # Only a real-TPU run may refresh the canonical artifact the roofline
    # verdict consumes; CPU smoke runs land beside it, suffixed.
    fname = ("membw.json" if "TPU" in kind
             else f"membw_{dev.platform}.json")
    out = os.path.join(os.path.dirname(__file__), "..", "..", "docs",
                       "measured", fname)
    with open(os.path.abspath(out), "w") as fh:
        json.dump({"device": kind,
                   "dtype": "bfloat16",
                   "methodology": "scanned-window-differenced",
                   "window_lengths": [K1, K2],
                   "dispatch_overhead_us": overhead_us,
                   "spec_gb_s": spec_gb_s if spec_known else None,
                   "rows": rows, "best_gb_s": best,
                   "suspect": suspect}, fh, indent=2)
    print(f"wrote {os.path.abspath(out)}")


if __name__ == "__main__":
    main()
