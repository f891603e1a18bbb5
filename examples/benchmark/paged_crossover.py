"""Paged-attention kernel-vs-gather crossover sweep (single bench chip).

Measures decode-step throughput of :func:`autodist_tpu.ops.
paged_attention.paged_decode_attention` under ``impl='gather'`` (the XLA
page-table gather that materializes the row timeline) and ``'kernel'``
(the pallas block loop streaming pages through VMEM with online softmax)
across decode-shaped (batch, table width) points, to locate the timeline
width where streaming beats gathering. Each (shape, impl) point runs in a
FRESH subprocess, one after the other, from a parent that never imports
jax — one process on the chip at a time, the same discipline as
``flash_crossover.py``.

Results land in ``docs/measured/paged_crossover.json``;
``ops.crossover.paged_crossover_timeline`` reads them to resolve
``paged_attention_impl='auto'`` per (batch, table width, heads) shape at
trace time. On CPU the kernel runs in pallas interpret mode (~100x slower
than the XLA gather — a correctness vehicle, not a perf proxy), so CPU
rows are stamped ``"device": "cpu"`` and "auto" stays "gather" off-TPU
regardless; the committed table holds CPU rows only — the device sweep is
ROADMAP S1.

Usage::

    python examples/benchmark/paged_crossover.py              # full sweep
    python examples/benchmark/paged_crossover.py --point 8 64 gather
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")))

# Decode-shaped points: PAGE_LEN x TABLE_PAGES spans short chats through
# near-ceiling timelines; batches span light and saturated decode.
BATCHES = (8, 32)
TABLE_PAGES = (8, 32, 128)
PAGE_LEN = 16
HEADS = 8
HEAD_DIM = 64
WINDOW = 50
IMPLS = ("gather", "kernel")


def measure_point(batch: int, table_pages: int, impl: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(0)
    n_pages = batch * table_pages + 1
    kp = jnp.asarray(rng.standard_normal(
        (n_pages, PAGE_LEN, HEADS * HEAD_DIM)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal(
        (n_pages, PAGE_LEN, HEADS * HEAD_DIM)), jnp.float32)
    tables = jnp.asarray(
        1 + rng.permutation(batch * table_pages).reshape(batch, table_pages),
        jnp.int32)
    q = jnp.asarray(rng.standard_normal((batch, HEADS, HEAD_DIM)),
                    jnp.float32)
    # Rows near the timeline ceiling: the whole table is live, the
    # worst-case (and steady-state) decode shape the crossover prices.
    positions = jnp.asarray(
        rng.integers(table_pages * PAGE_LEN // 2,
                     table_pages * PAGE_LEN, size=batch), jnp.int32)

    fn = jax.jit(lambda *a: pa.paged_decode_attention(*a, impl=impl))
    out = fn(q, kp, vp, tables, positions)
    jax.block_until_ready(out)                       # warmup + compile
    # Off-TPU the kernel runs interpreted (a per-grid-step Python loop):
    # shrink the window AND the trial count so the CPU-proxy sweep stays
    # minutes, not hours — the wide points run thousands of interpreted
    # grid steps per call.
    on_tpu = jax.default_backend() == "tpu"
    window = WINDOW if on_tpu else 1
    n_trials = 3 if on_tpu else 1
    trials = []
    for _ in range(n_trials):
        t0 = time.perf_counter()
        for _ in range(window):
            out = fn(q, kp, vp, tables, positions)
        jax.block_until_ready(out)
        trials.append((time.perf_counter() - t0) / window)
    dt = sorted(trials)[len(trials) // 2]
    return {
        "batch": batch, "table_pages": table_pages, "page_len": PAGE_LEN,
        "heads": HEADS, "head_dim": HEAD_DIM, "impl": impl,
        "tokens_per_sec": round(batch / dt, 1),
        "us_per_step": round(dt * 1e6, 2),
        "device": getattr(jax.devices()[0], "device_kind",
                          jax.devices()[0].platform),
    }


def main() -> None:
    if len(sys.argv) >= 5 and sys.argv[1] == "--point":
        print(json.dumps(measure_point(
            int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])))
        return

    rows = []
    failed = []
    # Off-TPU the widest kernel point runs ~8k interpreted grid steps;
    # give it headroom (the TPU sweep finishes each point in seconds).
    point_timeout = 900 if os.environ.get(
        "JAX_PLATFORMS", "") not in ("cpu",) else 2700
    for batch in BATCHES:
        for table_pages in TABLE_PAGES:
            for impl in IMPLS:
                try:
                    r = subprocess.run(
                        [sys.executable, os.path.abspath(__file__),
                         "--point", str(batch), str(table_pages), impl],
                        capture_output=True, text=True,
                        timeout=point_timeout,
                    )
                except subprocess.TimeoutExpired:
                    print(f"point batch={batch} pages={table_pages} "
                          f"impl={impl} TIMED OUT ({point_timeout}s)",
                          file=sys.stderr)
                    failed.append({"batch": batch,
                                   "table_pages": table_pages,
                                   "impl": impl})
                    continue
                line = (r.stdout.strip().splitlines()[-1]
                        if r.stdout.strip() else "")
                if r.returncode != 0 or not line.startswith("{"):
                    print(f"point batch={batch} pages={table_pages} "
                          f"impl={impl} FAILED:\n{r.stderr[-1500:]}",
                          file=sys.stderr)
                    failed.append({"batch": batch,
                                   "table_pages": table_pages,
                                   "impl": impl})
                    continue
                row = json.loads(line)
                rows.append(row)
                print(f"batch {batch:3d}  timeline "
                      f"{table_pages * PAGE_LEN:5d}  {impl:6s}: "
                      f"{row['tokens_per_sec']:>10.0f} tok/s  "
                      f"{row['us_per_step']:.0f} us/step")

    by_shape: dict = {}
    for row in rows:
        by_shape.setdefault(
            (row["batch"], row["table_pages"]), {})[row["impl"]] = row
    print("\nbatch timeline  gather tok/s  kernel tok/s  kernel/gather")
    for (batch, tp), v in sorted(by_shape.items()):
        g, k = v.get("gather"), v.get("kernel")
        if g and k:
            print(f"{batch:5d} {tp * PAGE_LEN:8d} "
                  f"{g['tokens_per_sec']:>13.0f} "
                  f"{k['tokens_per_sec']:>13.0f} "
                  f"{k['tokens_per_sec'] / g['tokens_per_sec']:>13.2f}x")

    out = os.path.abspath(os.path.join(
        os.path.dirname(__file__), "..", "..", "docs", "measured",
        "paged_crossover.json"))
    if failed:
        # Don't clobber a healthy committed artifact with a degraded-
        # session sweep: park partial results beside it.
        out += ".partial"
        print(f"\n{len(failed)} point(s) failed — writing partial sweep "
              f"to side path instead of the committed artifact",
              file=sys.stderr)
    with open(out, "w") as fh:
        json.dump({"page_len": PAGE_LEN, "heads": HEADS,
                   "head_dim": HEAD_DIM, "window": WINDOW,
                   "rows": rows, "failed_points": failed}, fh, indent=2)
    print(f"\nwrote {out}")


if __name__ == "__main__":
    main()
