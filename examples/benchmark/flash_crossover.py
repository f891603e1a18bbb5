"""Flash-vs-dot attention crossover sweep (single bench chip).

Measures windowed train-step throughput of the same transformer under
``attention_impl='dot'`` (XLA-fused dot-product attention) and ``'flash'``
(the pallas kernel, :mod:`autodist_tpu.ops.flash_attention`) across sequence
lengths, to locate the crossover where streaming K/V through VMEM beats
materializing the [S, S] logits in HBM. Each (seq, impl) point runs in a
FRESH subprocess, one after the other — this parent never imports jax, so
the chip belongs to one point at a time and no in-process state leaks
between points. Results land in ``docs/measured/flash_crossover.json`` and
the table in docs/performance.md.

Usage::

    python examples/benchmark/flash_crossover.py            # full sweep
    python examples/benchmark/flash_crossover.py --point 2048 flash  # one cell
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..")))

SEQS = (512, 1024, 2048, 4096)
IMPLS = ("dot", "flash")
BATCH = 8
WINDOW = 10
# Small-but-real model: attention is the piece under test, so keep the
# MLP/vocab share modest (4 layers, d512) the way the r2 sweep did.
MODEL_KW = dict(vocab_size=8192, num_layers=4, d_model=512, num_heads=8,
                d_ff=2048, causal=True)


def measure_point(seq: int, impl: str) -> dict:
    import jax

    from autodist_tpu.api import AutoDist
    from autodist_tpu.models import get_model
    import autodist_tpu.strategy as S

    spec = get_model("transformer", max_seq_len=seq, attention_impl=impl,
                     **MODEL_KW)
    params = spec.init(jax.random.PRNGKey(0))
    AutoDist.reset_default()
    ad = AutoDist(strategy_builder=S.AllReduce())
    batch = spec.example_batch(BATCH)
    step = ad.build(spec.loss_fn, params, batch)
    state = step.init(params)
    batch = jax.device_put(batch, step.plan.batch_shardings(batch))
    jax.block_until_ready(batch)
    state, m = step.run(state, batch, WINDOW)   # warmup + compile
    float(m["loss"][-1])
    trials = []
    # 4 windows back-to-back per trial, one trailing fetch: pipelined on
    # the device, so it never idles on a host round trip between windows.
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(4):
            state, m = step.run(state, batch, WINDOW)
        float(m["loss"][-1])  # host fetch of the last loss = barrier
        trials.append((time.perf_counter() - t0) / 4)
    dt = sorted(trials)[len(trials) // 2]
    tok_s = BATCH * seq * WINDOW / dt
    return {
        "seq": seq, "impl": impl, "tokens_per_sec": round(tok_s, 1),
        "ms_per_step": round(dt / WINDOW * 1e3, 2),
        "device": getattr(jax.devices()[0], "device_kind",
                          jax.devices()[0].platform),
    }


def main() -> None:
    if len(sys.argv) >= 4 and sys.argv[1] == "--point":
        print(json.dumps(measure_point(int(sys.argv[2]), sys.argv[3])))
        return

    rows = []
    failed = []
    for seq in SEQS:
        for impl in IMPLS:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--point",
                 str(seq), impl],
                capture_output=True, text=True, timeout=900,
            )
            line = r.stdout.strip().splitlines()[-1] if r.stdout.strip() else ""
            if r.returncode != 0 or not line.startswith("{"):
                print(f"point seq={seq} impl={impl} FAILED:\n{r.stderr[-1500:]}",
                      file=sys.stderr)
                failed.append({"seq": seq, "impl": impl})
                continue
            row = json.loads(line)
            rows.append(row)
            print(f"seq {seq:5d}  {impl:5s}: {row['tokens_per_sec']:>10.0f} tok/s  "
                  f"{row['ms_per_step']:.2f} ms/step")

    by_seq = {}
    for row in rows:
        by_seq.setdefault(row["seq"], {})[row["impl"]] = row
    print("\nseq    dot tok/s   flash tok/s   flash/dot")
    for seq in SEQS:
        d, f = by_seq.get(seq, {}).get("dot"), by_seq.get(seq, {}).get("flash")
        if d and f:
            print(f"{seq:5d} {d['tokens_per_sec']:>10.0f} {f['tokens_per_sec']:>13.0f}"
                  f"   {f['tokens_per_sec'] / d['tokens_per_sec']:>8.2f}x")

    out = os.path.abspath(os.path.join(
        os.path.dirname(__file__), "..", "..", "docs", "measured",
        "flash_crossover.json"))
    if failed:
        # Don't clobber a complete committed artifact with a partial
        # sweep: park partial results beside it, failures recorded.
        out += ".partial"
        print(f"\n{len(failed)} point(s) failed — writing partial sweep to "
              f"side path instead of the committed artifact", file=sys.stderr)
    with open(out, "w") as fh:
        json.dump({"model": MODEL_KW, "batch": BATCH, "window": WINDOW,
                   "rows": rows, "failed_points": failed}, fh, indent=2)
    print(f"\nwrote {out}")


if __name__ == "__main__":
    main()
