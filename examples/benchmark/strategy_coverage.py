"""Measure the BASELINE.json strategy-coverage configs on the bench chip.

BASELINE.json's ``configs`` list names the strategy×model pairs the rebuild
must train end-to-end (the reference's published benchmark matrix slots):

    ResNet-50  × AllReduce      (ICI mesh)
    BERT-base  × PartitionedPS  (variable sharding)
    LM1B LSTM  × Parallax       (sparse embeddings, hybrid PS+AR)
    VGG-16     × PartitionedAR  (dense-heavy partial reduce)
    NCF        × PSLoadBalancing (embedding-table bin packing)

This driver runs each through ``train.py --pin`` (steady-state device rate,
one fresh subprocess per pair so a failure or hang cannot poison the next)
and records one artifact: ``docs/measured/strategy_coverage.json``. The
point is coverage evidence — every pair trains AND its measured rate is on
record — not a horse race; single-chip strategy spread is small by design
(see the calibration notes in docs/performance.md).

Usage::

    python examples/benchmark/strategy_coverage.py [--steps 63] [--window 20]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..")))

PAIRS = (
    # (train.py --model, --strategy, batch)
    ("resnet50", "AllReduce", 128),
    ("bert_base", "PartitionedPS", 64),
    ("lm1b", "Parallax", 256),
    ("vgg16", "PartitionedAR", 128),
    ("ncf", "PSLoadBalancing", 4096),
)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=63)
    ap.add_argument("--window", type=int, default=20)
    args = ap.parse_args()

    train = os.path.join(os.path.dirname(os.path.abspath(__file__)), "train.py")
    rows, failures = [], []
    for model, strategy, batch in PAIRS:
        cmd = [sys.executable, train, "--model", model, "--strategy", strategy,
               "--batch-size", str(batch), "--steps", str(args.steps),
               "--window", str(args.window), "--pin"]
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=1800)
        line = r.stdout.strip().splitlines()[-1] if r.stdout.strip() else ""
        if r.returncode != 0 or not line.startswith("{"):
            failures.append({"model": model, "strategy": strategy,
                             "stderr": (r.stderr or "")[-800:]})
            print(f"{model:>10s} x {strategy:<16s}: FAILED", flush=True)
            continue
        row = json.loads(line)
        rows.append(row)
        print(f"{model:>10s} x {strategy:<16s}: {row['value']:>10.1f} {row['unit']}"
              f"  ({row['mean_step_s'] * 1e3:.1f} ms/step)", flush=True)

    out = os.path.join(os.path.dirname(__file__), "..", "..", "docs",
                       "measured", "strategy_coverage.json")
    with open(os.path.abspath(out), "w") as fh:
        json.dump({"steps": args.steps, "window": args.window,
                   "rows": rows, "failures": failures}, fh, indent=2)
    print(f"\nwrote {os.path.abspath(out)} "
          f"({len(rows)} measured, {len(failures)} failed)")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
