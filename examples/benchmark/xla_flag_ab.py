"""A/B the step time across XLA/libtpu compiler-flag settings.

Compiler flags must exist in the environment before backend init, so each
configuration runs ``resnet_bounds.py base`` in a FRESH subprocess with
``XLA_FLAGS`` / ``LIBTPU_INIT_ARGS`` composed from the table below. The
base config is measured first and last (drift guard: if the two base runs
disagree by >5% the session is unstable and the A/B is void).

The ``lhs_*`` / ``async_*`` / ``overlap_all`` rows exist for the bucketed
backward-overlap gradient sync (``GraphConfig.bucket_bytes``,
``kernel/bucketing.py``): per-bucket collectives emitted inside the
backward only hide the wire if the latency-hiding scheduler and async
collective fusion actually schedule them under compute — these flags ARE
the mechanism, so the winning set is part of the feature. ``--emit-json``
records the per-config ms/step and the winner into a JSON file (the
autopilot's flag-set action reads ``docs/measured/xla_flags.json`` when one
exists, ``pilot/actions.py``). Nothing applies the file to a run: a flag
this libtpu does not know aborts backend init, so each run names its flags
itself. The parent here never imports jax; the configurations run one
after the other, each owning the chip alone.

These are throughput experiments: anything that wins must be re-validated
for numerics before promotion (and flags are runtime-version-specific by
nature) — dryrun family #12 pins bucketed-vs-unbucketed bit-equality on
every gate run, which covers the collective-scheduling flags' numerics.

Usage::

    python examples/benchmark/xla_flag_ab.py [batch] [window] \
        [--emit-json docs/measured/xla_flags.json]
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys

# name -> (XLA_FLAGS additions, LIBTPU_INIT_ARGS additions)
CONFIGS = {
    "base": ("", ""),
    # Bigger scoped VMEM budget: deeper async prefetch of weights and
    # activation slices into the alternate memory the profile shows heavy
    # copy-start traffic through.
    "vmem128m": ("", "--xla_tpu_scoped_vmem_limit_kib=131072"),
    # Latency-hiding scheduler off: A/B whether its overlap choices help
    # this while-loop-of-fusions shape at all.
    "no_lhs": ("", "--xla_tpu_enable_latency_hiding_scheduler=false"),
    # Flip all-reduce/all-gather async continuation packing.
    "no_async_cf": ("", "--xla_tpu_enable_async_collective_fusion=false"),
    # Explicit enables of the scheduling passes bucketed backward-overlap
    # grad sync depends on (defaults vary across libtpu releases; pinning
    # them makes the bucketing win reproducible):
    "lhs_on": ("", "--xla_tpu_enable_latency_hiding_scheduler=true"),
    "async_cf_ag": ("", "--xla_tpu_enable_async_collective_fusion=true "
                        "--xla_tpu_enable_async_collective_fusion_"
                        "fuse_all_gather=true"),
    "overlap_all": ("", "--xla_tpu_enable_latency_hiding_scheduler=true "
                        "--xla_tpu_enable_async_collective_fusion=true "
                        "--xla_tpu_enable_async_collective_fusion_"
                        "fuse_all_gather=true"),
    "base_again": ("", ""),
}

LINE = re.compile(r"VARIANT \S+ b\d+ w\d+: ([0-9.]+) ms/step")


def run_one(name, xla, libtpu, batch, window):
    env = dict(os.environ)
    if xla:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + xla).strip()
    if libtpu:
        env["LIBTPU_INIT_ARGS"] = (
            env.get("LIBTPU_INIT_ARGS", "") + " " + libtpu).strip()
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "resnet_bounds.py")
    r = subprocess.run(
        [sys.executable, script, "base", batch, window],
        capture_output=True, text=True, timeout=900, env=env,
    )
    m = LINE.search(r.stdout or "")
    if r.returncode != 0 or not m:
        print(f"{name}: FAILED\n{(r.stderr or '')[-800:]}", file=sys.stderr)
        return None
    return float(m.group(1))


def emit_json(path, results, chosen, stable) -> None:
    """Record the winning flag set.

    ``chosen`` is a CONFIGS name; the file keeps the raw per-config
    ms/step so a later round can audit the decision."""
    xla, libtpu = CONFIGS[chosen]
    doc = {
        "source": "examples/benchmark/xla_flag_ab.py",
        "measured": stable and any(v for v in results.values()),
        "session_stable": stable,
        "chosen": {
            "name": chosen,
            "xla_flags": xla,
            "libtpu_init_args": libtpu,
        },
        "results_ms_per_step": {k: v for k, v in results.items()
                                if v is not None},
    }
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    print(f"recorded {chosen!r} -> {path}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("batch", nargs="?", default="128")
    ap.add_argument("window", nargs="?", default="20")
    ap.add_argument("--emit-json", metavar="PATH", default="",
                    help="record the winning flag set (bench.py applies it "
                         "by default)")
    args = ap.parse_args()

    results = {}
    for name, (xla, libtpu) in CONFIGS.items():
        ms = run_one(name, xla, libtpu, args.batch, args.window)
        results[name] = ms
        print(f"{name:>14s}: {'FAILED' if ms is None else f'{ms:.2f} ms/step'}",
              flush=True)
    b0, b1 = results.get("base"), results.get("base_again")
    stable = bool(b0 and b1 and abs(b0 - b1) / b0 <= 0.05)
    if b0 and b1 and not stable:
        print(f"\nUNSTABLE SESSION: base {b0:.2f} vs {b1:.2f} ms/step "
              "(>5% drift) — A/B void")
        return
    if b0:
        print("\nvs base:")
        for name, ms in results.items():
            if ms and name not in ("base", "base_again"):
                print(f"  {name:>14s}: {b0 / ms:5.2f}x")
    if args.emit_json:
        measured = {k: v for k, v in results.items()
                    if v is not None and k != "base_again"}
        # Winner = fastest measured config; "base" wins ties (no flags is
        # the simpler mechanism).
        chosen = min(measured, key=lambda k: (measured[k], k != "base")) \
            if measured else "overlap_all"
        emit_json(args.emit_json, results, chosen, stable)


if __name__ == "__main__":
    main()
