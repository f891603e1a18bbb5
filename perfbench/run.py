#!/usr/bin/env python3
"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that finds the cell's files by the names in
``BENCHMARK.json`` (its configuration, the model family that file states
under ``families/``, its traffic mix and that mix's driver, its limits, its
metric readers), sets up (weights from the seed, the program built and
warmed up), measures for ``--seconds``, checks what the timed path produced
against the family's plain reference, and prints one JSON object as its
last line.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. There is no fallback: without a TPU
that the peak table knows, or with fewer chips than the cell asks for, it
exits 3 and prints no result.
"""
import time

_T0 = time.time()   # process start, before anything heavy is imported

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _say(msg: str) -> None:
    print(msg, flush=True)


def main(argv=None, *, manifest_path=None, data_dir=None, require_chip=True,
         hooks=None, t0=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t0 = _T0 if t0 is None else t0

    from perfbench.harness import compare, device, manifest, runtime

    cell = manifest.Cell(manifest.load(manifest_path), args.workload,
                         data_dir=data_dir)
    try:
        devices, device_info = device.find_devices(cell.chips, require_chip)
    except device.NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3

    import jax

    if require_chip:
        from autodist_tpu.utils.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        # Cache every program, the pallas calls that compile in under a
        # second too: they are what a warm set-up would otherwise repeat.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _say(f"compile cache: {cache_dir}")
    meter = runtime.CompileMeter()

    ctx = {
        "cell": cell, "seed": args.seed, "seconds": args.seconds,
        "trace": bool(args.trace), "devices": devices, "device": device_info,
        "peaks": device.PEAKS.get(device_info["kind"]), "meter": meter,
        "t0": t0, "hooks": hooks or {}, "say": _say, "root": ROOT,
    }
    run = cell.driver().run(ctx)
    if "run" in ctx["hooks"]:       # a test or the builder's tool looks on
        ctx["hooks"]["run"](run, ctx)

    correct, checks = compare.decide(run["numbers"], cell.limits)
    dev = dict(device_info, memory_peak_bytes=run["memory_peak_bytes"])
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    if args.trace:
        trace = run.get("trace")
        dev["busy_s"] = trace.busy_seconds() if trace else 0.0
        dev["window_s"] = run.get("trace_window_s", 0.0)
        for m in wanted:
            value = cell.reader(m["name"]).read(run, ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in wanted:
            metrics[m["name"]] = {"value": run["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": dev}
    if args.trace and run.get("trace") is not None:
        line["breakdown"] = {"device_ops": run["trace"].top_ops(10),
                             "idle_gaps": run["trace"].idle_gaps(10)}
    line["checks"] = checks
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value} (limit {limit})", file=sys.stderr)
    print(f"correct: {bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
