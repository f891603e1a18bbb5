#!/usr/bin/env python3
"""The builder's tool, never the driver's: one run of a cell that also
reads the control (the reference in the precision below, and for training
the planted faults) beside the program's own numbers, and can print what a
profile holds. The limits in ``limits/`` were set from these readings.

    python3 perfbench/control.py --workload <cell> --seed <n> --seconds <s> [--trace 1] [--dump-trace FILE]

With ``--selections`` (and ``--trace 1``) it is the plain run, no control,
that also prints which device operations the readers select in its profile
beside what the selection by shape of before PR 29 picks there (the oracle
kept in ``tests/perfbench/test_selection.py``): counts, summed seconds and
whether the two are the same operations.
"""
import sys
import time

_T0 = time.time()

import os  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    argv = sys.argv[1:]
    dump = None
    if "--dump-trace" in argv:
        i = argv.index("--dump-trace")
        dump = argv[i + 1]
        del argv[i:i + 2]
    selections = "--selections" in argv
    if selections:
        argv.remove("--selections")
    from perfbench import run
    from perfbench.harness import manifest, runtime, trace

    if dump:
        read = runtime.ProfilerSession.read

        def read_and_dump(self):
            import contextlib

            with open(dump, "w") as f, contextlib.redirect_stdout(f):
                trace._dump(trace.find_xplane(self.dir))
            return read(self)

        runtime.ProfilerSession.read = read_and_dump
    hooks = {
        "control_precisions": ("fp8",),
        "controls": {"fp8": {"precision": "fp8"},
                     "half_batch": {"rows_used": "half"}},
    }
    if selections:
        oracle = manifest.load_module(os.path.join(
            ROOT, "tests", "perfbench", "test_selection.py"), "perfbench_selection_oracle")

        def say_selections(result, ctx):
            for what, (old, new) in oracle.selections(result, ctx).items():
                seconds = [sum(op[2] for op in side) for side in (old, new)]
                print(f"selection of {what}: old {len(old)} in {seconds[0]:.9f} s, "
                      f"new {len(new)} in {seconds[1]:.9f} s, "
                      f"the same operations: {old == new}", flush=True)

        hooks = {"run": say_selections}
    return run.main(argv, hooks=hooks, t0=_T0)


if __name__ == "__main__":
    sys.exit(main())
