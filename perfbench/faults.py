#!/usr/bin/env python3
"""The builder's tool beside ``control.py``, for a serving cell whose family
plants faults in its reference (``FAULTS``: names ``next_token_logits``
takes where it takes a precision): one run of the cell that reads, beside
the program's own ``logit_gap``, the gap of the byte the fp8 control puts
first and of the byte each faulty reference puts first, at the positions
of the same served bytes. A limit in ``limits/`` has to lie under all of
them. Never the driver's.

    python3 perfbench/faults.py --workload <cell> --seed <n> --seconds <s>
"""
import sys
import time

_T0 = time.time()

import os  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    from perfbench import run
    from perfbench.harness import manifest

    argv = sys.argv[1:]
    cell = manifest.Cell(manifest.load(), argv[argv.index("--workload") + 1])
    faults = tuple(getattr(cell.family(), "FAULTS", ()))
    return run.main(argv, hooks={"control_precisions": ("fp8",) + faults}, t0=_T0)


if __name__ == "__main__":
    sys.exit(main())
