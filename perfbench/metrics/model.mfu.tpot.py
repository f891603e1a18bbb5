"""model.mfu.tpot: Required FLOPs of one decode step over all slots / serve_tpot_tail_s / peak."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.mfu_tpot(run, ctx)
