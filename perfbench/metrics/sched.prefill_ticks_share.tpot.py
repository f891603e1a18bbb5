"""sched.prefill_ticks_share.tpot: Ticks that carried at least one prefill chunk / ticks."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.prefill_ticks_share(run, ctx)
