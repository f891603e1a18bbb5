"""sched.slot_occupancy.decode: Slots holding a request, averaged over the window's ticks."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.slot_occupancy(run, ctx)
