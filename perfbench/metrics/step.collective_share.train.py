"""step.collective_share.train: Device time of collective operations / traced window, lowest device."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.collective_share(run, ctx)
