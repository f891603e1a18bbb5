"""device.idle_share.decode: 1 - union of device-operation intervals / traced window, averaged over chips."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.idle_share(run, ctx)
