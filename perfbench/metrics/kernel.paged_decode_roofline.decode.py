"""kernel.paged_decode_roofline.decode: Paged Mosaic calls of the decode program: live KV bytes / 819 GB/s / their device time (memory-bound)."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.paged_decode_roofline(run, ctx)
