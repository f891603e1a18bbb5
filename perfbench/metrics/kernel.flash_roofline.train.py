"""kernel.flash_roofline.train: Flash Mosaic calls in the trace: required causal FLOPs / 197 TFLOP/s / their device time (compute-bound)."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.flash_roofline(run, ctx)
