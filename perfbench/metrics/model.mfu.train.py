"""model.mfu.train: Required forward+backward FLOPs per token x tokens/s/chip / peak."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.mfu_train(run, ctx)
