"""model.mfu.ttft: Required FLOPs of the window's prompts / sum of first-token seconds / peak."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.mfu_ttft(run, ctx)
