"""model.mfu.decode: Required FLOPs per generated token x serve_tok_s / peak."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.mfu_decode(run, ctx)
