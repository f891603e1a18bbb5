"""engine.decode_tick_p50_ms.decode: Median scheduler tick from the batcher's on_tick hook."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.tick_p50_ms(run, ctx)
