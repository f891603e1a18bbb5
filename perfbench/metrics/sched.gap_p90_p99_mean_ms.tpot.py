"""sched.gap_p90_p99_mean_ms.tpot: Mean of the window's gaps from the 90th to the 99th percentile: every class of chunk tick at once, where the tail's percentile sees the one it lies in."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.gap_band_mean_ms(run, ctx)
