"""device.peak_hbm_gb.decode: peak_bytes_in_use on the fullest chip."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.peak_hbm_gb(run, ctx)
