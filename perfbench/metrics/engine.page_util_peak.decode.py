"""engine.page_util_peak.decode: Peak of the gauge serve_page_pool_utilization over the window's ticks."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.positive(100.0 * readers.data(run, 'pool_peak'))
