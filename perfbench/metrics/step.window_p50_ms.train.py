"""step.window_p50_ms.train: Median host time between two windows' completions (one run[1] window = one step)."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return 1e3 * runtime.median(readers.data(run, 'window_times'))
