"""entry.compiles_in_window.train: Programs compiled inside the measured window; should read 0."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return float(readers.data(run, 'compiles_in_window'))
