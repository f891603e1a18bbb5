"""input.wait_share.train: Host time in the loader's next and the window's transfer / measured window."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.share(readers.data(run, 'input_wait_s'), readers.data(run, 'window_s'))
