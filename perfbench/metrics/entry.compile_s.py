"""entry.compile_s: Seconds of jax.monitoring compile and cache-load time in set-up."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.positive(readers.data(run, 'compile_s'))
