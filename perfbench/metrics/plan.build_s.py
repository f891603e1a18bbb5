"""plan.build_s: Host clock around AutoDist.build / build_inference: capture, strategy, plan."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.positive(readers.data(run, 'plan_build_s'))
