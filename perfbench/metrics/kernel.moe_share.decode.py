"""kernel.moe_share.decode: Device time of the operations named gmm (the experts' grouped product) / device busy time."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.kernel_share(run, 'gmm')
