"""kernel.paged_share.decode: Device time of the operations named paged_attention / device busy time (decode cell)."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.kernel_share(run, 'paged_attention')
