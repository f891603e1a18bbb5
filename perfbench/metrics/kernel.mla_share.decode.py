"""kernel.mla_share.decode: Device time of the operations named mla_paged_attention / device busy time."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.kernel_share(run, 'mla_paged_attention')
