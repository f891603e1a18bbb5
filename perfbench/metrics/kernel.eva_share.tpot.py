"""kernel.eva_share.tpot: Device time of the operations named eva_paged_attention (both serving programs) / device busy time."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.kernel_share(run, 'eva_paged_attention')
