"""kernel.paged_share.ttft: Device time of the operations named paged_attention / device busy time (prefill cell)."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.kernel_share(run, 'paged_attention')
