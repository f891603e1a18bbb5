"""kernel.flash_share.train: Device time of flash_fwd + flash_bwd_dkv + flash_bwd_dq / device busy time."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.kernel_share(run, 'flash_fwd', 'flash_bwd_dkv', 'flash_bwd_dq')
