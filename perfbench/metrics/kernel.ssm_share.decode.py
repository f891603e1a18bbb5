"""kernel.ssm_share.decode: Device time of the operations named ssm_state_update (the decode step's Mamba state update) / device busy time."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.kernel_share(run, 'ssm_state_update')
