"""sched.idle_outside_tick_share.decode: Idle seconds of the device that lie under no program span (the loop, a lock, the collector) / traced window."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.idle_share_in(run, ctx, 'outside')
