"""sched.idle_in_bookkeeping_share.decode: Idle seconds of the device that lie under the rest of serve.tick (admit, emit, tick_metrics, decode_step's and its own self time) and serve.on_tick / traced window."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.idle_share_in(run, ctx, 'bookkeeping')
