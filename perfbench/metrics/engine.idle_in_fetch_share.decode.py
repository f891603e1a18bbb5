"""engine.idle_in_fetch_share.decode: Idle seconds of the device that lie under serve.token_fetch (the fetch's tail after the device has finished) / traced window."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.idle_share_in(run, ctx, 'fetch')
