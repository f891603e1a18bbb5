"""sched.idle_in_dispatch_share.decode: Idle seconds of the device that lie under serve.prefill_chunk or serve.decode_dispatch (each idle gap split over the innermost program spans it overlaps) / traced window."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.idle_share_in(run, ctx, 'dispatch')
