"""engine.prefill_dispatch_p50_ms.ttft: Median length of the non-final serve.prefill_chunk spans: the host's floor under a chunk."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.dispatch_only_p50_ms(run)
