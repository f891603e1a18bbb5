"""sched.tick_idle_p50_ms.decode: Median over the serve.tick spans of the traced window of (span length - device busy time inside it)."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.tick_idle_p50_ms(run, ctx)
