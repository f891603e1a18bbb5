"""step.dispatch_p50_ms.train: Median length of the train.window_dispatch spans in the trace."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.span_p50_ms(run, 'train.window_dispatch')
