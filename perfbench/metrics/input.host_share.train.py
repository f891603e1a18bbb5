"""input.host_share.train: Host seconds under input.next and input.stage / traced window."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.span_share(run, 'input.next', 'input.stage')
