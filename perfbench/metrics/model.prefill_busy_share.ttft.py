"""model.prefill_busy_share.ttft: Device time inside the prefill-chunk program / device time inside both serving programs."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.prefill_busy_share(run, ctx)
