"""sched.host_busy_p50_ms.decode: Median over the serve.tick spans of the traced window that hold a serve.decode_step of (tick length - the wait.* spans inside it), on the trace's clock: the host's own work in a tick."""
from perfbench.harness import waits


def read(run, ctx):
    return waits.host_busy_p50_ms(run)
