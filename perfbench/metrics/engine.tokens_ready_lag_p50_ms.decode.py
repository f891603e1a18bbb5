"""engine.tokens_ready_lag_p50_ms.decode: Median over the traced decode steps of (end of the step's wait.tokens host event - end of the jit_serve_decode_step run it waited for), on the trace's clock."""
from perfbench.harness import waits


def read(run, ctx):
    return waits.tokens_ready_lag_p50_ms(run)
