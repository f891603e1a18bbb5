"""engine.token_copy_p50_ms.decode: Median over the serve.token_fetch spans of the traced window inside a serve.decode_step of (length - their wait.tokens), on the trace's clock: what is left of the copy once the tokens are ready."""
from perfbench.harness import waits


def read(run, ctx):
    return waits.token_copy_p50_ms(run)
