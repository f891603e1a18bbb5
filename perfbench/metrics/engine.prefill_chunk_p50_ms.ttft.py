"""engine.prefill_chunk_p50_ms.ttft: Median over requests of first-token seconds / prefill chunks of the prompt."""
from perfbench.harness import readers, runtime  # noqa: F401


def read(run, ctx):
    return readers.prefill_chunk_p50_ms(run, ctx)
