"""engine.prefill_chunk_device_p50_ms.tpot: Median device run of the module named jit_serve_prefill_chunk (decode cell)."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.module_p50_ms(run, 'jit_serve_prefill_chunk')
