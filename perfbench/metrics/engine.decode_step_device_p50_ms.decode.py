"""engine.decode_step_device_p50_ms.decode: Median device run of the module named jit_serve_decode_step."""
from perfbench.harness import spanread


def read(run, ctx):
    return spanread.module_p50_ms(run, 'jit_serve_decode_step')
