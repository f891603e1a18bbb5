"""engine.decode_step_device_p50_ms.tpot: Median device run of the module jit_serve_decode_step: the part of the gap's tail that every tick has.

The `.decode` reader under the name of the one end-to-end metric that
`evabyte-serve-decode-long` reports; it goes when that cell can report
`serve_tok_s` and joins `engine.decode_step_device_p50_ms.decode` (PERF.md
section 7.9)."""
from perfbench.harness import readers, spanread


def read(run, ctx):
    return spanread.module_p50_ms(run, readers.DECODE_PROGRAM)
