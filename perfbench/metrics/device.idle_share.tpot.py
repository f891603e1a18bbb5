"""device.idle_share.tpot: 1 - union of device op intervals / traced window.

The `.decode` reader under the name of the one end-to-end metric that
`evabyte-serve-decode-long` reports; it goes when that cell can report
`serve_tok_s` and joins `device.idle_share.decode` (PERF.md section 7.9)."""
from perfbench.harness import readers


def read(run, ctx):
    return readers.idle_share(run, ctx)
