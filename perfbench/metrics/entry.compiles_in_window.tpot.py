"""entry.compiles_in_window.tpot: Programs compiled or loaded between the window's opening and its close (has to be 0).

The `.decode` reader under the name of the one end-to-end metric that
`evabyte-serve-decode-long` reports; it goes when that cell can report
`serve_tok_s` and joins `entry.compiles_in_window.decode` (PERF.md section
7.9)."""
from perfbench.harness import readers


def read(run, ctx):
    return float(readers.data(run, 'compiles_in_window'))
