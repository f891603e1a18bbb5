"""The host's waits for the device, apart from its own work.

The serving engine opens a ``wait.tokens`` span (``program``:
``decode_step`` / ``prefill_chunk``) around ``jax.block_until_ready`` on a
program's token vector, inside ``serve.token_fetch``; every wait a tick
makes on the device carries a ``wait.`` name. A wait is not a phase of the
host's work, so ``spanread.PREFIXES`` leaves these names out and the phase
readers (the idle shares, the tick's idle time) read what they read before
the span existed.

Three numbers come of it, all over the traced window and on the trace's
clock: the host's own work in a decode tick (the tick less its waits), the
copy after the tokens are ready (a decode step's fetch less its wait), and
the time from the end of a decode step's device run to the end of the wait
on it, which is the instant the host learns the step has run. They read the
profile and not the span ring: the ring's ticks after the traced seconds
run beside the profiler's export, which takes the host's cores for as long
as it lasts, so what they read moves with the export. Each returns None
where no ``wait.tokens`` span is found: a program from before the span, or
a run without a trace.
"""
from __future__ import annotations

import bisect

from perfbench.harness import runtime, spanread

WAIT = "wait."
TOKENS = "wait.tokens"
STEP = "serve.decode_step"
DECODE_MODULE = "jit_serve_decode_step"


def _spans(run):
    """``{name: [(start, end)]}`` of the wait and program spans that lie
    inside the traced window, by start; None without a trace or without a
    ``wait.tokens`` span there."""
    trace, win = run.get("trace"), spanread.window(run)
    if trace is None or win is None:
        return None
    found = getattr(trace, "_wait_spans", None)
    if found is None:
        found = trace._wait_spans = sorted(
            (n, s, s + d) for n, s, d in trace.host_events if n.startswith(WAIT))
    by_name = {}
    for n, s, e in found + spanread.program_spans(trace):
        if s >= win[0] and e <= win[1]:
            by_name.setdefault(n, []).append((s, e))
    if not by_name.get(TOKENS):
        return None
    for spans in by_name.values():
        spans.sort()
    return by_name


def _within(outer, inner):
    """For each interval of ``outer``, the intervals of ``inner`` (sorted
    by start) that lie inside it."""
    starts = [s for s, _ in inner]
    return [[(s, e) for s, e in inner[bisect.bisect_left(starts, lo):
                                      bisect.bisect_right(starts, hi)] if e <= hi]
            for lo, hi in outer]


def _ms_median(values):
    return 1e3 * runtime.median(values) if values else None


def _waits(by_name):
    return sorted(iv for n, ivs in by_name.items() if n.startswith(WAIT)
                  for iv in ivs)


def tick_host_busy_s(run):
    """Seconds of each traced ``serve.tick`` that holds a
    ``serve.decode_step``, less the ``wait.*`` spans inside it; empty where
    the trace holds no ``wait.tokens``."""
    by_name = _spans(run)
    if by_name is None:
        return []
    ticks = by_name.get(spanread.TICK, [])
    steps = _within(ticks, by_name.get(STEP, []))
    waits = _within(ticks, _waits(by_name))
    return [e - s - sum(we - ws for ws, we in w)
            for (s, e), st, w in zip(ticks, steps, waits) if st]


def host_busy_p50_ms(run):
    """Median over the traced decode ticks of the tick's length less its
    waits on the device: the host's own work in a tick."""
    return _ms_median(tick_host_busy_s(run))


def token_copy_p50_ms(run):
    """Median over the traced ``serve.token_fetch`` spans inside a
    ``serve.decode_step`` of their length less their ``wait.tokens``: what
    is left of the copy of the tokens (and the step's facts) once they are
    ready."""
    by_name = _spans(run)
    if by_name is None:
        return None
    fetch = sorted(iv for n in spanread.FETCH for iv in by_name.get(n, []))
    fetches = sorted(f for fs in _within(by_name.get(STEP, []), fetch)
                     for f in fs)
    waits = _within(fetches, by_name[TOKENS])
    return _ms_median([e - s - sum(we - ws for ws, we in w)
                       for (s, e), w in zip(fetches, waits) if w])


def tokens_ready_lag_p50_ms(run):
    """Median over the traced waits inside a ``serve.decode_step`` of the
    wait's end less the end of the latest ``jit_serve_decode_step`` device
    run that ends before it does: how long after the step's run the host
    learns of it."""
    by_name = _spans(run)
    if by_name is None:
        return None
    waits = [w for ws in _within(by_name.get(STEP, []), by_name[TOKENS])
             for w in ws]
    ends = sorted(e for _, e in spanread.module_runs(run["trace"], DECODE_MODULE))
    lags = []
    for _, e in waits:
        j = bisect.bisect_right(ends, e) - 1
        if j >= 0:
            lags.append(e - ends[j])
    return _ms_median(lags)
