"""The program's own spans, read off the device trace's clock.

``autodist_tpu.obs.spans`` holds a ``jax.profiler.TraceAnnotation`` open
for the life of every span, so a profile's host plane carries the program's
phases (``serve.tick``, ``serve.decode_dispatch``, ``train.window_dispatch``
...) as events named like the span, beside the runtime's own events and on
the clock of the device's operations. This module takes them out of
``run["trace"].host_events`` by name, flattens them to the innermost span
at each instant, and cuts them against ``Trace.busy_intervals``: which phases of the program the host
went through while the device had nothing to run.

The program's modules and kernels are found by the names the program gives
them (``jit_serve_decode_step``, ``paged_attention``), never by a shape.
Every reader returns None where the trace holds no such span, module or
kernel: a program from before these names, or a run without a trace.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

from perfbench.harness import runtime

PREFIXES = ("serve.", "train.", "input.")
DISPATCH = ("serve.prefill_chunk", "serve.decode_dispatch")
FETCH = ("serve.token_fetch",)
TICK = "serve.tick"
OUTSIDE = "(no program span)"


# ------------------------------------------------------------- the spans
def program_spans(trace):
    """``[(name, start, end)]`` of the host events that are program spans,
    by start; kept on the trace object, which is read many times."""
    found = getattr(trace, "_program_spans", None)
    if found is None:
        found = sorted(((n, s, s + d) for n, s, d in trace.host_events
                        if n.startswith(PREFIXES)), key=lambda e: (e[1], -e[2]))
        trace._program_spans = found
    return found


def named(trace, *names):
    """Intervals ``[(start, end)]`` of the program spans called ``names``."""
    return [(s, e) for n, s, e in program_spans(trace) if n in names]


# ------------------------------------------------------------ the window
def window(run):
    """The traced window ``(start, end)`` on the trace's clock: it opens
    where the profiler's ``start_trace`` call returns (the harness takes
    its own clock there) and lasts ``trace_window_s``. None without both."""
    trace, length = run.get("trace"), run.get("trace_window_s")
    if trace is None or not length:
        return None
    ends = [s + d for n, s, d in trace.host_events if n.endswith(" start_trace")]
    start = min(ends) if ends else 0.0
    return start, start + length


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def device(trace):
    return min(trace.device_ops) if trace.device_ops else None


def busy_intervals(trace):
    """``Trace.busy_intervals`` of the lowest device, kept on the trace
    object: the union over half a million operations is taken once."""
    found = getattr(trace, "_busy_lowest", None)
    if found is None:
        found = trace._busy_lowest = trace.busy_intervals(device(trace))
    return found


def busy(run):
    """Busy intervals of the lowest device, cut to the traced window."""
    trace, win = run.get("trace"), window(run)
    if trace is None or win is None or device(trace) is None:
        return None
    return _clip(busy_intervals(trace), *win)


class _Cover:
    """Seconds of a set of disjoint sorted intervals inside any ``[lo, hi]``."""

    def __init__(self, intervals):
        self.starts = [s for s, _ in intervals]
        self.ends = [e for _, e in intervals]
        self.before = [0.0]
        for s, e in intervals:
            self.before.append(self.before[-1] + e - s)

    def upto(self, t):
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.before[i] - max(0.0, self.ends[i - 1] - t)

    def within(self, lo, hi):
        return self.upto(hi) - self.upto(lo)


def idle_gaps(run):
    """The traced window minus the busy intervals: ``[(start, end)]``."""
    win, b = window(run), busy(run)
    if win is None or b is None:
        return None
    gaps, at = [], win[0]
    for s, e in b:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if win[1] > at:
        gaps.append((at, win[1]))
    return gaps


def innermost(spans):
    """The spans flattened to disjoint ``[(start, end, name)]`` pieces, each
    under the innermost span open there (a child cut to its parent)."""
    pieces, stack, at = [], [], 0.0     # stack: [name, end] of the open spans
    eps = 1e-9      # the profile's resolution: a sibling may start on the dot

    def close_until(t):
        nonlocal at
        while stack and stack[-1][1] <= t + eps:
            name, end = stack.pop()
            if end > at:
                pieces.append((at, end, name))
                at = end

    for name, s, e in spans:
        close_until(s)
        if stack:
            if s > at:
                pieces.append((at, s, stack[-1][0]))
            e = min(e, stack[-1][1])
        at = max(at, s) if stack else s
        if e > at:
            stack.append([name, e])
    close_until(float("inf"))
    return pieces


def self_seconds(trace):
    """``{span name: seconds}``: each span's length minus what its children
    cover (choosing-metrics section 4), added up by name: the pieces of
    the timeline that have it as their innermost span."""
    out = defaultdict(float)
    for s, e, name in innermost(program_spans(trace)):
        out[name] += e - s
    return dict(out)


def idle_by_span(run):
    """``{span name: idle seconds}``: every idle gap of the traced window is
    split over the innermost program spans it overlaps; what lies under no
    span goes to ``OUTSIDE``. (A gap of the decode cell runs from the end
    of one device run to the start of the next, across the fetch's tail,
    the book-keeping and the next dispatch: given whole to the span over
    its middle it read as book-keeping, whichever phase was the long one.)"""
    trace, gaps = run.get("trace"), idle_gaps(run)
    if gaps is None:
        return None
    pieces = innermost(program_spans(trace))
    starts = [p[0] for p in pieces]
    out = defaultdict(float)
    for lo, hi in gaps:
        under = 0.0
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(pieces) and pieces[i][0] < hi:
            s, e, name = pieces[i]
            part = min(e, hi) - max(s, lo)
            if part > 0:
                out[name] += part
                under += part
            i += 1
        out[OUTSIDE] += (hi - lo) - under
    return dict(out)


# -------------------------------------------------- the decode cell's split
def idle_split(run, ctx):
    """The four shares of the traced window (in %) in which the device was
    idle: under a dispatch span, under the token fetch, under the rest of
    the tick and the observer's hook, and under no program span. They have
    to add up to the cell's idle share within half a point; otherwise, or
    where the trace holds no ``serve.tick``, None. Prints the idle seconds
    and the self time per span name once."""
    if "_idle_split" in run:
        return run["_idle_split"]
    run["_idle_split"] = None
    trace, by_span = run.get("trace"), idle_by_span(run)
    if by_span is None or not named(trace, TICK):
        return None
    length = run["trace_window_s"]
    split = {"dispatch": 0.0, "fetch": 0.0, "bookkeeping": 0.0, "outside": 0.0}
    for name, sec in by_span.items():
        key = ("outside" if name == OUTSIDE else "dispatch" if name in DISPATCH
               else "fetch" if name in FETCH else "bookkeeping")
        split[key] += 100.0 * sec / length
    whole = 100.0 * (1.0 - trace.busy_seconds() / length)
    say = ctx.get("say", print)
    say("idle seconds of the traced window by program span: " + ", ".join(
        f"{n} {s:.4f}" for n, s in sorted(by_span.items(), key=lambda kv: -kv[1])))
    say("self seconds by program span: " + ", ".join(
        f"{n} {s:.4f}" for n, s in sorted(self_seconds(trace).items(),
                                          key=lambda kv: -kv[1])))
    say(f"idle shares {split} add up to {sum(split.values()):.3f}%; "
        f"the device's idle share is {whole:.3f}%")
    if abs(sum(split.values()) - whole) > 0.5:
        return None
    run["_idle_split"] = split
    return split


def idle_share_in(run, ctx, part):
    split = idle_split(run, ctx)
    return None if split is None else split[part]


def tick_idle_p50_ms(run, ctx):
    """Median over the ``serve.tick`` spans inside the traced window of
    (the span's length - the device's busy time inside it)."""
    win, b = window(run), busy(run)
    if b is None:
        return None
    cover = _Cover(b)
    idle = [(e - s) - cover.within(s, e) for s, e in named(run["trace"], TICK)
            if s >= win[0] and e <= win[1]]
    return 1e3 * runtime.median(idle) if idle else None


# ------------------------------------------------- span lengths and shares
def span_p50_ms(run, name):
    """Median length of the program spans called ``name`` in the trace."""
    trace = run.get("trace")
    lengths = [e - s for s, e in named(trace, name)] if trace else []
    return 1e3 * runtime.median(lengths) if lengths else None


def span_share(run, *names):
    """Seconds under the program spans called ``names`` / traced window, %."""
    trace, length = run.get("trace"), run.get("trace_window_s")
    found = named(trace, *names) if trace and length else []
    if not found:
        return None
    return 100.0 * sum(e - s for s, e in found) / length


def dispatch_only_p50_ms(run):
    """Median length of the ``serve.prefill_chunk`` spans that are not a
    prompt's last chunk (the program marks them ``final=False``): what the
    host needs to prepare and dispatch a chunk. From the program's own span
    ring over the measured window."""
    lengths = [s.dur_s for s in run["data"].get("spans", ())
               if s.name == "serve.prefill_chunk"
               and s.attrs.get("final") is False]
    return 1e3 * runtime.median(lengths) if lengths else None


# -------------------------------------------------- modules and kernels
def module_runs(trace, module):
    """Device runs ``[(start, end)]`` of the program whose module is called
    ``module`` (``jit_`` + the jitted function's name; the trace adds a
    fingerprint), by start."""
    return sorted((s, e) for name, rs in trace.module_runs().items()
                  if re.match(re.escape(module) + r"(\(|$)", name) for s, e in rs)


def module_p50_ms(run, module):
    """Median device run of the program whose module is called ``module``."""
    trace = run.get("trace")
    runs = [e - s for s, e in module_runs(trace, module)] if trace else []
    return 1e3 * runtime.median(runs) if runs else None


def kernel_ops(trace, kernels, runs=None):
    """``{kernel: [(name, start, seconds)]}``: the operations of the lowest
    device whose own name holds one of ``kernels`` (the ``name=`` of a
    ``pl.pallas_call``; transforms wrap it: ``jvp_flash_fwd_``,
    ``transpose_jvp_flash_bwd_dq__``), those that start inside ``runs``
    where runs are given. An operation is named by the instruction it is,
    never by an operand it reads or a shape it has."""
    if device(trace) is None:
        return {}
    # the longest name first, so that a kernel whose name holds another's
    # is not taken for it
    rx = re.compile(r"^%?[\w.\-]*?(" + "|".join(
        map(re.escape, sorted(kernels, key=len, reverse=True))) + r")[\w.\-]*( = |$)")
    ops = trace.device_ops[device(trace)] if runs is None else trace.ops_within(runs)
    found = defaultdict(list)
    for op in ops:
        m = rx.match(op[0])
        if m:
            found[m.group(1)].append(op)
    return dict(found)


def kernel_share(run, *kernels):
    """Device time of the operations named as ``kernels`` (``kernel_ops``)
    / the device's busy time, in %."""
    trace = run.get("trace")
    if trace is None:
        return None
    sec = sum(op[2] for ops in kernel_ops(trace, kernels).values() for op in ops)
    whole = sum(e - s for s, e in busy_intervals(trace)) if sec else 0.0
    if not sec or not whole:
        return None
    return 100.0 * sec / whole
