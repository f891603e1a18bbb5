"""Arithmetic shared by the per-layer metric readers under ``metrics/``.

A reader is ``read(run, ctx)``: ``run`` is what the driver returned
(``data``, ``end_to_end``, ``trace``, ``memory_peak_bytes``), ``ctx`` holds
the cell, the model and the chip's peaks. A reader that finds nothing to
read returns None and the metric is left out of the line; it never returns
0 for a share of a roofline or of a peak.

Required work (a model's operations per token, a kernel's operations and
bytes) is the cell's family's to count (``perfbench/families/<family>.py``);
programs and kernels are found in the trace by the names the program gives
them (``harness/spanread.py``), never by a shape.
"""
from __future__ import annotations

import math

from perfbench.harness import counts, runtime, spanread

PREFILL_PROGRAM = "jit_serve_prefill_chunk"
DECODE_PROGRAM = "jit_serve_decode_step"
PAGED_KERNELS = ("paged_attention",)
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")


def data(run, key):
    return run["data"].get(key)


def share(part, whole):
    if part is None or not whole:
        return None
    return 100.0 * part / whole


def positive(x):
    return x if x is not None and x > 0 else None


def peak_flops(ctx):
    return ctx["peaks"]["flops_per_s"] if ctx.get("peaks") else None


def family(ctx):
    return ctx["cell"].family()


# ------------------------------------------------------------------ device
def idle_share(run, ctx):
    tr, w = run.get("trace"), run.get("trace_window_s")
    if tr is None or not w:
        return None
    return 100.0 * (1.0 - tr.busy_seconds() / w)


def peak_hbm_gb(run, ctx):
    return positive(run["memory_peak_bytes"] / 1e9)


# ---------------------------------------------------------------- training
def mfu_train(run, ctx):
    d, peak = run["data"], peak_flops(ctx)
    if peak is None:
        return None
    per_token = family(ctx).train_flops_per_token(ctx["cell"].model, d["seq"])
    return 100.0 * per_token * d["tokens"] / d["window_s"] / d["chips"] / peak


def collective_share(run, ctx):
    tr, w = run.get("trace"), run.get("trace_window_s")
    if tr is None or not w:
        return None
    return positive(100.0 * tr.collective_seconds() / w)


# ----------------------------------------------------------------- serving
def _request_flops(fam, model, prompt, n_out):
    return fam.prefill_flops(model, prompt) + sum(
        fam.decode_flops(model, prompt + i) for i in range(1, n_out))


def flops_per_served_token(run, ctx):
    """Required operations per generated token, averaged over the
    requests the window finished (the first token is the prompt's)."""
    done = [(p, n) for p, n in run["data"]["finished"] if n > 0]
    if not done:
        return None
    fam, model = family(ctx), ctx["cell"].model
    return (sum(_request_flops(fam, model, p, n) for p, n in done)
            / sum(n for _, n in done))


def mean_context(run):
    done = [(p, n) for p, n in run["data"]["finished"] if n > 0]
    if not done:
        return None
    return sum((p + n / 2.0) * n for p, n in done) / sum(n for _, n in done)


def mfu_decode(run, ctx):
    per, peak = flops_per_served_token(run, ctx), peak_flops(ctx)
    if per is None or peak is None:
        return None
    return 100.0 * per * run["data"]["tokens"] / run["data"]["window_s"] / peak


def mfu_tpot(run, ctx):
    tail, peak, c = run["end_to_end"].get("serve_tpot_tail_s"), peak_flops(ctx), mean_context(run)
    if not tail or peak is None or c is None:
        return None
    rows = run["data"]["engine"]["n_slots"]
    return 100.0 * rows * family(ctx).decode_flops(ctx["cell"].model, int(c)) / tail / peak


def mfu_ttft(run, ctx):
    first, peak = run["data"]["first"], peak_flops(ctx)
    if not first or peak is None:
        return None
    fam, model = family(ctx), ctx["cell"].model
    need = sum(fam.prefill_flops(model, n) for _, n in first)
    return 100.0 * need / sum(t for t, _ in first) / peak


def prefill_chunk_p50_ms(run, ctx):
    first = run["data"]["first"]
    if not first:
        return None
    chunk = run["data"]["engine"]["prefill_chunk"]
    return 1e3 * runtime.median([t / math.ceil(n / chunk) for t, n in first])


def tick_p50_ms(run, ctx):
    ticks = run["data"]["ticks"]
    return 1e3 * runtime.median([t[0] for t in ticks]) if ticks else None


def gap_band_mean_ms(run, ctx):
    gaps = run["data"]["gaps"]
    return 1e3 * runtime.band_mean(gaps) if gaps else None


def slot_occupancy(run, ctx):
    ticks = run["data"]["ticks"]
    if not ticks:
        return None
    return 100.0 * sum(t[2] for t in ticks) / len(ticks) / run["data"]["engine"]["n_slots"]


def prefill_ticks_share(run, ctx):
    ticks = run["data"]["ticks"]
    return positive(100.0 * sum(1 for t in ticks if t[1]) / len(ticks)) if ticks else None


# ------------------------------------------------------------ device trace
def serving_runs(run):
    """``(prefill runs, decode runs)``: the device runs of the two serving
    programs, found by their modules' names; None without a trace."""
    tr = run.get("trace")
    if tr is None:
        return None
    return (spanread.module_runs(tr, PREFILL_PROGRAM),
            spanread.module_runs(tr, DECODE_PROGRAM))


def prefill_busy_share(run, ctx):
    found = serving_runs(run)
    if not found or not (found[0] or found[1]):
        return None
    pre = sum(e - s for s, e in found[0])
    return positive(100.0 * pre / (pre + sum(e - s for s, e in found[1])))


def kernel_calls(run, kernels, program=None):
    """``{kernel: [operation, ...]}`` of the traced window: the device
    operations named as one of ``kernels``, inside the runs of the module
    named ``program`` where one is given."""
    tr = run.get("trace")
    if tr is None:
        return {}
    runs = None
    if program is not None:
        runs = spanread.module_runs(tr, program)
        if not runs:
            return {}
    return spanread.kernel_ops(tr, kernels, runs)


def run_facts(run):
    """What the run knows of the shapes a kernel worked on, for the
    family's ``kernel_work``: a train step's rows a chip and ``seq``; a
    serving window's rows (slots in use, averaged over its ticks), mean
    ``context`` of the tokens it served, and the ``engine``'s own facts."""
    d = run["data"]
    if d["kind"] == "train":
        return {"rows": d["batch"] // d["chips"], "seq": d["seq"]}
    ticks, eng = d["ticks"], d["engine"]
    rows = sum(t[2] for t in ticks) / len(ticks) if ticks else eng["n_slots"]
    return {"rows": rows, "context": mean_context(run), "engine": eng}


def kernel_roofline(run, ctx, kernels, program=None):
    """The least time the chip could take for the traced calls of
    ``kernels`` (per call the family's ``kernel_work`` against the peaks:
    whichever of operations and bytes bounds it) / their device time, %."""
    if not ctx.get("peaks"):
        return None
    calls = kernel_calls(run, kernels, program)
    facts = run_facts(run) if calls else {}
    if not calls or any(v is None for v in facts.values()):
        return None
    fam, model = family(ctx), ctx["cell"].model
    least = sum(len(ops) * counts.roofline_seconds(
        *fam.kernel_work(kernel, model, facts), ctx["peaks"])[0]
        for kernel, ops in calls.items())
    return 100.0 * least / sum(op[2] for ops in calls.values() for op in ops)


def paged_decode_roofline(run, ctx):
    """Mosaic page-walking calls inside the decode program: the least
    time the chip could take for the live keys and values (bytes at
    819 GB/s against FLOPs at 197 TFLOP/s: memory bounds it) / their time."""
    return kernel_roofline(run, ctx, PAGED_KERNELS, program=DECODE_PROGRAM)


def flash_roofline(run, ctx):
    """Flash Mosaic calls (forward, dk/dv, dq) of the traced steps: required
    causal FLOPs at 197 TFLOP/s (compute bounds them) / their device time."""
    return kernel_roofline(run, ctx, FLASH_KERNELS)
