"""Arithmetic shared by the per-layer metric readers under ``metrics/``.

A reader is ``read(run, ctx)``: ``run`` is what the driver returned
(``data``, ``end_to_end``, ``trace``, ``memory_peak_bytes``), ``ctx`` holds
the cell, the model and the chip's peaks. A reader that finds nothing to
read returns None and the metric is left out of the line; it never returns
0 for a share of a roofline or of a peak.
"""
from __future__ import annotations

import math

from perfbench.harness import counts, runtime


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
    per_token = counts.train_flops_per_token(ctx["cell"].model, d["seq"])
    return 100.0 * per_token * d["tokens"] / d["window_s"] / d["chips"] / peak


def collective_share(run, ctx):
    tr, w = run.get("trace"), run.get("trace_window_s")
    if tr is None or not w:
        return None
    return positive(100.0 * tr.collective_seconds() / w)


# ----------------------------------------------------------------- serving
def _request_flops(model, prompt, n_out):
    return counts.prefill_flops(model, prompt) + sum(
        counts.decode_flops(model, prompt + i) for i in range(1, n_out))


def flops_per_served_token(run, ctx):
    """Required operations per generated token, averaged over the
    requests the window finished (the first token is the prompt's)."""
    done = [(p, n) for p, n in run["data"]["finished"] if n > 0]
    if not done:
        return None
    model = ctx["cell"].model
    return (sum(_request_flops(model, p, n) for p, n in done)
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
    p95, peak, c = run["end_to_end"].get("serve_tpot_p95_s"), peak_flops(ctx), mean_context(run)
    if not p95 or peak is None or c is None:
        return None
    rows = run["data"]["engine"]["n_slots"]
    return 100.0 * rows * counts.decode_flops(ctx["cell"].model, int(c)) / p95 / peak


def mfu_ttft(run, ctx):
    first, peak = run["data"]["first"], peak_flops(ctx)
    if not first or peak is None:
        return None
    model = ctx["cell"].model
    need = sum(counts.prefill_flops(model, n) for _, n in first)
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


def slot_occupancy(run, ctx):
    ticks = run["data"]["ticks"]
    if not ticks:
        return None
    return 100.0 * sum(t[2] for t in ticks) / len(ticks) / run["data"]["engine"]["n_slots"]


def prefill_ticks_share(run, ctx):
    ticks = run["data"]["ticks"]
    return positive(100.0 * sum(1 for t in ticks if t[1]) / len(ticks)) if ticks else None


# ------------------------------------------------------------ device trace
def _serving_modules(run, ctx):
    """Tell the two serving programs apart in the trace: the prefill-chunk
    program works on ``[chunk, d]`` activations, the decode program on
    ``[slots, d]``. Returns ``(prefill runs, decode runs)`` or None where
    the two sizes are equal or the trace holds no such program."""
    tr = run.get("trace")
    if tr is None:
        return None
    eng, d = run["data"]["engine"], ctx["cell"].model["n_embd"]
    if eng["prefill_chunk"] == eng["n_slots"]:
        return None
    prefill, decode = [], []
    for name, runs in tr.module_runs().items():
        texts = [op[0] for op in tr.ops_within(runs[:1])]
        if any(f"[{eng['prefill_chunk']},{d}]" in t for t in texts):
            prefill += runs
        elif any(f"[{eng['n_slots']},{d}]" in t for t in texts):
            decode += runs
    return prefill, decode


def prefill_busy_share(run, ctx):
    found = _serving_modules(run, ctx)
    if not found or not (found[0] or found[1]):
        return None
    pre = sum(e - s for s, e in found[0])
    return positive(100.0 * pre / (pre + sum(e - s for s, e in found[1])))


def paged_decode_roofline(run, ctx):
    """Mosaic page-walking calls inside the decode program: the least
    time the chip could take for the live keys and values (bytes at
    819 GB/s against FLOPs at 197 TFLOP/s: memory bounds it) / their time."""
    found, c = _serving_modules(run, ctx), mean_context(run)
    if not found or not found[1] or c is None or not ctx.get("peaks"):
        return None
    tr, model, eng = run["trace"], ctx["cell"].model, run["data"]["engine"]
    hd = model["n_embd"] // model["n_head"]
    pool = f"[{eng['n_pages'] + 1},{eng['page_len']},{model['n_head']},{hd}]"
    calls = [op for op in tr.ops_within(found[1])
             if " custom-call(" in op[0] and pool in op[0]]
    if not calls:
        return None
    ticks = run["data"]["ticks"]
    rows = sum(t[2] for t in ticks) / len(ticks) if ticks else eng["n_slots"]
    one_layer = dict(model, n_layer=1)
    least, _ = counts.roofline_seconds(
        rows * counts.attention_flops(one_layer, c),
        rows * counts.decode_kv_bytes(one_layer, c), ctx["peaks"])
    return 100.0 * least * len(calls) / sum(op[2] for op in calls)


def flash_roofline(run, ctx):
    """Flash Mosaic calls (forward, dk/dv, dq) of the traced steps: required
    causal FLOPs at 197 TFLOP/s (compute bounds them) / their device time."""
    tr = run.get("trace")
    if tr is None or not ctx.get("peaks"):
        return None
    model, d = ctx["cell"].model, run["data"]
    rows = d["batch"] // d["chips"]
    hd = model["n_embd"] // model["n_head"]
    operand = f"custom-call(bf16[{rows * model['n_head']},{d['seq']},{hd}]"
    calls = [op for op in tr.device_ops.get(min(tr.device_ops), ())
             if operand in op[0]] if tr.device_ops else []
    steps = sum(len(r) for r in tr.module_runs().values())
    if not calls or not steps:
        return None
    one_layer = dict(model, n_layer=1)
    least = 0.0
    for backward in (False, True):
        t, _ = counts.roofline_seconds(
            counts.flash_flops(one_layer, rows, d["seq"], backward),
            counts.flash_bytes(one_layer, rows, d["seq"], backward), ctx["peaks"])
        least += t
    return 100.0 * least * model["n_layer"] * steps / sum(op[2] for op in calls)
