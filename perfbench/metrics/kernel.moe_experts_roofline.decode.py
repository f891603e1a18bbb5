"""kernel.moe_experts_roofline.decode: gmm calls (the experts' grouped product) inside jit_serve_decode_step: the weights of the experts hit, once, and the pairs' rows / 819 GB/s (or the pairs' FLOPs / peak, whichever bounds) / their device time."""
from perfbench.harness import counts, readers


def read(run, ctx):
    """What a step hit is the device's to know: the ``serve.decode_step``
    spans carry it (``moe_pairs``, ``moe_experts_hit``). The family's
    ``kernel_work`` takes a step's facts and gives a call's work; the
    steps are those of the traced seconds, which the calls come from."""
    ops = readers.kernel_calls(run, ("gmm",), readers.DECODE_PROGRAM).get("gmm")
    spans = readers.data(run, "spans") or ()
    if not ops or not spans or not ctx.get("peaks"):
        return None
    traced_until = min(s.t_start_s for s in spans) + run.get("trace_window_s", 0.0)
    steps = [s.attrs for s in spans
             if s.name == "serve.decode_step" and "moe_experts_hit" in s.attrs
             and s.t_start_s <= traced_until]
    if not steps:
        return None
    facts = {"pairs": sum(a["moe_pairs"] for a in steps) / len(steps),
             "experts_hit": sum(a["moe_experts_hit"] for a in steps) / len(steps)}
    least = counts.roofline_seconds(*readers.family(ctx).kernel_work(
        "gmm", ctx["cell"].model, facts), ctx["peaks"])[0]
    return 100.0 * len(ops) * least / sum(op[2] for op in ops)
