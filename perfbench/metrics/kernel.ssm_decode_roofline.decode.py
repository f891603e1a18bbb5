"""kernel.ssm_decode_roofline.decode: ssm_state_update calls inside jit_serve_decode_step: each updated row's float32 state read and written once, with its x, dt, B, C and y / 819 GB/s (or the operations / peak, whichever bounds) / their device time."""
from perfbench.harness import counts, readers


def read(run, ctx):
    """How many rows a step updates the ``serve.decode_dispatch`` spans
    carry (``ssm_rows``); the family's ``kernel_work`` takes a step's mean
    and gives a call's work (one call a Mamba layer). The steps are those
    of the traced seconds, which the calls come from."""
    ops = readers.kernel_calls(run, ("ssm_state_update",),
                               readers.DECODE_PROGRAM).get("ssm_state_update")
    spans = readers.data(run, "spans") or ()
    if not ops or not spans or not ctx.get("peaks"):
        return None
    traced_until = min(s.t_start_s for s in spans) + run.get("trace_window_s", 0.0)
    rows = [s.attrs["ssm_rows"] for s in spans
            if s.name == "serve.decode_dispatch" and "ssm_rows" in s.attrs
            and s.t_start_s <= traced_until]
    if not rows:
        return None
    least = counts.roofline_seconds(*readers.family(ctx).kernel_work(
        "ssm_state_update", ctx["cell"].model, {"ssm_rows": sum(rows) / len(rows)}),
        ctx["peaks"])[0]
    return 100.0 * len(ops) * least / sum(op[2] for op in ops)
