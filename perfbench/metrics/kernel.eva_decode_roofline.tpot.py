"""kernel.eva_decode_roofline.tpot: eva_paged_attention calls inside jit_serve_decode_step: bytes of the entries the decoding rows see / 819 GB/s (or their FLOPs / peak, whichever bounds) / their device time."""
from perfbench.harness import counts, readers


def read(run, ctx):
    """``entries(p)`` is not linear in the context, so its mean is taken
    over the served bytes of the finished requests here; the rows are those
    the decode step really carried (``serve.decode_step`` spans, ``active``)."""
    fam, model = readers.family(ctx), ctx["cell"].model
    calls = readers.kernel_calls(run, ("eva_paged_attention",), readers.DECODE_PROGRAM)
    ops = calls.get("eva_paged_attention")
    active = [s.attrs["active"] for s in readers.data(run, "spans") or ()
              if s.name == "serve.decode_step" and "active" in s.attrs]
    if not ops or not active or not ctx.get("peaks") or not hasattr(fam, "served_entries"):
        return None
    entries = fam.served_entries(model, readers.data(run, "finished"))
    if entries is None:
        return None
    facts = {"rows": sum(active) / len(active), "entries": entries}
    least = counts.roofline_seconds(
        *fam.kernel_work("eva_paged_attention", model, facts), ctx["peaks"])[0]
    return 100.0 * len(ops) * least / sum(op[2] for op in ops)
