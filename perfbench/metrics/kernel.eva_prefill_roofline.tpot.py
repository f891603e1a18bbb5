"""kernel.eva_prefill_roofline.tpot: eva_paged_attention calls inside jit_serve_prefill_chunk: FLOPs of each chunk's queries over the entries they see / peak (or the bytes of the entries read / 819 GB/s, whichever bounds) / their device time."""
from perfbench.harness import counts, readers, runtime


def read(run, ctx):
    """The chunks are those the window's ``serve.prefill_chunk`` spans name
    (``start``, ``prompt_len``); a chunk's queries past the prompt's end are
    padding and require nothing."""
    fam, model = readers.family(ctx), ctx["cell"].model
    calls = readers.kernel_calls(run, ("eva_paged_attention",), readers.PREFILL_PROGRAM)
    ops = calls.get("eva_paged_attention")
    chunks = [(s.attrs["start"], s.attrs["prompt_len"])
              for s in readers.data(run, "spans") or ()
              if s.name == "serve.prefill_chunk" and "start" in s.attrs]
    if not ops or not chunks or not ctx.get("peaks") or not hasattr(fam, "entries_sum"):
        return None
    size = run["data"]["engine"]["prefill_chunk"]
    least = []
    for start, prompt in chunks:
        n = min(start + size, prompt) - start
        seen = fam.entries_sum(model, start + n) - fam.entries_sum(model, start)
        facts = {"rows": 1, "queries": n, "entries": seen / n,
                 "entries_read": fam.entries(model, start + n - 1)}
        least.append(counts.roofline_seconds(
            *fam.kernel_work("eva_paged_attention", model, facts), ctx["peaks"])[0])
    return 100.0 * len(ops) * runtime.median(least) / sum(op[2] for op in ops)
