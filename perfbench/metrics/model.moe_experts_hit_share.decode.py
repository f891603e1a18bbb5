"""model.moe_experts_hit_share.decode: Held experts with a pair in a decode step / held experts (over the expert layers), from the increase of the counters serve_moe_experts_hit_total and serve_moe_steps_total over the window."""
from perfbench.harness import readers


def read(run, ctx):
    """The counters' readings ride the ``serve.tick_metrics`` spans."""
    fam = readers.family(ctx)
    seen = [s.attrs for s in readers.data(run, "spans") or ()
            if s.name == "serve.tick_metrics" and "moe_steps" in s.attrs]
    if len(seen) < 2 or not hasattr(fam, "held_expert_slots"):
        return None
    steps = seen[-1]["moe_steps"] - seen[0]["moe_steps"]
    hit = seen[-1]["moe_experts_hit"] - seen[0]["moe_experts_hit"]
    return readers.share(hit / steps, fam.held_expert_slots(ctx["cell"].model)) if steps else None
