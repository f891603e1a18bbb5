"""The comparison that decides ``correct``: numbers of the timed path held
against the plain reference, each under a limit of its own.

Training numbers (gaps of norms by the worst leaf, as a share of the
reference's norm of that leaf or of the median leaf, whichever is larger):

- ``loss_gap``    : widest |program - reference| / reference over the steps.
- ``grad_gap``    : first gradient as the optimizer got it, worst leaf.
- ``change_gap``  : the parameters' change after the followed steps, worst
  leaf, leaving out leaves whose reference gradient is under a thousandth
  of the median leaf's (they move under Adam by round-off alone).

Serving number:

- ``logit_gap``   : widest gap by which a served token's reference logit
  lies below the reference's best at that position.

Counts (``tokens_missing``, ``requests_failed``, ``out_of_vocab``,
``nonfinite``) have the limit 0.
"""
from __future__ import annotations

import math
import statistics


def worst_leaf_gap(program, reference, keep=None) -> float:
    """Worst over leaves of ``|program - reference|`` norm gap, as a share
    of ``max(reference leaf, median reference leaf)``."""
    med = statistics.median(reference)
    worst = 0.0
    for i, (a, b) in enumerate(zip(program, reference)):
        if keep is not None and not keep[i]:
            continue
        denom = max(b, med)
        gap = abs(a - b) / denom if denom > 0 else (0.0 if a == b else math.inf)
        if not math.isfinite(gap):
            return math.inf
        worst = max(worst, gap)
    return worst


def moving_leaves(ref_grad_norms):
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's: the others are left out of ``change_gap``."""
    med = statistics.median(ref_grad_norms)
    return [g >= 1e-3 * med for g in ref_grad_norms]


def train_numbers(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref``: ``losses``, ``grad_norms``, ``change_norms``."""
    n = min(len(prog["losses"]), len(ref["losses"]))
    loss_gap = max(abs(prog["losses"][i] - ref["losses"][i]) / abs(ref["losses"][i])
                   for i in range(n))
    finite = all(math.isfinite(x) for x in prog["losses"])
    return {
        "loss_gap": loss_gap if finite else math.inf,
        "grad_gap": worst_leaf_gap(prog["grad_norms"], ref["grad_norms"]),
        "change_gap": worst_leaf_gap(prog["change_norms"], ref["change_norms"],
                                     keep=moving_leaves(ref["grad_norms"])),
    }


def decide(numbers: dict, limits: dict):
    """``(correct, checks)``: every number named in ``limits`` has to be
    there and at or under its limit. ``checks`` maps each name to
    ``[value, limit]`` for the result line."""
    checks, ok = {}, True
    for name, limit in limits.items():
        if name.startswith("_"):
            continue
        value = numbers.get(name)
        good = value is not None and math.isfinite(value) and value <= limit
        ok = ok and good
        checks[name] = [value, limit]
    return ok, checks
