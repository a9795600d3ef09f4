"""The comparison that decides ``correct`` for a training cell.

Two numbers, each against its own limit (``chipbench/limits/<cell>.json``):

- ``grad``: the worst leaf's gap between the norms of the first gradient as
  the optimizer got it;
- ``change``: the worst leaf's gap between the norms of the weights' change
  over those steps.

A leaf's gap is |program norm - reference norm| over the larger of the
reference's norm of that leaf and of the median leaf (a per-layer slice of
a stacked weight is a leaf). Leaves whose reference gradient is below a
thousandth of the median leaf's move by round-off alone and are left out
of ``change``.

Each step's loss gap is reported (``loss`` the largest, ``loss_steps``
each) and not compared: on the chip, sound runs of the one-chip cell read
about 1e-3 on every seed and the float8 control only 2-3 times that, so no
limit could separate them. A likely cause, not yet shown: Adam's first
update moves every weight by about the learning rate in the direction of
its gradient's sign, so bfloat16 rounding of near-zero gradients (the
output rows of tokens the batch never targets) flips whole rows of the
tied embedding, and the later losses move about as far as a lower
precision moves them.
"""
from __future__ import annotations

import math
import statistics

QUIET_GRAD = 1e-3
NUMBERS = ("grad", "change")


def _worst_leaf(prog: dict[str, float], ref: dict[str, float], skip=()) -> float:
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))[:6]}")
    floor = statistics.median(ref.values())
    worst = 0.0
    for k, r in ref.items():
        if k in skip:
            continue
        gap = abs(prog[k] - r) / max(r, floor)
        if not math.isfinite(gap):
            return math.inf
        worst = max(worst, gap)
    return worst


def gaps(prog: dict, ref: dict) -> dict[str, float]:
    """The compared numbers, the largest and every step's loss gap; inf
    where the program gave no finite value."""
    steps = [abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"])]
    steps = [g if math.isfinite(g) else math.inf for g in steps]
    med = statistics.median(ref["grad"].values())
    quiet = {k for k, v in ref["grad"].items() if v < QUIET_GRAD * med}
    return {"loss": max(steps),
            "grad": _worst_leaf(prog["grad"], ref["grad"]),
            "change": _worst_leaf(prog["change"], ref["change"], skip=quiet),
            "loss_steps": steps}


def verdict(g: dict[str, float], limits: dict[str, float]) -> bool:
    return all(g[k] <= limits[k] for k in NUMBERS)


def report(g: dict[str, float], limits: dict[str, float]) -> dict:
    """{"loss": {"value": ..., "limit": ...}, ...} for the result line."""
    return {k: {"value": g[k], "limit": limits[k]} for k in NUMBERS}
