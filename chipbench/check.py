"""The comparison that decides ``correct``.

The program's first three training steps (driven through ``Trainer.step``
by the run's own feed, at the timed sizes) are compared with the plain
reference (``reference.py``) on the same weights and batches:

- ``loss_gap``: the largest relative gap of a step's loss;
- ``grad_norm_gap``: the first gradient as AdamW received it (its first
  moment over 1 - b1), by the worst leaf: |norm(program) - norm(reference)|
  over the larger of the reference's norm of that leaf and of the median
  leaf;
- ``update_norm_gap``: the same for each leaf's change over the three
  steps.  Leaves whose reference gradient is under a thousandth of the
  median leaf's are left out: AdamW moves those by rounding alone.

Each number has the limit in the cell's limits file; ``PERF.md`` gives the
readings each limit was set from.
"""
from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "grad_norm_gap", "update_norm_gap")


def _worst_leaf(got: dict, want: dict, keep) -> float:
    floor = statistics.median(want.values())
    gaps = [abs(got[n] - want[n]) / max(want[n], floor)
            for n in want if keep(n)]
    return max(gaps) if gaps else math.inf


def gaps(prog, ref) -> dict[str, float]:
    """The three numbers compared, from the program's and the reference's
    ``reference.Readings``; a missing or non-finite reading gives inf."""
    try:
        loss = max(abs(p - r) / abs(r)
                   for p, r in zip(prog.losses, ref.losses, strict=True))
        grad = _worst_leaf(prog.grad_norms, ref.grad_norms, lambda n: True)
        tiny = 1e-3 * statistics.median(ref.grad_norms.values())
        upd = _worst_leaf(prog.change_norms, ref.change_norms,
                          lambda n: ref.grad_norms[n] >= tiny)
    except (KeyError, ValueError, ZeroDivisionError):
        return {n: math.inf for n in NUMBERS}
    out = {"loss_gap": loss, "grad_norm_gap": grad, "update_norm_gap": upd}
    return {n: (v if math.isfinite(v) else math.inf) for n, v in out.items()}


def verdict(numbers: dict[str, float], limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): correct when every number is
    at or under its limit."""
    shown = {n: {"value": numbers[n], "limit": float(limits[n])}
             for n in NUMBERS}
    return all(v["value"] <= v["limit"] for v in shown.values()), shown
