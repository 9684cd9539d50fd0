"""The comparison that decides ``correct``: what the timed path produced
in its first three steps against the plain reference on the same inputs.

Four numbers; a cell compares those that its ``limits/<workload>.json``
lists, each against a limit of its own:

  first_loss_gap  the relative gap of the first round's training loss;
  loss_gap    the largest relative gap between a round's training loss and
              the reference's, over every round of the three steps;
  grad_gap    the corrections ``c`` after step 1 (each client's gradient
              less the clients' mean, as the state holds them): per leaf,
              the gap between the program's norm and the reference's;
  change_gap  the change of ``x_bar`` after three steps: per leaf, the gap
              between the program's norm and the reference's.

A leaf's gap is measured against the larger of the reference's norm of that
leaf and of the median leaf, and the number is the worst leaf's.  Leaves
whose first mean gradient in the reference is under a thousandth of the
median leaf's are left out of both: they move by round-off alone.

Where the program's own supplier draws the batches, the reference replays
the harness's draw of them, and one exact number joins these:

  feed_rows_wrong  the examples (rows of one client's one local step) that
                   the supplier served in the three steps and that differ
                   from the harness's draw; its limit is 0.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("first_loss_gap", "loss_gap", "grad_gap", "change_gap")
EXACT = ("feed_rows_wrong",)
NEGLIGIBLE_GRAD = 1e-3


def _leaf_gap(prog, ref, keep) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    med = float(np.median(ref[keep]))
    denom = np.maximum(ref[keep], med)
    return float(np.max(np.abs(prog[keep] - ref[keep]) / denom))


def numbers(prog: dict, ref: dict) -> dict:
    """The numbers from the program's and the reference's readings
    (``losses``, ``c_norms``, ``change_norms``; the reference's
    ``grad_norms`` picks the leaves)."""
    g = np.asarray(ref["grad_norms"], np.float64)
    keep = g >= NEGLIGIBLE_GRAD * np.median(g)
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    if lp.shape != lr.shape:
        raise ValueError(f"{lp.size} program losses against {lr.size} "
                         "reference losses")
    gaps = np.abs(lp - lr) / np.abs(lr)
    return {
        "first_loss_gap": float(gaps[0]),
        "loss_gap": float(np.max(gaps)),
        "grad_gap": _leaf_gap(prog["c_norms"], ref["c_norms"], keep),
        "change_gap": _leaf_gap(prog["change_norms"], ref["change_norms"],
                                keep),
        "leaves_left_out": int(np.count_nonzero(~keep)),
    }


def rows_wrong(served: list, drawn: list) -> int:
    """Examples of the rounds ``served`` (batch pytrees, leaves ``(n, tau,
    b, ...)``) that differ in any key from ``drawn``, or are missing."""
    wrong = 0
    for i, want in enumerate(drawn):
        got = served[i] if i < len(served) else None
        n = int(np.prod(next(iter(want.values())).shape[:3]))
        if got is None or set(got) != set(want):
            wrong += n
            continue
        bad = np.zeros(n, bool)
        for k, w in want.items():
            g = np.asarray(got[k])
            if g.shape != w.shape:
                bad[:] = True
                break
            bad |= np.any((g != w).reshape(n, -1), axis=1)
        wrong += int(np.count_nonzero(bad))
    return wrong


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(all compared numbers within their limits, {name: {"value",
    "limit"}}), for the numbers ``limits["numbers"]`` lists: a cell
    compares those that separate its sound runs from its control and
    faults.  A number that is not finite fails."""
    out, ok = {}, True
    for name, spec in limits["numbers"].items():
        v, lim = nums[name], float(spec["limit"])
        out[name] = {"value": v, "limit": lim}
        ok = ok and bool(np.isfinite(v)) and v <= lim
    return ok, out
