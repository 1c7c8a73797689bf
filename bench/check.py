"""The comparison that decides ``correct`` for a training cell.

The program's first window, driven from the seed by the window's own
call, is held against the plain reference over the same steps
(``bench.reference``). The numbers compared, each against the limit the
cell's file under ``bench/limits/`` gives it:

* ``loss``: the largest relative gap of a step's loss;
* ``update``: the worst leaf's gap between the norms of the params'
  change over the window, |‖Δw_prog‖ - ‖Δw_ref‖|, over the larger of
  that leaf's ‖Δw_ref‖ and the median leaf's;
* ``momentum``: the same for the momentum after the window, which holds
  every step's gradient as the optimizer got it;
* under CSC, ``hg``: the same for the historical gradients, and
  ``selection``: the share of the last step's selected chunks that one
  side selected and the other did not;
* on more than one chip, ``replicas``: the worst leaf's spread of the
  data-parallel replicas of the params, over that leaf's ‖Δw_ref‖
  (replicas that never exchanged gradients drift apart).

Leaves whose first gradient in the reference is under a thousandth of
the median leaf's move by round-off alone and are left out of the norm
gaps.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

EXCLUDE_BELOW = 1e-3


def _gap(prog: List[float], ref: List[float], keep: List[bool]) -> float:
    ref_a = np.asarray(ref, np.float64)
    prog_a = np.asarray(prog, np.float64)
    kept = np.asarray(keep)
    if not kept.any():
        return math.nan
    floor = float(np.median(ref_a[kept]))
    denom = np.maximum(ref_a, floor)
    diff = np.abs(prog_a - ref_a)
    # A leaf neither side moved reads 0; one only the program moved, inf.
    gaps = np.where(denom > 0, diff / np.where(denom > 0, denom, 1.0),
                    np.where(diff > 0, np.inf, 0.0))
    return float(np.max(np.where(kept, gaps, 0.0)))


def numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The compared numbers of one run (see the module docstring)."""
    if prog["names"] != ref["names"]:
        raise ValueError("program and reference leaves differ: "
                         f"{prog['names']} vs {ref['names']}")
    g0 = np.asarray(ref["grad0"], np.float64)
    keep = list(g0 >= EXCLUDE_BELOW * float(np.median(g0)))
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    out = {
        "loss": float(np.max(np.abs(lp - lr) / np.abs(lr)))
        if lp.shape == lr.shape else math.inf,
        "update": _gap(prog["update"], ref["update"], keep),
        "momentum": _gap(prog["momentum"], ref["momentum"], keep),
    }
    if "hg" in ref:
        out["hg"] = _gap(prog["hg"], ref["hg"], keep)
        sel_p = np.asarray(prog["selected"], bool)
        sel_r = np.asarray(ref["selected"], bool)
        out["selection"] = float(np.sum(sel_p != sel_r)
                                 / max(int(sel_r.sum()), 1))
    if "replicas" in prog:
        upd = np.asarray(ref["update"], np.float64)
        spread = np.asarray(prog["replicas"], np.float64)
        out["replicas"] = float(np.max(np.where(
            keep, spread / np.maximum(upd, 1e-30), 0.0)))
    return out


def judge(nums: Dict[str, float],
          limits: Dict[str, float]) -> Tuple[bool, List[Tuple]]:
    """(correct, [(name, value, limit)]). A number that is not finite, or
    that the cell's limits do not name, fails. A number whose limit is
    null is printed and not compared: no upper reading was found for it
    (see PERF.md)."""
    rows, ok = [], True
    for name, value in nums.items():
        if name in limits and limits[name] is None:
            rows.append((name, value, None))
            continue
        limit = limits.get(name)
        good = limit is not None and math.isfinite(value) \
            and value <= limit
        ok = ok and good
        rows.append((name, value, limit))
    return ok, rows
