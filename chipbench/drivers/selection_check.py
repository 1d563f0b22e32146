"""The checks of one CRAIG selection, shared by the drivers whose window
runs refreshes: the float64 reference's reading of the picks, of γ and of
the reported coverage (``chipbench/reference/fl.py``), and the exact check
of the coreset's mass."""
from __future__ import annotations

import numpy as np

from chipbench import harness

__all__ = ["NUMBERS", "selection_numbers", "control_numbers", "mass_gap"]

NUMBERS = ("greedy_gap", "gamma_gap", "cov_gap")


def _fl():
    return harness.load_module(harness.BENCH_DIR / "reference" / "fl.py")


def _inputs(call):
    feats, init, sel = call
    r0 = 0 if init is None else len(init)
    return np.asarray(feats, np.float32), r0, sel


def selection_numbers(calls) -> dict:
    """``greedy_gap``, ``gamma_gap`` and ``cov_gap`` (``fl.compare``) of the
    selections ``calls`` (``SelectionTap`` records), the worst of them."""
    fl, out = _fl(), dict.fromkeys(NUMBERS, 0.0)
    for call in calls:
        x, r0, sel = _inputs(call)
        nums = fl.compare(x, sel.indices, sel.weights, sel.coverage, r0)
        out = {k: max(out[k], nums[k]) for k in out}
    return out


def control_numbers(calls, mode: str) -> dict:
    """The same numbers with the reference at precision ``mode`` in the
    program's place, on the same features and warm-start prefixes."""
    fl, out = _fl(), dict.fromkeys(NUMBERS, 0.0)
    for call in calls:
        x, r0, sel = _inputs(call)
        init = np.asarray(sel.indices[:r0], np.int64)
        nums = fl.control(x, len(sel.indices), init, mode)
        out = {k: max(out[k], nums[k]) for k in out}
    return out


def mass_gap(indices, weights, n: int, k: int) -> float:
    """``|Σγ - n|`` plus repeated indices plus the distance of the size from
    the budget: 0 for a coreset that covers the pool, exactly."""
    idx = np.asarray(indices, np.int64)
    w = np.asarray(weights, np.float64)
    return float(abs(w.sum() - n) + (idx.size - np.unique(idx).size)
                 + abs(idx.size - k))
