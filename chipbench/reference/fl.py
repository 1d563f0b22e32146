"""Plain float64 reference of CRAIG's facility-location selection (l2).

Written from the CRAIG paper (Mirzasoleiman et al., ICML 2020, Alg. 1): the
similarity of rows ``i`` and ``e`` is ``d_max - ‖x_i - x_e‖``, with
``d_max = 2 max_i ‖x_i‖`` (the auxiliary element every row starts covered
by), the greedy adds the row of largest marginal gain
``Σ_i max(0, s_ie - max_{j∈S} s_ij)``, and each selected row's weight γ is
the number of rows nearest to it.  It imports nothing of the program.

Distances come from ``‖x‖² + ‖e‖² - 2 x·e`` in float64 with the dot product
at a stated precision: ``"f64"`` for the reference; ``"high"`` rounds each
operand to a bfloat16 pair (``hi + lo``) and keeps the three products a TPU
makes at ``Precision.HIGH``; ``"bf16"`` keeps one bfloat16 product
(``Precision.DEFAULT``).  The last two are controls only.
"""
from __future__ import annotations

import numpy as np

__all__ = ["distances", "d_max", "gain_gaps", "greedy", "assign", "compare",
           "control"]


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float64 values to the nearest bfloat16 (ties to even)."""
    b = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32).astype(np.float64)


def _dot(a: np.ndarray, b: np.ndarray, mode: str) -> np.ndarray:
    if mode == "f64":
        return a @ b.T
    ah, bh = _bf16(a), _bf16(b)
    if mode == "bf16":
        return ah @ bh.T
    if mode == "high":
        al, bl = _bf16(a - ah), _bf16(b - bh)
        return ah @ bh.T + ah @ bl.T + al @ bh.T
    raise ValueError(f"unknown dot mode {mode!r}")


def distances(x, mode: str = "f64") -> np.ndarray:
    """(n, n) l2 distances between the rows of ``x``."""
    x = np.asarray(x, np.float64)
    sq = np.einsum("nd,nd->n", x, x)
    d2 = sq[:, None] + sq[None, :] - 2.0 * _dot(x, x, mode)
    return np.sqrt(np.maximum(d2, 0.0))


def d_max(x) -> float:
    x = np.asarray(x, np.float64)
    return 2.0 * float(np.sqrt(np.max(np.einsum("nd,nd->n", x, x))))


def _cover(sim: np.ndarray, chosen) -> np.ndarray:
    cur = np.zeros(sim.shape[0])
    for e in chosen:
        cur = np.maximum(cur, sim[:, e])
    return cur


def gain_gaps(dist: np.ndarray, dm: float, picks, r0: int) -> np.ndarray:
    """For each pick after the first ``r0``: how far its marginal gain falls
    short of the best gain given the picks before it, as a share of that
    best gain (0 for a true greedy pick; 1 for a pick made twice)."""
    sim = dm - dist
    picks = [int(p) for p in picks]
    cur = _cover(sim, picks[:r0])
    chosen = np.zeros(sim.shape[0], bool)
    chosen[picks[:r0]] = True
    gaps = []
    for e in picks[r0:]:
        gains = np.maximum(sim - cur[:, None], 0.0).sum(axis=0)
        best = float(np.max(np.where(chosen, -np.inf, gains)))
        gaps.append(1.0 if chosen[e] else (best - gains[e]) / max(best, 1e-300))
        chosen[e] = True
        cur = np.maximum(cur, sim[:, e])
    return np.asarray(gaps)


def greedy(dist: np.ndarray, dm: float, k: int, init=()) -> np.ndarray:
    """The greedy's picks (lowest index among equal gains), continued from
    the prefix ``init``."""
    sim = dm - dist
    picks = [int(p) for p in init]
    cur = _cover(sim, picks)
    chosen = np.zeros(sim.shape[0], bool)
    chosen[picks] = True
    while len(picks) < k:
        gains = np.maximum(sim - cur[:, None], 0.0).sum(axis=0)
        e = int(np.argmax(np.where(chosen, -np.inf, gains)))
        picks.append(e)
        chosen[e] = True
        cur = np.maximum(cur, sim[:, e])
    return np.asarray(picks)


def assign(dist: np.ndarray, picks) -> tuple[np.ndarray, float]:
    """γ (the number of rows nearest to each pick, in pick order) and the
    coverage residual ``L(S) = Σ_i min_{e∈S} d_ie``."""
    sub = dist[:, np.asarray(picks, np.int64)]
    gamma = np.bincount(np.argmin(sub, axis=1), minlength=sub.shape[1])
    return gamma.astype(np.float64), float(np.sum(np.min(sub, axis=1)))


def compare(x, picks, gamma, coverage: float, r0: int) -> dict:
    """The selection's compared numbers against the float64 reference:

    * ``greedy_gap`` — the worst pick after the warm prefix (``gain_gaps``);
    * ``gamma_gap`` — the share of rows whose γ the selection gives to
      another pick than the nearest one: ``Σ |γ - γ_ref| / 2n``;
    * ``cov_gap`` — the relative gap of the coverage residual ``L(S)`` the
      selection reports from the reference's ``L(S)`` of the same picks.
    """
    x = np.asarray(x, np.float64)
    ref = distances(x, "f64")
    gaps = gain_gaps(ref, d_max(x), picks, r0)
    g_ref, cov_ref = assign(ref, picks)
    g = np.asarray(gamma, np.float64)
    return {
        "greedy_gap": float(gaps.max()) if gaps.size else 0.0,
        "gamma_gap": float(np.abs(g - g_ref).sum() / (2.0 * x.shape[0])),
        "cov_gap": abs(float(coverage) - cov_ref) / cov_ref,
    }


def control(x, k: int, init, mode: str) -> dict:
    """The reference at a lower precision ``mode`` in the program's place:
    its picks (continued from the same prefix) and its γ, read against the
    float64 reference like the program's."""
    x = np.asarray(x, np.float64)
    low = distances(x, mode)
    picks = greedy(low, d_max(x), k, init)
    gamma, coverage = assign(low, picks)
    return compare(x, picks, gamma, coverage, len(init))
