"""Sieve-streaming facility-location engine (DESIGN.md §10).

The batch engines re-sweep the whole pool per refresh; under continuous
ingestion (ROADMAP north-star) that cost grows with the pool while the
information per refresh does not.  Sieve-streaming (Badanidiyuru et al.,
KDD'14) maintains a *geometric grid of threshold sieves* instead: for each
guess ``v = (1+eps)^j`` of OPT, a sieve greedily admits an arriving element
when its marginal gain clears ``(v/2 − f(S_v)) / (k − |S_v|)``.  One sieve's
guess lands within (1+eps) of OPT and its set achieves ``(1/2 − O(eps))·OPT``
— a one-pass, O(Δn·k)-per-delta guarantee with no re-sweep of prior data.

Adaptation to CRAIG's facility location: past points cannot be revisited, so
the objective is tracked as the running per-point mean coverage — each sieve
accumulates ``Σ_i max_{j∈S_v} s_ij`` over the deltas it has seen (``fval``),
marginal gains are estimated batch-locally on the arriving delta (CREST,
arXiv:2306.01244: selection over pool subsets arriving over time preserves
the data-efficiency guarantees when deltas are representative samples), and
the max-singleton estimate ``m`` that anchors the grid is the running max
*mean* similarity — scale-stable as the stream grows.  When ``m`` rises, the
live window of OPT guesses ``[m, 2km]`` shifts: each sieve slot holds an
absolute level and re-anchors by jumping a multiple of L levels (retiring its
selections), so the L slots always hold L consecutive levels of the current
window — a circular buffer over the geometric grid, O(L) per element.

With a single delta equal to the full pool, the estimates are exact and
``select`` *is* textbook sieve-streaming, hence the property-test gate
``F(S) ≥ (1/2 − eps)·F(greedy)`` (tests/test_selection_properties.py).

Three surfaces:

  * ``init_streaming_state`` / ``ingest_delta`` / ``streaming_result`` — the
    functional core.  ``StreamingState`` is an arrays-only NamedTuple (a
    pytree): ``ingest_delta`` is jit-compiled once per delta shape, and the
    state serializes losslessly for checkpoints (``StreamingSelector``).
  * ``StreamingEngine`` (``engine='streaming'``) — the registry plugin: a
    one-shot ``select`` (init → single-delta ingest → finalize) behind the
    common protocol; not exact, matrix-free, jit-safe.
  * ``StreamingSelector`` — the stateful host wrapper the coreset service
    builds on: sequential ``ingest(delta)`` calls, per-class stratified
    budgets (paper §5) apportioned at ``result`` time from observed class
    arrival counts, and a JSON-able ``state_dict`` that resumes
    bit-identically mid-stream.

Finalization (``streaming_result``) maps the best sieve back to a full
``FLResult``: it replays the warm prefix, takes the sieve's picks in
admission order, backfills any remaining budget with worst-covered points
(farthest-point traversal), and computes γ weights / residual coverage
against the pool — the only step that touches all n rows, and the only one
whose cost scales with the pool rather than the delta.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import ClassVar, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engines.base import (
    Capabilities,
    EngineConfig,
    FLResult,
    SelectionEngine,
    _replay_prefix,
    cosine_residual_coverage,
    normalize_for_metric,
)
from repro.core.engines.registry import register_engine
from repro.kernels import ops as kops

__all__ = [
    "LVL_UNSET",
    "StreamingConfig",
    "StreamingEngine",
    "StreamingSelector",
    "StreamingState",
    "init_streaming_state",
    "ingest_delta",
    "num_sieves",
    "streaming_result",
    "streaming_result_blocked",
]

# Sentinel level for a sieve slot that has never been anchored (no element
# seen yet).  Any real absolute level ``floor(log m / log(1+eps))`` is far
# above it, so the first element cold-starts the whole grid.
LVL_UNSET = -(2**30)


class StreamingState(NamedTuple):
    """Serializable sieve-streaming state — arrays only, hence a pytree.

    Static meta (budget, eps) is *not* carried here: it is baked into the
    array shapes (L, k) at :func:`init_streaming_state` time and travels
    alongside in ``StreamingSelector.state_dict`` / engine configs.

    Attributes:
      n_seen: () int32 — points ingested so far.
      d_max: () float32 — similarity offset ``2·max‖x‖ + 1e-6``, frozen at
        the first ingest so sieve values stay comparable across deltas
        (later similarities clip at 0).
      m: () float32 — running max singleton *mean* similarity (grid anchor).
      lvl: (L,) int32 — absolute threshold level per sieve slot
        (``v = (1+eps)^lvl``); ``LVL_UNSET`` before the first element.
      count: (L,) int32 — elements admitted per sieve.
      fval: (L,) float32 — Σ coverage of past delta points at their ingest
        time, per sieve (the running objective estimate, in sum units).
      fval_pre: () float32 — same accumulator for the warm prefix alone;
        the O(1) reset value when a sieve retires.
      sel_idx: (L, k) int32 — admitted indices per sieve (-1 = empty slot).
      sel_feats: (L, k, d) float32 — their features (past points are gone;
        the sieves keep the only copy).
      pre_idx: (r0,) int32 — warm-start prefix indices (excluded from sieve
        admission; replayed at finalize).
      pre_feats: (r0, d) float32 — prefix features.
    """

    n_seen: jax.Array
    d_max: jax.Array
    m: jax.Array
    lvl: jax.Array
    count: jax.Array
    fval: jax.Array
    fval_pre: jax.Array
    sel_idx: jax.Array
    sel_feats: jax.Array
    pre_idx: jax.Array
    pre_feats: jax.Array

    @property
    def capacity(self) -> int:
        """k — sieve capacity (budget minus warm-prefix length)."""
        return self.sel_idx.shape[1]

    @property
    def num_levels(self) -> int:
        """L — number of sieve slots."""
        return self.lvl.shape[0]


def num_sieves(budget: int, eps: float, levels: int = 0) -> int:
    """Sieve-count default: span the OPT window ``[m, 2·budget·m]``.

    The geometric grid needs ``log(2k)/log(1+eps)`` levels to cover the
    window; capped at 64 (OPT sits far below ``k·m`` on real pools) and
    floored at 4.  ``levels > 0`` overrides.
    """
    if levels > 0:
        return int(levels)
    k = max(int(budget), 2)
    want = math.ceil(math.log(2.0 * k) / math.log1p(eps)) + 1
    return max(4, min(64, want))


def init_streaming_state(
    budget: int,
    dim: int,
    *,
    eps: float = 0.15,
    levels: int = 0,
    init_selected=None,
    init_feats=None,
) -> StreamingState:
    """Empty sieve grid for ``budget`` selections over ``dim``-d features.

    ``init_selected``/``init_feats`` seed a warm-start prefix: those
    elements are treated as already selected (every sieve's coverage starts
    from theirs; they are excluded from admission) and are replayed first at
    :func:`streaming_result`, preserving the warm-start-prefix contract of
    the batch engines.
    """
    budget = int(budget)
    if budget < 1:
        raise ValueError(f"budget must be ≥ 1, got {budget}")
    if init_selected is None:
        pre_idx = jnp.zeros((0,), jnp.int32)
        pre_feats = jnp.zeros((0, dim), jnp.float32)
    else:
        pre_idx = jnp.asarray(init_selected, jnp.int32).ravel()
        if init_feats is None:
            raise ValueError("init_selected needs init_feats (past rows are gone)")
        pre_feats = jnp.asarray(init_feats, jnp.float32).reshape(-1, dim)
        if pre_feats.shape[0] != pre_idx.shape[0]:
            raise ValueError(
                f"init_feats rows {pre_feats.shape[0]} != "
                f"init_selected length {pre_idx.shape[0]}"
            )
        if pre_idx.shape[0] > budget:
            raise ValueError(
                f"init_selected has {pre_idx.shape[0]} elements > budget {budget}"
            )
    k = budget - pre_idx.shape[0]
    L = num_sieves(budget, eps, levels)
    return StreamingState(
        n_seen=jnp.zeros((), jnp.int32),
        d_max=jnp.zeros((), jnp.float32),
        m=jnp.zeros((), jnp.float32),
        lvl=jnp.full((L,), LVL_UNSET, jnp.int32),
        count=jnp.zeros((L,), jnp.int32),
        fval=jnp.zeros((L,), jnp.float32),
        fval_pre=jnp.zeros((), jnp.float32),
        sel_idx=jnp.full((L, k), -1, jnp.int32),
        sel_feats=jnp.zeros((L, k, dim), jnp.float32),
        pre_idx=pre_idx,
        pre_feats=pre_feats,
    )


def _sim_to(feats: jax.Array, sq: jax.Array, x: jax.Array, d_max) -> jax.Array:
    """(Δn,) clipped similarity of every delta point to one element x."""
    d2 = sq + jnp.sum(x * x) - 2.0 * (feats @ x)
    return jnp.maximum(d_max - jnp.sqrt(jnp.maximum(d2, 0.0)), 0.0)


def _ingest_delta(state: StreamingState, feats, idx, eps) -> StreamingState:
    """One-pass sieve update over a megabatch delta (jit-compiled).

    Work is O(Δn·(Δn + L)·d′) with d′ the feature dim — independent of
    ``n_seen``: prior data is never revisited.
    """
    feats = jnp.asarray(feats, jnp.float32)
    dn, dim = feats.shape
    L, k = state.num_levels, state.capacity
    r0 = state.pre_idx.shape[0]
    idx = jnp.asarray(idx, jnp.int32)
    sq = jnp.sum(feats * feats, axis=-1)

    # freeze the similarity offset at first ingest (later sims clip at 0)
    d_max = jnp.where(
        state.n_seen == 0, 2.0 * jnp.sqrt(jnp.max(sq)) + 1e-6, state.d_max
    )

    # prefix coverage of the delta (the floor every sieve shares)
    if r0 > 0:
        psq = jnp.sum(state.pre_feats * state.pre_feats, axis=-1)
        d2p = sq[:, None] + psq[None, :] - 2.0 * (feats @ state.pre_feats.T)
        simp = jnp.maximum(d_max - jnp.sqrt(jnp.maximum(d2p, 0.0)), 0.0)
        cov_pre = jnp.max(simp, axis=1)
        is_pre = jnp.any(idx[:, None] == state.pre_idx[None, :], axis=1)
    else:
        cov_pre = jnp.zeros((dn,), jnp.float32)
        is_pre = jnp.zeros((dn,), bool)
    pre_sum = jnp.sum(cov_pre)

    if k == 0:  # budget == prefix: nothing to sieve, just account coverage
        return state._replace(
            n_seen=state.n_seen + dn,
            d_max=d_max,
            fval=state.fval + pre_sum,
            fval_pre=state.fval_pre + pre_sum,
        )

    # coverage of the delta by each sieve's existing selections
    ssq = jnp.sum(state.sel_feats * state.sel_feats, axis=-1)  # (L, k)
    dots = jnp.einsum("nd,lkd->lnk", feats, state.sel_feats)
    d2s = sq[None, :, None] + ssq[:, None, :] - 2.0 * dots
    sims = jnp.maximum(d_max - jnp.sqrt(jnp.maximum(d2s, 0.0)), 0.0)
    valid = jnp.arange(k)[None, None, :] < state.count[:, None, None]
    cov0 = jnp.max(jnp.where(valid, sims, 0.0), axis=2)  # (L, Δn)
    cov0 = jnp.maximum(cov0, cov_pre[None, :])

    n_seen_f = state.n_seen.astype(jnp.float32)
    log1p_eps = math.log1p(float(eps))
    slot_arange = jnp.arange(L, dtype=jnp.int32)

    # The scan carries only the O(L·Δn) cover rows and O(L) scalars; the
    # big (L, k[, d]) selection arrays are never read inside the body, so
    # they are reconstructed post-scan from the accept/retire history —
    # carrying them would copy L·k·d floats per element.
    def step(carry, xs):
        m, lvl, count, fval, covsum, cov = carry
        x, ispre = xs
        col = _sim_to(feats, sq, x, d_max)  # (Δn,)

        # grid anchor: running max singleton mean; re-anchor the window
        m = jnp.maximum(m, jnp.mean(col))
        j_lo = jnp.floor(jnp.log(m) / log1p_eps).astype(jnp.int32)
        unset = lvl == LVL_UNSET
        w = jnp.maximum(-((lvl - j_lo) // L), 0)
        lvl = jnp.where(unset, j_lo + slot_arange, lvl + w * L)
        retire = unset | (w > 0)
        count = jnp.where(retire, 0, count)
        cov = jnp.where(retire[:, None], cov_pre[None, :], cov)
        covsum = jnp.where(retire, pre_sum, covsum)
        fval = jnp.where(retire, state.fval_pre, fval)

        # threshold admission, vectorized over the L sieves
        v = jnp.exp(lvl.astype(jnp.float32) * log1p_eps)
        g = jnp.sum(jnp.maximum(col[None, :] - cov, 0.0), axis=1)  # (L,)
        g_mean = g / dn
        f_cur = (fval + covsum) / (n_seen_f + dn)
        thresh = (0.5 * v - f_cur) / jnp.maximum(k - count, 1).astype(jnp.float32)
        accept = (count < k) & (g_mean >= thresh) & (g_mean > 0.0) & (~ispre)

        count = count + accept.astype(jnp.int32)
        cov_new = jnp.maximum(cov, col[None, :])
        cov = jnp.where(accept[:, None], cov_new, cov)
        covsum = jnp.where(accept, jnp.sum(cov_new, axis=1), covsum)
        return (m, lvl, count, fval, covsum, cov), (accept, retire)

    carry0 = (
        state.m,
        state.lvl,
        state.count,
        state.fval,
        jnp.sum(cov0, axis=1),
        cov0,
    )
    (m, lvl, count, fval, covsum, _), (acc_hist, ret_hist) = jax.lax.scan(
        step, carry0, (feats, is_pre)
    )

    # Reconstruct (sel_idx, sel_feats) from the (Δn, L) histories: a sieve
    # keeps only admissions after its last retirement; those fill slots in
    # arrival order, starting at the pre-delta count for never-retired
    # sieves and at 0 otherwise.  One O(Δn·L·d) scatter, not Δn of them.
    t_col = jnp.arange(dn, dtype=jnp.int32)[:, None]
    last_ret = jnp.max(jnp.where(ret_hist, t_col, -1), axis=0)  # (L,)
    keep = acc_hist & (t_col >= last_ret[None, :])  # (Δn, L)
    retired = last_ret >= 0
    base = jnp.where(retired, 0, state.count)  # slot offset at (re)start
    slot = base[None, :] + jnp.cumsum(keep.astype(jnp.int32), axis=0) - 1
    slot_safe = jnp.where(keep, jnp.clip(slot, 0, k - 1), k)  # k = dump slot

    sel_idx = jnp.where(retired[:, None], -1, state.sel_idx)
    sel_feats = jnp.where(retired[:, None, None], 0.0, state.sel_feats)
    l_grid = jnp.broadcast_to(jnp.arange(L)[None, :], (dn, L))
    sel_idx = (
        jnp.concatenate([sel_idx, jnp.full((L, 1), -1, jnp.int32)], axis=1)
        .at[l_grid.ravel(), slot_safe.ravel()]
        .set(jnp.broadcast_to(idx[:, None], (dn, L)).ravel())[:, :k]
    )
    sel_feats = (
        jnp.concatenate([sel_feats, jnp.zeros((L, 1, dim), jnp.float32)], axis=1)
        .at[l_grid.ravel(), slot_safe.ravel()]
        .set(jnp.broadcast_to(feats[:, None, :], (dn, L, dim)).reshape(-1, dim))[
            :, :k
        ]
    )
    return state._replace(
        n_seen=state.n_seen + dn,
        d_max=d_max,
        m=m,
        lvl=lvl,
        count=count,
        fval=fval + covsum,
        fval_pre=state.fval_pre + pre_sum,
        sel_idx=sel_idx,
        sel_feats=sel_feats,
    )


ingest_delta = jax.jit(_ingest_delta, static_argnums=(3,))


def streaming_result(
    state: StreamingState, feats: jax.Array, budget: int, *, d_max=None
) -> FLResult:
    """Finalize: best sieve → full FLResult against the pool (dense sweep).

    ``feats`` is the (n,) pool the stored indices refer to (the service
    keeps it; the one-shot engine has it by construction).  Order: warm
    prefix (replayed), then the best sieve's picks in admission order, then
    worst-covered backfill (farthest-point) for any unfilled budget.  γ and
    coverage use this call's own offset (or the caller's ``d_max`` — the
    per-class selector passes one pool-wide offset so class coverages and
    gains share units), so the frozen ingest-time ``d_max`` never leaks
    into reported units.

    This is the jit-traceable reference path — one dense matvec per budget
    step plus an (n, budget) similarity materialization.  The host-side
    :func:`streaming_result_blocked` computes the same result with blocked
    tiles; CI asserts parity between the two.
    """
    feats = jnp.asarray(feats, jnp.float32)
    n, _ = feats.shape
    budget = int(min(int(budget), n))
    if budget < 1:
        raise ValueError(f"budget must be ≥ 1, got {budget}")
    k = state.capacity
    r0 = state.pre_idx.shape[0]
    if r0 > budget:
        raise ValueError(f"warm prefix {r0} exceeds finalize budget {budget}")

    sq = jnp.sum(feats * feats, axis=-1)
    if d_max is None:
        d_maxf = 2.0 * jnp.sqrt(jnp.max(sq)) + 1e-6
    else:
        d_maxf = jnp.asarray(d_max, jnp.float32)

    def sim_cols(e_arr: jax.Array) -> jax.Array:
        """(n, m) similarity of every pool point to elements ``e_arr``."""
        cf = feats[e_arr]
        d2 = sq[:, None] + jnp.sum(cf * cf, axis=-1)[None, :] - 2.0 * (feats @ cf.T)
        return d_maxf - jnp.sqrt(jnp.maximum(d2, 0.0))

    init_idx, init_gains, cur_max0, chosen0 = _replay_prefix(
        state.pre_idx if r0 > 0 else None,
        budget,
        n,
        lambda e: sim_cols(e[None])[:, 0],
    )

    best = jnp.argmax(state.fval)
    cand = jnp.clip(state.sel_idx[best], -1, n - 1)  # (k,)
    ccount = state.count[best]

    def step(carry, t):
        cur_max, chosen = carry
        resid = jnp.where(chosen, -jnp.inf, d_maxf - cur_max)
        back_e = jnp.argmax(resid).astype(jnp.int32)
        if k > 0:
            se = cand[jnp.clip(t, 0, k - 1)]
            se_safe = jnp.clip(se, 0, n - 1)
            use = (t < ccount) & (se >= 0) & (~chosen[se_safe])
            e = jnp.where(use, se_safe, back_e)
        else:
            e = back_e
        col = sim_cols(e[None])[:, 0]
        gain = jnp.sum(jnp.maximum(col - cur_max, 0.0))
        return (jnp.maximum(cur_max, col), chosen.at[e].set(True)), (
            e.astype(jnp.int32),
            gain,
        )

    (cur_max, _), (new_idx, new_gains) = jax.lax.scan(
        step, (cur_max0, chosen0), jnp.arange(budget - r0)
    )
    indices = jnp.concatenate([init_idx, new_idx])
    gains = jnp.concatenate([init_gains, new_gains]).astype(jnp.float32)

    sel_sim = sim_cols(indices)  # (n, budget)
    assign = jnp.argmax(sel_sim, axis=1)
    weights = jnp.zeros((budget,), jnp.float32).at[assign].add(1.0)
    coverage = jnp.sum(d_maxf - jnp.max(sel_sim, axis=1))
    return FLResult(indices, gains, weights, coverage)


# ---------------------------------------------------------------------------
# Blocked finalize: the host-side fast path (DESIGN.md §10)
# ---------------------------------------------------------------------------
#
# The dense ``streaming_result`` pays one O(n·d) matvec per budget step plus
# a final dense (n, budget) materialization.  The blocked path exploits a
# structural fact of the finalize scan: a backfill (farthest-point) step can
# only occur once the sieve's picks are exhausted (``t ≥ ccount``), and sieve
# picks are distinct and disjoint from the warm prefix, so the dense pick
# sequence decomposes into [prefix | sieve picks | backfill suffix].  The
# first two segments are known up front — a *blocked* sequential replay
# (one (block_n × block_m) similarity tile per matmul, prefix-cummax for the
# per-column cover state) replaces per-step matvecs — and only the short
# backfill suffix stays sequential.  γ assignment and coverage ride along as
# a running per-row (best value, best position) pair, so the (n, budget)
# similarity matrix is never materialized.


@functools.partial(jax.jit, static_argnames=("block_m",))
def _replay_blocked_jax(feats, sq, d_maxf, ef, esq, valid, cur0, block_m: int):
    """Blocked-jnp sequential replay (the CPU/GPU twin of ``kops.fl_replay``).

    ``ef``/``esq``/``valid`` are block-padded (m % block_m == 0); dead
    columns have valid=False.  Returns (gains (m,), cur (n,), best_v (n,),
    best_i (n,)) with the same semantics as the Pallas kernel.
    """
    n = feats.shape[0]
    nblk = ef.shape[0] // block_m
    ef_b = ef.reshape(nblk, block_m, -1)
    esq_b = esq.reshape(nblk, block_m)
    val_b = valid.reshape(nblk, block_m)

    def blk(carry, xs):
        cur, bv, bi, base = carry
        eb, eqb, vb = xs
        d2 = sq[:, None] + eqb[None, :] - 2.0 * (feats @ eb.T)
        s = d_maxf - jnp.sqrt(jnp.maximum(d2, 0.0))  # (n, bm)
        s_cov = jnp.where(vb[None, :], s, -1e30)
        run = jax.lax.cummax(s_cov, axis=1)
        prev = jnp.maximum(
            cur[:, None],
            jnp.concatenate(
                [jnp.full((n, 1), -1e30, jnp.float32), run[:, :-1]], axis=1
            ),
        )
        gains = jnp.sum(jnp.maximum(s_cov - prev, 0.0), axis=0)  # (bm,)
        cur = jnp.maximum(cur, run[:, -1])
        bvb = jnp.max(s_cov, axis=1)
        bib = jnp.argmax(s_cov, axis=1).astype(jnp.int32) + base
        upd = bvb > bv  # strict: earlier block wins ties, like jnp.argmax
        return (
            (cur, jnp.where(upd, bvb, bv), jnp.where(upd, bib, bi),
             base + block_m),
            gains,
        )

    carry0 = (
        cur0,
        jnp.full((n,), -1e30, jnp.float32),
        jnp.zeros((n,), jnp.int32),
        jnp.int32(0),
    )
    (cur, bv, bi, _), gs = jax.lax.scan(blk, carry0, (ef_b, esq_b, val_b))
    return gs.reshape(-1), cur, bv, bi


@jax.jit
def _backfill_step(feats, sq, d_maxf, cur, chosen, bv, bi, pos):
    """One farthest-point backfill pick + incremental γ/coverage update."""
    resid = jnp.where(chosen, -jnp.inf, d_maxf - cur)
    e = jnp.argmax(resid).astype(jnp.int32)
    x = feats[e]
    d2 = sq + jnp.sum(x * x) - 2.0 * (feats @ x)
    col = d_maxf - jnp.sqrt(jnp.maximum(d2, 0.0))
    gain = jnp.sum(jnp.maximum(col - cur, 0.0))
    upd = col > bv
    return (
        e,
        gain,
        jnp.maximum(cur, col),
        chosen.at[e].set(True),
        jnp.where(upd, col, bv),
        jnp.where(upd, pos, bi),
    )


def streaming_result_blocked(
    state: StreamingState,
    feats: jax.Array,
    budget: int,
    *,
    d_max=None,
    impl: str = "auto",
    block_m: int = 128,
) -> FLResult:
    """Blocked finalize: same result as :func:`streaming_result`, without
    the per-step dense sweep.  Host-side only (it pulls the best sieve's
    tiny metadata to plan the replay) — the jit-safe engine path keeps the
    dense reference.

    ``impl``: 'auto' (Pallas on TPU, blocked jnp elsewhere) | 'pallas' |
    'jax' | 'dense' (delegate to the reference path).
    """
    impl = kops.resolve_impl(impl, "jax")
    if impl == "dense":
        return streaming_result(state, feats, budget, d_max=d_max)
    if impl not in ("pallas", "jax"):
        raise ValueError(f"unknown finalize impl {impl!r}")
    feats = jnp.asarray(feats, jnp.float32)
    n, _ = feats.shape
    budget = int(min(int(budget), n))
    if budget < 1:
        raise ValueError(f"budget must be ≥ 1, got {budget}")
    k = state.capacity
    r0 = state.pre_idx.shape[0]
    if r0 > budget:
        raise ValueError(f"warm prefix {r0} exceeds finalize budget {budget}")

    # Host-static pick plan from the sieve's O(L + k) metadata.
    pre = np.asarray(state.pre_idx, np.int64)
    if k > 0:
        best = int(np.argmax(np.asarray(state.fval)))
        cand = np.clip(np.asarray(state.sel_idx[best], np.int64), -1, n - 1)
        ccount = int(np.asarray(state.count)[best])
    else:
        cand = np.zeros((0,), np.int64)
        ccount = 0
    u = max(0, min(ccount, budget - r0))
    ordered = np.concatenate([pre, cand[:u]])
    if len(ordered) and (
        (ordered < 0).any() or len(np.unique(ordered)) != len(ordered)
    ):
        # a sieve pick collides with the prefix or repeats — can only happen
        # on a malformed state; the dense scan's per-step guards handle it
        return streaming_result(state, feats, budget, d_max=d_max)

    sq = jnp.sum(feats * feats, axis=-1)
    if d_max is None:
        d_maxf = 2.0 * jnp.sqrt(jnp.max(sq)) + 1e-6
    else:
        d_maxf = jnp.asarray(d_max, jnp.float32)

    m = len(ordered)
    if m > 0:
        eidx = jnp.asarray(ordered, jnp.int32)
        ef = feats[eidx]
        if impl == "pallas":
            gains_o, cur, bv, bi = kops.fl_replay(
                feats, ef, jnp.ones((m,), bool), jnp.zeros((n,), jnp.float32),
                d_maxf, block_m=block_m,
            )
        else:
            pad = (-m) % block_m
            ef_p = jnp.pad(ef, ((0, pad), (0, 0)))
            esq_p = jnp.pad(jnp.sum(ef * ef, axis=-1), (0, pad))
            val_p = jnp.pad(jnp.ones((m,), bool), (0, pad))
            gains_o, cur, bv, bi = _replay_blocked_jax(
                feats, sq, d_maxf, ef_p, esq_p, val_p,
                jnp.zeros((n,), jnp.float32), block_m,
            )
        gains_o = gains_o[:m]
        chosen = jnp.zeros((n,), bool).at[eidx].set(True)
    else:
        gains_o = jnp.zeros((0,), jnp.float32)
        cur = jnp.zeros((n,), jnp.float32)
        bv = jnp.full((n,), -1e30, jnp.float32)
        bi = jnp.zeros((n,), jnp.int32)
        chosen = jnp.zeros((n,), bool)

    back_idx, back_gains = [], []
    for t in range(budget - m):
        e, g, cur, chosen, bv, bi = _backfill_step(
            feats, sq, d_maxf, cur, chosen, bv, bi, jnp.int32(m + t)
        )
        back_idx.append(e)
        back_gains.append(g)

    indices = jnp.concatenate(
        [jnp.asarray(ordered, jnp.int32), jnp.stack(back_idx)]
        if back_idx
        else [jnp.asarray(ordered, jnp.int32)]
    )
    gains = jnp.concatenate(
        [gains_o, jnp.stack(back_gains)] if back_gains else [gains_o]
    ).astype(jnp.float32)
    weights = jnp.zeros((budget,), jnp.float32).at[bi].add(1.0)
    coverage = jnp.sum(d_maxf - bv)
    return FLResult(indices, gains, weights, coverage)


# ---------------------------------------------------------------------------
# Registry plugin: one-shot select behind the common protocol
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StreamingConfig(EngineConfig):
    """Sieve-streaming engine knobs.

    Attributes:
      eps: geometric grid density — thresholds are ``(1+eps)^j``.  Smaller
        eps → more sieves → tighter ``(1/2 − O(eps))`` guarantee, linearly
        more state and per-element work.
      levels: sieve-slot count override (0 = auto: span ``[m, 2·budget·m]``,
        capped at 64 — see :func:`num_sieves`).
      finalize_impl: blocked-finalize backend for ``StreamingSelector``
        ('auto' = Pallas on TPU / blocked jnp elsewhere; 'pallas' | 'jax' |
        'dense').  The one-shot jit-safe ``StreamingEngine.select`` always
        uses the dense reference path — it must stay traceable.
      finalize_block_m: candidate-block width of the blocked finalize.
    """

    name: ClassVar[str] = "streaming"
    eps: float = 0.15
    levels: int = 0
    finalize_impl: str = "auto"
    finalize_block_m: int = 128


@register_engine
class StreamingEngine(SelectionEngine):
    name = "streaming"
    config_cls = StreamingConfig
    capabilities = Capabilities(
        exact=False,  # (1/2 − eps) sieve guarantee, not exact greedy
        matrix_free=True,
        jit_safe=True,
        supports_cover=False,
        supports_metrics=("l2", "cosine"),  # cosine via normalized l2
        # state is L·k·d plus the pool row it sweeps: L≈48, k≈n/20 heuristic
        memory=lambda n, d: 4 * (n * d + 48 * d * max(n // 20, 64)),
    )

    def select(
        self, feats, budget, *, metric="l2", init_selected=None, rng=None
    ) -> FLResult:
        feats = normalize_for_metric(jnp.asarray(feats), metric)
        n = feats.shape[0]
        budget = int(min(int(budget), n))
        if init_selected is not None:
            init_idx = jnp.asarray(init_selected, jnp.int32).ravel()
            if init_idx.shape[0] > budget:
                raise ValueError(
                    f"init_selected has {init_idx.shape[0]} elements > "
                    f"budget {budget}"
                )
            state = init_streaming_state(
                budget,
                feats.shape[1],
                eps=self.config.eps,
                levels=self.config.levels,
                init_selected=init_idx,
                init_feats=feats[init_idx],
            )
        else:
            state = init_streaming_state(
                budget, feats.shape[1],
                eps=self.config.eps, levels=self.config.levels,
            )
        if state.capacity > 0:
            # the whole pool as ONE delta: estimates are exact — this is
            # textbook sieve-streaming over the pool in index order
            state = ingest_delta(
                state, feats, jnp.arange(n, dtype=jnp.int32), self.config.eps
            )
        res = streaming_result(state, feats, budget)
        if metric == "cosine":  # report L(S) in cosine-distance units
            res = res._replace(
                coverage=cosine_residual_coverage(feats, res.indices)
            )
        return res


# ---------------------------------------------------------------------------
# Stateful host wrapper: the coreset service's selection core
# ---------------------------------------------------------------------------

_FLAT = "__flat__"

_STATE_DTYPES = {
    "n_seen": np.int32, "d_max": np.float32, "m": np.float32,
    "lvl": np.int32, "count": np.int32, "fval": np.float32,
    "fval_pre": np.float32, "sel_idx": np.int32, "sel_feats": np.float32,
    "pre_idx": np.int32, "pre_feats": np.float32,
}


def _state_to_dict(state: StreamingState) -> dict:
    """JSON-able snapshot: shapes + flat lists (float32↔float round-trips
    exactly, so restores are bit-identical)."""
    out = {}
    for name in StreamingState._fields:
        arr = np.asarray(getattr(state, name))
        out[name] = {"shape": list(arr.shape), "data": arr.ravel().tolist()}
    return out


def _state_from_dict(d: dict) -> StreamingState:
    kw = {}
    for name in StreamingState._fields:
        spec = d[name]
        arr = np.asarray(spec["data"], _STATE_DTYPES[name]).reshape(spec["shape"])
        kw[name] = jnp.asarray(arr)
    return StreamingState(**kw)


class StreamingSelector:
    """Stateful sieve-streaming selection over a pool arriving in deltas.

    The contract mirrors ``CraigSelector`` where it can: γ sums to the pool
    size, per-class mode stratifies budgets ∝ observed class frequency
    (paper §5, apportioned with the same largest-remainder rule), and the
    warm-start prefix (flat mode) is preserved at the front of the result.
    The difference is lifecycle: ``ingest`` is called once per arriving
    megabatch (O(Δn·k) work, no re-sweep), and ``result`` finalizes against
    the accumulated pool on demand.

    Pool indexing: deltas are assigned positions in arrival order, so the
    ``feats`` passed to :meth:`result` must be the ingested deltas
    concatenated in ingest order (the coreset service maintains exactly
    that buffer).  With ``evict=True`` the positions are *live-pool*
    coordinates instead: :meth:`compact` drops every row no sieve
    references, the caller applies the same row selection to its buffer,
    and :attr:`live_ids` maps live positions back to global arrival order
    — memory becomes O(L·k·d) instead of O(n·d) for unbounded streams, and
    γ then sums to the live-pool size rather than ``n_seen``.

    ``state_dict`` / ``load_state_dict`` round-trip the full mid-stream
    state (JSON-able — rides ``CheckpointManager`` extras) bit-identically,
    including the compaction remap.
    """

    def __init__(
        self,
        budget: int,
        dim: int,
        *,
        config: StreamingConfig | None = None,
        metric: str = "l2",
        per_class: bool = False,
        evict: bool = False,
        init_selected=None,
        init_feats=None,
    ):
        config = config or StreamingConfig()
        caps = StreamingEngine.capabilities
        if metric not in caps.supports_metrics:
            raise ValueError(
                f"engine 'streaming' supports metrics {caps.supports_metrics}, "
                f"got {metric!r}"
            )
        if per_class and init_selected is not None:
            raise ValueError(
                "warm-start prefix is flat-mode only (per-class budgets are "
                "apportioned at result time, after arrival counts are known)"
            )
        self.budget = int(budget)
        self.dim = int(dim)
        self.config = config
        self.metric = metric
        self.per_class = bool(per_class)
        self.evict = bool(evict)
        self._n_seen = 0
        self._n_rows = 0  # live pool rows (== n_seen unless evict compacts)
        self._live = np.zeros((0,), np.int64)  # live pos -> global arrival id
        self._class_seen: dict = {}  # label -> total arrivals (pre-eviction)
        self._states: dict = {}
        self._rows: dict = {}  # label -> pool positions, class-arrival order
        if not per_class:
            init_feats = (
                None
                if init_feats is None
                else normalize_for_metric(
                    jnp.asarray(init_feats, jnp.float32), metric
                )
            )
            self._states[_FLAT] = init_streaming_state(
                self.budget, self.dim,
                eps=config.eps, levels=config.levels,
                init_selected=init_selected, init_feats=init_feats,
            )

    @property
    def n_seen(self) -> int:
        """Total points ingested so far (monotone; eviction never lowers it)."""
        return self._n_seen

    @property
    def n_rows(self) -> int:
        """Live pool rows the next :meth:`result` call expects."""
        return self._n_rows

    @property
    def live_ids(self) -> np.ndarray:
        """(n_rows,) int64 — global arrival id of each live pool position
        (the identity map unless ``evict=True`` has compacted)."""
        if not self.evict:
            return np.arange(self._n_rows, dtype=np.int64)
        return self._live.copy()

    def ingest(self, feats, labels=None) -> int:
        """Ingest one megabatch delta; returns the running pool size.

        O(Δn·(Δn + L)·d) — independent of the pool ingested so far.
        """
        feats = normalize_for_metric(jnp.asarray(feats, jnp.float32), self.metric)
        dn = feats.shape[0]
        if feats.ndim != 2 or feats.shape[1] != self.dim:
            raise ValueError(f"expected (Δn, {self.dim}) features, got {feats.shape}")
        if self.per_class:
            if labels is None:
                raise ValueError("per_class=True ingest needs labels")
            labels = np.asarray(labels).ravel()
            if labels.shape[0] != dn:
                raise ValueError(f"labels length {labels.shape[0]} != Δn {dn}")
            for c in np.unique(labels):
                key = int(c)
                mask = labels == c
                rows = self._rows.setdefault(key, [])
                if key not in self._states:
                    self._states[key] = init_streaming_state(
                        self.budget, self.dim,
                        eps=self.config.eps, levels=self.config.levels,
                    )
                local = len(rows) + np.arange(int(mask.sum()), dtype=np.int32)
                self._states[key] = ingest_delta(
                    self._states[key], feats[np.nonzero(mask)[0]],
                    jnp.asarray(local), self.config.eps,
                )
                rows.extend((self._n_rows + np.nonzero(mask)[0]).tolist())
                self._class_seen[key] = (
                    self._class_seen.get(key, 0) + int(mask.sum())
                )
        else:
            idx = self._n_rows + jnp.arange(dn, dtype=jnp.int32)
            self._states[_FLAT] = ingest_delta(
                self._states[_FLAT], feats, idx, self.config.eps
            )
        if self.evict:
            self._live = np.concatenate(
                [self._live, self._n_seen + np.arange(dn, dtype=np.int64)]
            )
        self._n_seen += int(dn)
        self._n_rows += int(dn)
        return self._n_seen

    def compact(self) -> np.ndarray:
        """Evict pool rows no sieve references (``evict=True`` only).

        Keeps exactly the rows referenced by any sieve's ``sel_idx`` or the
        warm prefix, remaps every stored index into the compacted
        coordinates, and returns the kept positions (into the
        pre-compaction pool order, ascending) — the caller MUST apply the
        same row selection to its pool buffer before the next
        :meth:`result`.  A no-op identity when ``evict=False``.
        """
        if not self.evict or self._n_rows == 0:
            return np.arange(self._n_rows, dtype=np.int64)
        if not self.per_class:
            st = self._states[_FLAT]
            sel = np.asarray(st.sel_idx, np.int64)
            pre = np.asarray(st.pre_idx, np.int64)
            keep = np.unique(np.concatenate([sel[sel >= 0].ravel(), pre]))
            new_sel = np.where(
                sel >= 0, np.searchsorted(keep, np.clip(sel, 0, None)), -1
            ).astype(np.int32)
            self._states[_FLAT] = st._replace(
                sel_idx=jnp.asarray(new_sel),
                pre_idx=jnp.asarray(np.searchsorted(keep, pre), jnp.int32),
            )
        else:
            keep_mask = np.zeros(self._n_rows, bool)
            kept_local: dict = {}
            for c, st in self._states.items():
                sel = np.asarray(st.sel_idx, np.int64)
                kl = np.unique(sel[sel >= 0].ravel())
                kept_local[c] = kl
                rows_c = np.asarray(self._rows[c], np.int64)
                keep_mask[rows_c[kl]] = True
            keep = np.nonzero(keep_mask)[0].astype(np.int64)
            pool_remap = np.full(self._n_rows, -1, np.int64)
            pool_remap[keep] = np.arange(len(keep))
            for c, st in self._states.items():
                kl = kept_local[c]
                sel = np.asarray(st.sel_idx, np.int64)
                new_sel = np.where(
                    sel >= 0, np.searchsorted(kl, np.clip(sel, 0, None)), -1
                ).astype(np.int32)
                self._states[c] = st._replace(sel_idx=jnp.asarray(new_sel))
                rows_c = np.asarray(self._rows[c], np.int64)
                self._rows[c] = pool_remap[rows_c[kl]].tolist()
        self._live = self._live[keep]
        self._n_rows = int(len(keep))
        return keep

    def result(self, feats) -> FLResult:
        """Finalize the current selection against the accumulated pool.

        ``feats`` must be the ingested deltas concatenated in arrival
        order (rows align with the positions ``ingest`` assigned); after a
        :meth:`compact`, the same row selection must have been applied.
        Indices in the result are pool positions — map through
        :attr:`live_ids` for global arrival ids when ``evict=True``.
        """
        feats = normalize_for_metric(jnp.asarray(feats, jnp.float32), self.metric)
        n = feats.shape[0]
        if n != self._n_rows:
            raise ValueError(
                f"pool has {n} rows but {self._n_rows} are live — result() "
                "needs the ingested deltas concatenated in order, compacted "
                "in lockstep with compact()"
            )
        if n == 0:
            raise ValueError("nothing ingested yet")
        impl = self.config.finalize_impl
        bm = self.config.finalize_block_m
        if not self.per_class:
            res = streaming_result_blocked(
                self._states[_FLAT], feats, min(self.budget, n),
                impl=impl, block_m=bm,
            )
            if self.metric == "cosine":
                res = res._replace(
                    coverage=cosine_residual_coverage(feats, res.indices)
                )
            return res

        # paper §5: stratified budgets ∝ observed class arrival counts
        from repro.core.craig import _apportion_budgets  # lazy: avoid cycle

        classes = sorted(self._states)
        counts = np.array(
            [self._class_seen.get(c, len(self._rows[c])) for c in classes],
            np.int64,
        )
        budgets = _apportion_budgets(counts, min(self.budget, n))
        # one pool-wide offset so per-class gains/coverages share units
        # (each subpool's own d_max would make classes incommensurable)
        sq = jnp.sum(feats * feats, axis=-1)
        d_max_pool = 2.0 * jnp.sqrt(jnp.max(sq)) + 1e-6
        all_idx, all_gains, all_w = [], [], []
        coverage = 0.0
        for c, b in zip(classes, budgets):
            b = int(min(b, len(self._rows[c])))
            if b == 0:
                continue
            rows = np.asarray(self._rows[c], np.int64)
            sub = feats[rows]
            r = streaming_result_blocked(
                self._states[c], sub, b,
                d_max=d_max_pool, impl=impl, block_m=bm,
            )
            all_idx.append(rows[np.asarray(r.indices, np.int64)])
            all_gains.append(np.asarray(r.gains, np.float32))
            all_w.append(np.asarray(r.weights, np.float32))
            coverage += float(
                cosine_residual_coverage(sub, r.indices)
                if self.metric == "cosine"
                else r.coverage
            )
        return FLResult(
            jnp.asarray(np.concatenate(all_idx), jnp.int32),
            jnp.asarray(np.concatenate(all_gains)),
            jnp.asarray(np.concatenate(all_w)),
            jnp.asarray(coverage, jnp.float32),
        )

    # -- serialization -------------------------------------------------------

    def state_dict(self) -> dict:
        """JSON-able full snapshot (config + per-class sieve states + the
        eviction remap)."""
        return {
            "budget": self.budget,
            "dim": self.dim,
            "metric": self.metric,
            "per_class": self.per_class,
            "evict": self.evict,
            "n_seen": self._n_seen,
            "n_rows": self._n_rows,
            "live": self._live.tolist(),
            "class_seen": {
                str(key): int(v) for key, v in self._class_seen.items()
            },
            "config": self.config.to_dict(),
            "states": {
                str(key): _state_to_dict(st) for key, st in self._states.items()
            },
            "rows": {str(key): list(rows) for key, rows in self._rows.items()},
        }

    def load_state_dict(self, d: dict) -> None:
        """Inverse of :meth:`state_dict` — resumes bit-identically."""
        cfg = EngineConfig.from_dict(d["config"])
        if not isinstance(cfg, StreamingConfig):
            raise ValueError(f"not a streaming state_dict: {d['config']!r}")
        self.budget = int(d["budget"])
        self.dim = int(d["dim"])
        self.metric = d["metric"]
        self.per_class = bool(d["per_class"])
        self.evict = bool(d.get("evict", False))
        self.config = cfg
        self._n_seen = int(d["n_seen"])
        self._n_rows = int(d.get("n_rows", d["n_seen"]))
        self._live = np.asarray(d.get("live", []), np.int64)
        self._states = {
            (key if key == _FLAT else int(key)): _state_from_dict(sd)
            for key, sd in d["states"].items()
        }
        self._rows = {int(key): list(rows) for key, rows in d["rows"].items()}
        self._class_seen = {
            int(key): int(v) for key, v in d.get("class_seen", {}).items()
        } or {c: len(r) for c, r in self._rows.items()}
