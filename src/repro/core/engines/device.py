"""Device-resident fused greedy engine (DESIGN.md §3.6).

The whole selection loop lives in one jitted ``lax.while_loop``; a sweep
round is a single fused gains-sweep + per-block argmax kernel launch
(``fl_gains_argmax`` on TPU, a blockwise jnp scan elsewhere), streaming
feature tiles so the (n, n) similarity never exists.  ``q > 1`` amortizes
each sweep over up to q commits via device-resident Minoux bounds.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import ClassVar

import jax
import jax.numpy as jnp

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

__all__ = ["DeviceConfig", "DeviceEngine", "greedy_fl_device"]


@partial(
    jax.jit,
    static_argnames=(
        "budget", "q", "gains_impl", "block_n", "block_m", "tile_dtype",
        "stale_tol",
    ),
)
def greedy_fl_device(
    feats: jax.Array,
    budget: int,
    *,
    q: int = 1,
    gains_impl: str = "auto",
    block_n: int = 512,
    block_m: int = 2048,
    tile_dtype: str = "float32",
    stale_tol: float = 0.7,
    init_selected: jax.Array | None = None,
) -> FLResult:
    """Fully jitted device-resident greedy FL from features (DESIGN.md §3.6).

    The entire selection loop is one ``lax.while_loop`` on device — no
    per-round host round-trip, no (n, n) similarity, no host-visible gains
    vector on the Pallas path.  A *sweep* round runs one fused
    gains + argmax pass over every candidate — on TPU a single
    ``fl_gains_argmax`` kernel launch (gains accumulate tile-by-tile in
    VMEM, the argmax epilogue is fused, chosen candidates are penalized
    in-kernel), elsewhere an equivalent blockwise jnp scan with identical
    tie semantics (lowest index within a block, lowest block across blocks
    — i.e. ``jnp.argmax`` order) — and commits the winner.

    Block-greedy mode (``q > 1``) amortizes that O(n²·d) sweep over up to
    ``q`` commits: the sweep's full gains vector stays resident as Minoux
    upper bounds.  Between sweeps the loop refreshes the top-P bounds
    against the *updated* cover state in one (n, d)×(d, P) matmul and
    commits the best refreshed winner iff its fresh gain retains at least
    ``stale_tol`` of the best outstanding bound (bounds only overestimate,
    so ``stale_tol=1.0`` is the exact Minoux acceptance rule — the winner
    is the true argmax; the 0.7 default admits near-argmax winners, which
    in practice keeps coverage within ~1% of exact while committing far
    more often).  A failed re-check writes the fresh gains back as new
    (tighter) bounds; once the refresh budget is spent — the bounds have
    gone uniformly stale under heavy cover overlap — the engine falls back
    to a fresh q=1-style sweep.

    ``q=1`` sweeps before every commit and is bit-faithful to
    ``greedy_fl_matrix``/``greedy_fl_features`` (same objective, same
    tie-breaking) regardless of ``stale_tol``.

    Args:
      feats: (n, d) proxy features.
      budget: r (static); clamped to n.
      q: max winners committed per sweep (static).  1 = sweep every round;
        larger values amortize sweeps at large budgets via the lazy bounds.
      gains_impl: 'auto' (pallas on TPU, jax elsewhere) | 'pallas' | 'jax'.
      block_n / block_m: pool/candidate tile sizes for the sweep.
      tile_dtype: 'float32' | 'bfloat16' feature tiles; gains always
        accumulate fp32.
      stale_tol: lazy-commit floor in (0, 1]; 1.0 = exact greedy at any q.
      init_selected: optional warm-start prefix (see ``greedy_fl_matrix``).
    """
    n, d = feats.shape
    feats = feats.astype(jnp.float32)
    budget = int(min(budget, n))
    gains_impl = kops.resolve_impl(gains_impl, "jax")
    if gains_impl not in ("pallas", "jax"):
        raise ValueError(f"unknown gains_impl {gains_impl!r}")
    if tile_dtype not in ("float32", "bfloat16"):
        raise ValueError(f"unsupported tile_dtype {tile_dtype!r}")
    td = jnp.dtype(tile_dtype)

    sq = jnp.sum(feats * feats, axis=-1)  # (n,)
    d_max = 2.0 * jnp.sqrt(jnp.max(sq)) + 1e-6

    def sim_cols(idx: jax.Array) -> jax.Array:
        """(n, m) similarity of every point to elements ``idx`` ((m,))."""
        cf = feats[idx]
        d2 = sq[:, None] + sq[idx][None, :] - 2.0 * (feats @ cf.T)
        return d_max - jnp.sqrt(jnp.maximum(d2, 0.0))

    def sim_col(e: jax.Array) -> jax.Array:
        """(n,) similarity of every point to element e."""
        return sim_cols(jnp.asarray(e)[None])[:, 0]

    bm = min(block_m, n)
    n_blocks = (n + bm - 1) // bm
    pad_m = n_blocks * bm
    if gains_impl == "jax":
        featp = jnp.pad(feats, ((0, pad_m - n), (0, 0)))
        sqp = jnp.pad(sq, (0, pad_m - n))
        featp_t = featp.astype(td)
        feats_t = feats.astype(td)

    def sweep(cur_max, chosen):
        """One fused pass: full gains vector + per-block (best_gain,
        best_idx) partials.  Blocks whose every candidate is chosen/padded
        report best_gain ≤ −1e29 (real gains are ≥ 0)."""
        if gains_impl == "pallas":
            return kops.fl_gains_argmax(
                feats, feats, cur_max, sq, sq, d_max, chosen,
                block_n=block_n, block_m=bm, tile_dtype=tile_dtype,
            )
        penp = jnp.where(
            jnp.pad(chosen, (0, pad_m - n), constant_values=True), -1e30, 0.0
        )

        def blk(carry, b):
            lo = b * bm
            cf = jax.lax.dynamic_slice_in_dim(featp_t, lo, bm)
            csq = jax.lax.dynamic_slice_in_dim(sqp, lo, bm)
            cpen = jax.lax.dynamic_slice_in_dim(penp, lo, bm)
            dots = jax.lax.dot_general(
                feats_t, cf, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (n, bm)
            d2 = sq[:, None] + csq[None, :] - 2.0 * dots
            s = d_max - jnp.sqrt(jnp.maximum(d2, 0.0))
            g = jnp.sum(jnp.maximum(s - cur_max[:, None], 0.0), axis=0)
            gp = g + cpen
            p = jnp.argmax(gp)
            return carry, (g, gp[p], (lo + p).astype(jnp.int32))

        _, (g, pg, pi) = jax.lax.scan(blk, None, jnp.arange(n_blocks))
        return g.reshape(pad_m)[:n], pg, pi

    init_idx, init_gains, cur_max0, chosen0 = _replay_prefix(
        init_selected, budget, n, sim_col
    )
    r0 = init_idx.shape[0]
    q = max(1, int(q))
    # Between sweeps, stale bounds are refreshed P at a time (one
    # (n, d) × (d, P) matmul — ~P/n of a sweep, and one loop dispatch
    # instead of P).  The refresh budget caps the worst-case chew at ~1/4
    # sweep before falling back to a fresh full sweep.  Between two commits
    # each candidate can go stale at most once (a refreshed bound is exact),
    # so the loop terminates even without the fallback.
    refresh_p = min(128, n)
    max_fails = max(1, n // (4 * refresh_p))

    out_idx0 = jnp.zeros((budget,), jnp.int32).at[:r0].set(init_idx)
    out_g0 = jnp.zeros((budget,), jnp.float32).at[:r0].set(init_gains)
    neg = jnp.float32(-jnp.inf)

    # Carry: cover state, chosen mask, Minoux upper bounds (−inf = invalid /
    # chosen), commits since the last sweep, consecutive stale re-checks,
    # output buffers, count.  commits0 = q forces a sweep on entry.
    state0 = (
        cur_max0, chosen0, jnp.full((n,), neg), jnp.int32(q), jnp.int32(0),
        out_idx0, out_g0, jnp.int32(r0),
    )

    def cond(state):
        return state[7] < budget

    def body(state):
        cur_max, chosen, ub, commits, fails, out_idx, out_g, count = state
        need_sweep = (commits >= q) | (fails >= max_fails)

        def sweep_round(_):
            g, pg, pi = sweep(cur_max, chosen)
            e = pi[jnp.argmax(pg)]  # exact winner (jnp.argmax tie order)
            col = sim_col(e)
            fresh = jnp.sum(jnp.maximum(col - cur_max, 0.0))
            new_ub = jnp.where(chosen, neg, g).at[e].set(neg)
            return (
                jnp.maximum(cur_max, col),
                chosen.at[e].set(True),
                new_ub,
                jnp.int32(1),
                jnp.int32(0),
                out_idx.at[count].set(e),
                out_g.at[count].set(fresh),
                count + 1,
            )

        def lazy_round(_):
            # Refresh the top-P bounds in one matmul, then the tolerance-
            # scaled Minoux rule: the best refreshed (exact) gain commits
            # iff it retains ≥ stale_tol of the best bound outside the
            # batch; at stale_tol=1.0 the winner is the true argmax
            # (bounds only overestimate).
            tg, tp = jax.lax.top_k(ub, refresh_p)
            cols = sim_cols(tp)  # (n, P)
            fresh_p = jnp.sum(
                jnp.maximum(cols - cur_max[:, None], 0.0), axis=0
            )
            fresh_p = jnp.where(jnp.isfinite(tg), fresh_p, neg)  # chosen
            j = jnp.argmax(fresh_p)
            e = tp[j]
            fresh = fresh_p[j]
            col = cols[:, j]
            rest = jnp.max(ub.at[tp].set(neg))
            # Small slack absorbs the sweep-vs-column summation-order
            # difference.
            commit = fresh * (1.0 + 1e-5) + 1e-6 >= stale_tol * rest
            new_ub = ub.at[tp].set(fresh_p).at[e].set(
                jnp.where(commit, neg, fresh)
            )
            return (
                jnp.where(commit, jnp.maximum(cur_max, col), cur_max),
                chosen.at[e].set(chosen[e] | commit),
                new_ub,
                commits + commit.astype(jnp.int32),
                jnp.where(commit, 0, fails + 1).astype(jnp.int32),
                out_idx.at[count].set(jnp.where(commit, e, out_idx[count])),
                out_g.at[count].set(jnp.where(commit, fresh, out_g[count])),
                count + commit.astype(jnp.int32),
            )

        return jax.lax.cond(need_sweep, sweep_round, lazy_round, None)

    cur_max, _, _, _, _, indices, gains, _ = jax.lax.while_loop(
        cond, body, state0
    )

    # γ / coverage: exact assignment of every point to its nearest medoid.
    sel_sim = sim_cols(indices)  # (n, r)
    assign = jnp.argmax(sel_sim, axis=1)
    weights = jnp.zeros((budget,), jnp.float32).at[assign].add(1.0)
    coverage = jnp.sum(d_max - jnp.max(sel_sim, axis=1))
    return FLResult(indices, gains, weights, coverage)


@dataclasses.dataclass(frozen=True)
class DeviceConfig(EngineConfig):
    """Device-resident fused greedy.

    Attributes:
      q: winners committed per fused sweep (block greedy).  1 = exact
        greedy; larger amortizes the O(n²·d) sweep at large budgets.
      stale_tol: lazy-commit floor in (0, 1]; 1.0 = exact Minoux rule
        (exact greedy at any q), the 0.7 default is near-exact.
      tile_dtype: 'float32' | 'bfloat16' feature tiles (gains always
        accumulate fp32).
      gains_impl: 'auto' (pallas on TPU, jax elsewhere) | 'pallas' | 'jax'.
      block_n / block_m: pool/candidate tile sizes for the sweep.
    """

    name: ClassVar[str] = "device"
    q: int = 1
    stale_tol: float = 0.7
    tile_dtype: str = "float32"
    gains_impl: str = "auto"
    block_n: int = 512
    block_m: int = 2048


@register_engine
class DeviceEngine(SelectionEngine):
    name = "device"
    config_cls = DeviceConfig
    capabilities = Capabilities(
        exact=True,  # at the q=1 default (or stale_tol=1.0); near-exact past
        matrix_free=True,
        jit_safe=True,
        supports_cover=False,
        supports_metrics=("l2", "cosine"),  # cosine via normalized l2
        memory=lambda n, d: 4 * n * (d + 2048),
    )

    def select(
        self, feats, budget, *, metric="l2", init_selected=None, rng=None
    ) -> FLResult:
        cfg = self.config
        feats = normalize_for_metric(jnp.asarray(feats), metric)
        init = None if init_selected is None else jnp.asarray(init_selected)
        res = greedy_fl_device(
            feats,
            budget,
            q=cfg.q,
            gains_impl=cfg.gains_impl,
            block_n=cfg.block_n,
            block_m=cfg.block_m,
            tile_dtype=cfg.tile_dtype,
            stale_tol=cfg.stale_tol,
            init_selected=init,
        )
        if metric == "cosine":  # report L(S) in cosine-distance units
            res = res._replace(
                coverage=cosine_residual_coverage(feats, res.indices)
            )
        return res
