"""Two-round distributed CRAIG selection (GreeDi-style, shard_map).

Pod-scale training cannot ship the whole candidate pool's proxy features to
one host.  Following the paper's own scaling references (Mirzasoleiman et al.
2015b, 2016 — distributed submodular cover/maximization), selection runs in
two rounds over the data-parallel mesh axis:

  Round 1 (local):  every data shard runs greedy facility location over its
      local partition of the pool, selecting ``r_local`` candidates with local
      γ weights.  (Per-class partitioning composes with this: the trainer
      shards each class across hosts.)  The round-1 body is picked by a typed
      ``EngineConfig`` (``repro.core.engines``) — any engine in
      ``ROUND1_ENGINES`` works, and ``local_engine='auto'`` (the default)
      resolves it per *shard* pool size via the documented policy:
      * ``MatrixConfig``   — dense exact greedy per shard (§3.1);
      * ``FeaturesConfig`` — matrix-free blocked greedy (§3.4);
      * ``SparseConfig``   — top-k graph greedy (``topk_graph`` +
        ``greedy_fl_topk``), O(n_local·k) round-1 footprint — the pod-scale
        path for shards past ~10⁵ points (DESIGN.md §6);
      * ``DeviceConfig``   — device-resident fused greedy (§3.6): matrix-free
        like sparse, exact like matrix, the whole round-1 loop jitted inside
        the shard_map body.

  Round 2 (merge):  candidate features and γ weights are all-gathered
      (r_total = shards·r_local ≪ n), and a *weighted* greedy FL — each
      candidate counts γ_c points — selects the final ``r_final`` medoids.
      This runs replicated on every shard (deterministic → identical result).

  Re-weighting:     every shard assigns its local points to the final medoids
      and the per-medoid counts are ``psum``-reduced, so the final γ weights
      cover the *entire* pool exactly (Σγ = n globally).

The approximation factor of the two-round scheme is (1−1/e)²/2-ish in the
worst case but empirically near-exact (GreeDi); tests verify parity with the
centralized selection on clustered data.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import facility_location as fl
from repro.core.engines import (
    DeviceConfig,
    EngineConfig,
    FeaturesConfig,
    MatrixConfig,
    SparseConfig,
    auto_engine_config,
)
from repro.core.engines.legacy import resolve_distributed_engine
from repro.kernels.ops import interpret_default, resolve_impl

__all__ = [
    "DistributedSelection",
    "distributed_select",
    "local_then_merge",
    "make_distributed_extract",
    "ROUND1_ENGINES",
    "normalize_round1_config",
    "resolve_round1_config",
    "leaf_round",
    "merge_round",
    "check_candidate_counts",
    "check_even_shards",
]

# Engines with a jit/shard_map-safe round-1 body.  Host-side engines (lazy)
# and the sampled stochastic greedy have no distributed round 1; callers
# fall back to 'auto'.
ROUND1_ENGINES = ("matrix", "features", "sparse", "device")


def normalize_round1_config(ec: "EngineConfig") -> "EngineConfig":
    """Pin a round-1 config to what the shard_map body actually runs.

    The kernel-impl knobs (``gains_impl`` on features/device, ``impl`` on
    the sparse graph builder) resolve here rather than inside the body, so
    provenance (``CoresetSelection.engine``, checkpoints, benches) records
    the real execution path.  On a TPU backend the Pallas kernels lower
    inside shard_map (DESIGN.md §6), so ``'auto'`` resolves to
    ``'pallas'`` and an explicit ``'pallas'`` is honored.  Elsewhere the
    kernels would run in interpret mode, which shard_map bodies do not
    support: the knobs pin to ``'jax'`` — silently for ``'auto'``, with a
    warning for an explicit ``'pallas'``.  All other knobs (q, stale_tol,
    tile_dtype, k, block sizes) are shard_map-safe and honored as given.
    """
    for attr in ("gains_impl", "impl"):
        val = getattr(ec, attr, "jax")
        resolved = resolve_impl(val, "jax")
        if resolved == "pallas" and interpret_default():
            if val == "pallas":
                warnings.warn(
                    f"distributed round 1 runs the jnp kernels off-TPU; "
                    f"{type(ec).__name__}({attr}='pallas') is pinned to "
                    "'jax' inside shard_map",
                    UserWarning,
                    stacklevel=3,
                )
            resolved = "jax"
        if resolved != val:
            ec = dataclasses.replace(ec, **{attr: resolved})
    return ec


def resolve_round1_config(
    local_engine, legacy_knobs: dict, n_local: int
) -> "EngineConfig":
    """The ONE resolve pipeline for round-1 engine configs.

    Shared by ``distributed_select``, ``local_then_merge``'s legacy
    surface, and ``CraigSelector.select_distributed`` so every entry point
    agrees: legacy strings/knobs shim-map with a ``DeprecationWarning``,
    ``'auto'`` resolves per shard pool size, engines with no
    shard_map-safe round-1 body (``lazy``, ``stochastic``) warn and fall
    back to the auto pick, and the result is pinned to what the body
    actually runs (``normalize_round1_config``).  Idempotent on an
    already-resolved config.
    """
    ec = resolve_distributed_engine(local_engine, legacy_knobs)
    if ec is None:  # 'auto': per-shard pool size drives the pick
        ec = auto_engine_config(max(1, n_local))
    elif ec.name not in ROUND1_ENGINES:
        replacement = auto_engine_config(max(1, n_local))
        warnings.warn(
            f"engine {ec.name!r} has no shard_map-safe round-1 body; "
            f"distributed round 1 uses {replacement!r} instead "
            f"(round-1 engines: {ROUND1_ENGINES})",
            UserWarning,
            stacklevel=3,
        )
        ec = replacement
    return normalize_round1_config(ec)


def make_distributed_extract(select_fn, mesh: Mesh, axis_name: str = "data"):
    """Data-parallel megabatch proxy extraction (DESIGN.md §9).

    Returns ``fn(params, batches) → (M·B, D)`` where ``batches`` is a
    megabatch pytree with leading dims (M, B, ...) and M divisible by the
    ``axis_name`` size: each shard ``lax.scan``s ``select_fn`` over its
    contiguous slice of the M batches, then features all-gather ON DEVICE
    (tiled, so contiguous leading-dim sharding restores pool order) — the
    pool sweep scales with the data axis and the gathered feature matrix
    never visits the host.  Params are replicated, like round-2 selection.

    The shard body is plain jnp (``select_fn`` must be shard_map-traceable
    — the train/select steps are; Pallas proxy kernels run in interpret
    mode off-TPU, same rule as ``normalize_round1_config``).
    """
    from repro.core.extract import make_scan_extract

    scan = make_scan_extract(select_fn)  # the ONE scan body (bit parity)

    def body(params, batches):
        return jax.lax.all_gather(scan(params, batches), axis_name, tiled=True)

    # check_vma=False here and below: the mapped bodies start scan carries
    # from constants, which the varying-manual-axes check rejects
    return jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P(), P(axis_name)), out_specs=P(),
            check_vma=False,
        )
    )


class DistributedSelection(NamedTuple):
    indices: jax.Array  # (r_final,) int32 — *global* pool indices
    weights: jax.Array  # (r_final,) float32 — Σ == n_global
    coverage: jax.Array  # () float32 — global L(S)


def check_candidate_counts(
    n_local: int,
    n_nodes: int,
    r_local: int,
    r_final: int,
    *,
    where: str = "distributed_select",
) -> None:
    """Static candidate-count invariants for a local-select → merge level.

    Greedy engines asked for a budget past their pool size silently select
    duplicates (the argmax of an all-(−inf) gains row re-picks element 0),
    which then poisons the merge round with padding artifacts — the audits
    below turn those silent truncation/duplication modes into errors at
    trace time, while every shape involved is still a Python int:

      * ``r_local ≤ n_local`` — a shard cannot yield more candidates than
        it has points;
      * ``n_nodes · r_local ≥ r_final`` — the merge must see at least
        ``r_final`` distinct candidates or the final greedy degenerates.
    """
    if r_final < 1 or r_local < 1:
        raise ValueError(
            f"{where}: budgets must be ≥ 1 (r_local={r_local}, "
            f"r_final={r_final})"
        )
    if r_local > n_local:
        raise ValueError(
            f"{where}: r_local={r_local} exceeds the shard pool size "
            f"n_local={n_local} — a greedy run past its pool size selects "
            f"duplicate candidates; lower r_local to ≤ {n_local} or use "
            "fewer/larger shards"
        )
    if n_nodes * r_local < r_final:
        raise ValueError(
            f"{where}: the merge round would see only "
            f"{n_nodes}×{r_local}={n_nodes * r_local} candidates, fewer "
            f"than r_final={r_final} — raise r_local to ≥ "
            f"{-(-r_final // n_nodes)} so the final greedy has enough "
            "distinct candidates"
        )


def check_even_shards(n: int, n_shards: int, *, where: str) -> None:
    """Ragged-shard audit: ``shard_map`` needs dim 0 divisible by the mesh
    axis, and a silent pad/truncate would fabricate or drop pool points —
    raise the informative error instead of jax's sharding complaint."""
    if n % n_shards != 0:
        raise ValueError(
            f"{where}: pool size n={n} is not divisible by the "
            f"{n_shards}-shard mesh axis — shard_map cannot split it "
            f"evenly and padding would fabricate phantom pool points.  "
            f"Trim the pool to {n - n % n_shards} or use "
            "repro.distributed.tree_select.tree_select_host, which "
            "supports ragged leaf shards"
        )


def _local_round(feats: jax.Array, r_local: int):
    """Round 1 on one shard: dense greedy FL over local features."""
    sq = jnp.sum(feats * feats, axis=-1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * feats @ feats.T
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    d_max = jnp.max(dist) + 1e-6
    res = fl.greedy_fl_matrix(d_max - dist, r_local)
    return res.indices, res.weights


def _local_round_sparse(feats: jax.Array, r_local: int, cfg: SparseConfig):
    """Round 1 on one shard via the top-k graph — O(n_local·k) memory.

    Selection runs on the sparsified objective; γ weights are then exact:
    every local point is assigned to its nearest selected medoid from
    features (an (n_local, r_local) distance block, never (n, n)).  The
    graph builder's ``impl`` arrives resolved for the platform
    (``normalize_round1_config``).
    """
    vals, idx = fl.topk_graph(feats, cfg.k, impl=cfg.impl, block_m=cfg.block_m)
    res = fl.greedy_fl_topk(vals, idx, r_local)
    sel = feats[res.indices]  # (r_local, d)
    sq = jnp.sum(feats * feats, axis=-1)
    sqs = jnp.sum(sel * sel, axis=-1)
    d2 = sq[:, None] + sqs[None, :] - 2.0 * feats @ sel.T
    _, weights = fl.assign_and_weights(jnp.maximum(d2, 0.0))
    return res.indices, weights


def _local_round_device(feats: jax.Array, r_local: int, cfg: DeviceConfig):
    """Round 1 on one shard via the device-resident fused greedy.

    Exact greedy selections (q=1 or stale_tol=1.0) without a dense
    (n_local, n_local) block; γ weights come straight from the engine's
    exact blocked assignment.  ``gains_impl`` arrives resolved for the
    platform (``normalize_round1_config``): the fused Pallas sweep on TPU,
    the jnp sweep elsewhere.
    """
    res = fl.greedy_fl_device(
        feats, r_local, q=cfg.q, gains_impl=cfg.gains_impl,
        stale_tol=cfg.stale_tol, tile_dtype=cfg.tile_dtype,
        block_n=cfg.block_n, block_m=cfg.block_m,
    )
    return res.indices, res.weights


def _local_round_features(feats: jax.Array, r_local: int, cfg: FeaturesConfig):
    """Round 1 on one shard via the matrix-free blocked greedy (§3.4);
    ``gains_impl`` arrives resolved for the platform
    (``normalize_round1_config``)."""
    res = fl.greedy_fl_features(
        feats, r_local, gains_impl=cfg.gains_impl, block_n=cfg.block_n
    )
    return res.indices, res.weights


def leaf_round(feats: jax.Array, r_local: int, engine_config: "EngineConfig | None"):
    """One local selection: ``r_local`` candidates + local γ from ``feats``.

    The level-reusable round-1 body (DESIGN.md §6): ``local_then_merge``'s
    round 1 and every leaf of the hierarchical tree
    (``repro.distributed.tree_select``) dispatch through here, so a new
    shard_map-safe engine extends both paths at once.  ``engine_config``
    must be one of ``ROUND1_ENGINES`` (already normalized via
    ``normalize_round1_config``); ``None`` means the pre-registry default,
    the dense matrix round.

    Returns ``(local_idx (r_local,), local_w (r_local,))`` with
    Σ local_w == n_local.
    """
    ec = engine_config if engine_config is not None else MatrixConfig()
    if isinstance(ec, SparseConfig):
        return _local_round_sparse(feats, r_local, ec)
    if isinstance(ec, DeviceConfig):
        return _local_round_device(feats, r_local, ec)
    if isinstance(ec, FeaturesConfig):
        return _local_round_features(feats, r_local, ec)
    if isinstance(ec, MatrixConfig):
        return _local_round(feats, r_local)
    raise ValueError(
        f"engine {ec.name!r} has no shard_map-safe round-1 body; "
        f"round-1 engines: {ROUND1_ENGINES}"
    )


def merge_round(cand_feats: jax.Array, cand_w: jax.Array, budget: int):
    """One merge level: weighted greedy FL over a gathered candidate union.

    Level-reusable (DESIGN.md §6): the two-round path calls it once at the
    root; the hierarchical tree calls it at every non-leaf node with that
    node's children's candidates.  Each candidate counts γ_c points, so
    maximizing the weighted objective keeps the merged set representative
    of the *points* below it, not just of the candidate vectors.

    Returns the full weighted ``FLResult``: ``indices`` are positions into
    the candidate union, ``weights`` are the re-aggregated γ (every
    dropped candidate's mass moves to its nearest kept medoid —
    Σ weights == Σ cand_w, so γ conservation holds level over level).
    """
    sq = jnp.sum(cand_feats * cand_feats, axis=-1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * cand_feats @ cand_feats.T
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    d_max = jnp.max(dist) + 1e-6
    return fl.greedy_fl_matrix(d_max - dist, budget, point_weights=cand_w)


def local_then_merge(
    feats_sharded: jax.Array,
    r_local: int,
    r_final: int,
    axis_name: str = "data",
    engine_config: EngineConfig | None = None,
    squared_coverage: bool = False,
    local_engine: str | None = None,
    **legacy_knobs,
):
    """shard_map body: runs on one shard with a mapped ``axis_name``.

    Args:
      feats_sharded: (n_local, d) this shard's proxy features (fp32).
      r_local: round-1 budget per shard.
      r_final: final global budget.
      engine_config: typed round-1 engine config (``ROUND1_ENGINES``);
        None means ``MatrixConfig()``.
      squared_coverage: report L(S) as Σ min ‖x−m‖²/2 instead of
        Σ min ‖x−m‖ — on unit-normalized pools that is Σ min (1 − cos θ),
        keeping cosine coverage units identical to the local engines'.
      local_engine / legacy flat knob kwargs: the pre-registry surface;
        shim-mapped with a ``DeprecationWarning``
        (``engines.legacy.resolve_distributed_engine``).
    Returns:
      (global_indices (r_final,), weights (r_final,), coverage ()).
    """
    if local_engine is not None or legacy_knobs:
        if engine_config is not None:
            raise TypeError(
                "pass engine_config or the legacy local_engine surface, "
                "not both"
            )
        engine_config = resolve_round1_config(
            # the pre-registry default was the dense matrix round 1
            "matrix" if local_engine is None else local_engine,
            legacy_knobs,
            feats_sharded.shape[0],
        )
    ec = engine_config if engine_config is not None else MatrixConfig()
    n_local, _ = feats_sharded.shape
    n_shards = jax.lax.axis_size(axis_name)
    check_candidate_counts(
        n_local, n_shards, r_local, r_final, where="local_then_merge"
    )
    shard_id = jax.lax.axis_index(axis_name)

    local_idx, local_w = leaf_round(feats_sharded, r_local, ec)
    local_global_idx = shard_id * n_local + local_idx

    # Gather candidate features / weights / global ids from all shards.
    cand_feats = jax.lax.all_gather(
        feats_sharded[local_idx], axis_name, tiled=True
    )  # (n_shards·r_local, d)
    cand_w = jax.lax.all_gather(local_w, axis_name, tiled=True)
    cand_gidx = jax.lax.all_gather(local_global_idx, axis_name, tiled=True)

    sel_pos = merge_round(cand_feats, cand_w, r_final).indices  # replicated
    sel_feats = cand_feats[sel_pos]  # (r_final, d)
    sel_gidx = cand_gidx[sel_pos]

    # Exact global re-weighting: assign local points to final medoids.
    sqx = jnp.sum(feats_sharded * feats_sharded, axis=-1)
    sqm = jnp.sum(sel_feats * sel_feats, axis=-1)
    d2 = sqx[:, None] + sqm[None, :] - 2.0 * feats_sharded @ sel_feats.T
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))  # (n_local, r_final)
    assign = jnp.argmin(dist, axis=1)
    local_counts = jnp.zeros((r_final,), jnp.float32).at[assign].add(1.0)
    weights = jax.lax.psum(local_counts, axis_name)
    min_dist = jnp.min(dist, axis=1)
    residual = jnp.square(min_dist) / 2.0 if squared_coverage else min_dist
    coverage = jax.lax.psum(jnp.sum(residual), axis_name)
    return sel_gidx.astype(jnp.int32), weights, coverage


def distributed_select(
    feats: jax.Array,
    mesh: Mesh,
    r_local: int,
    r_final: int,
    axis_name: str = "data",
    local_engine: str | EngineConfig = "auto",
    squared_coverage: bool = False,
    **legacy_knobs,
) -> DistributedSelection:
    """Run two-round distributed selection over ``mesh[axis_name]``.

    ``feats`` is (n, d) with n divisible by the axis size; it is sharded over
    the first dimension.  Output indices/weights are fully replicated.

    ``local_engine`` picks the round-1 body: a typed ``EngineConfig``
    (``MatrixConfig``/``FeaturesConfig``/``SparseConfig``/``DeviceConfig``),
    or ``'auto'`` (default) to resolve it per shard pool size via
    ``engines.auto_engine_config``.  Legacy engine strings plus flat knob
    kwargs still work through the deprecation shim
    (``engines.legacy.resolve_distributed_engine``) and warn.
    """
    n_shards = int(mesh.shape[axis_name])
    check_even_shards(feats.shape[0], n_shards, where="distributed_select")
    n_local = feats.shape[0] // n_shards
    check_candidate_counts(
        n_local, n_shards, r_local, r_final, where="distributed_select"
    )
    engine_config = resolve_round1_config(local_engine, legacy_knobs, n_local)
    body = partial(
        local_then_merge, r_local=r_local, r_final=r_final,
        axis_name=axis_name, engine_config=engine_config,
        squared_coverage=squared_coverage,
    )
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P(axis_name, None),),
        out_specs=(P(), P(), P()), check_vma=False,
    )
    idx, w, cov = fn(feats.astype(jnp.float32))
    return DistributedSelection(idx, w, cov)
