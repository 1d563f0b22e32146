"""Hierarchical (tree) distributed selection with compressed candidate
collectives (DESIGN.md §6).

Two-round selection (``core/distributed.local_then_merge``) is the depth-1
special case of a leaf→merge→root tree: leaves select ``r_local``
candidates with any round-1 engine, every non-leaf node merges its
children's candidate sets with one bounded weighted re-greedy pass
(``merge_round``), and the root runs the final exact weighted round.  The
tree is what takes selection past one host's mesh: leaf fan-in happens
close to the data (ICI / intra-host), and only ``r_node``-sized candidate
sets cross the slow axes toward the root.

The bandwidth wall is the candidate-feature gather at each non-leaf
level.  Every gather here ships int8 per-row block-quantized payloads
(``distributed.compression.quantize_rows_int8`` — ~4x fewer bytes than
fp32, one-shot so no error feedback), with ``compress='none'`` as the
fp32 escape hatch; ``bench_tree_select`` gates the compressed tree at
≥ 0.95 of the uncompressed tree's objective.

Three drivers share the same level math (``leaf_round``/``merge_round``
from ``core.distributed`` — the N-level generalization of the two-round
refactor), so their selections agree bit for bit on the same pool:

* :func:`tree_select_host` — single-process orchestration over a global
  (n, d) pool.  Supports ragged leaf shards, needs no mesh; the reference
  implementation and the tier-1 test surface.
* :func:`tree_select_mesh` — one ``shard_map`` program over an N-axis
  mesh (one axis per tree level, built by :func:`tree_mesh`); merges run
  replicated within each subtree exactly like the two-round path's
  replicated merge.  Spans processes wherever XLA's cross-process
  collectives exist (TPU/GPU pods via ``jax.distributed``); on CPU it
  runs single-process over simulated devices.
* ``tree_select_processes`` (``repro.distributed.process_tree``) — one
  process per leaf over the ``jax.distributed`` KV store, the
  multi-process CPU path (XLA CPU has no cross-process collectives); the
  tier-2 CI lane drives it end to end with 2 real processes.

Guarantee shape: each merge level is a GreeDi-style composition — greedy
over the union of children's (1−1/e)-approximate candidate sets, weighted
by the γ mass each candidate represents — so the worst-case factor decays
geometrically with depth but the empirical loss is small (the CREST
observation: selection from pool *subsets* loses little), and the final
exact re-weighting pass keeps Σγ = n and coverage exact over the whole
pool regardless of depth.  ``tests/test_selection_properties.py`` gates
the objective ratio vs lazy greedy across depths and fan-outs.
"""
from __future__ import annotations

import dataclasses
from typing import ClassVar, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.distributed import (
    check_candidate_counts,
    check_even_shards,
    leaf_round,
    merge_round,
    resolve_round1_config,
)
from repro.core.engines import EngineConfig
from repro.distributed.compression import (
    dequantize_rows_int8,
    quantize_rows_int8,
)

__all__ = [
    "WIRE_MODES",
    "TreeTopology",
    "TreeSelectConfig",
    "TreeSelection",
    "tree_mesh",
    "tree_select_host",
    "tree_select_mesh",
    "wire_bytes_plan",
    "default_r_node",
]

WIRE_MODES = ("int8", "none")


# ---------------------------------------------------------------------------
# Topology
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TreeTopology:
    """A leaf→root merge tree described by per-level fan-outs.

    ``fanouts[0]`` leaves merge into each level-1 node, ``fanouts[1]``
    level-1 nodes merge into each level-2 node, …, and the last fan-out
    merges into the single root.  ``n_leaves = Π fanouts`` and
    ``depth = len(fanouts)`` merge levels; ``fanouts=(n_shards,)`` is
    exactly the existing two-round path (one merge at the root).
    """

    fanouts: tuple[int, ...]

    def __post_init__(self):
        fo = tuple(int(f) for f in self.fanouts)
        object.__setattr__(self, "fanouts", fo)
        if not fo:
            raise ValueError("TreeTopology needs at least one fan-out level")
        if any(f < 1 for f in fo):
            raise ValueError(f"fan-outs must be ≥ 1, got {fo}")
        if all(f == 1 for f in fo):
            raise ValueError(
                f"degenerate topology {fo}: at least one fan-out must be "
                "> 1 (a chain of 1-child merges re-greedies the same "
                "candidate set over and over)"
            )

    @property
    def depth(self) -> int:
        """Number of merge levels (leaves excluded)."""
        return len(self.fanouts)

    @property
    def n_leaves(self) -> int:
        n = 1
        for f in self.fanouts:
            n *= f
        return n

    def nodes_at(self, level: int) -> int:
        """Node count after ``level`` merges (level 0 = leaves)."""
        n = self.n_leaves
        for f in self.fanouts[:level]:
            n //= f
        return n

    @property
    def axis_names(self) -> tuple[str, ...]:
        """Mesh axis per merge level, leaf-adjacent first."""
        return tuple(f"lvl{i}" for i in range(self.depth))

    def to_dict(self) -> dict:
        return {"fanouts": list(self.fanouts)}

    @classmethod
    def from_dict(cls, d: dict) -> "TreeTopology":
        return cls(fanouts=tuple(d["fanouts"]))


@dataclasses.dataclass(frozen=True)
class TreeSelectConfig(EngineConfig):
    """Provenance record for tree-orchestrated selections.

    Not a registered ``SelectionEngine`` — the tree is an orchestration
    layer over the round-1 engines, not a greedy maximizer itself — but it
    speaks the ``EngineConfig`` dict protocol so ``CoresetSelection.engine``
    / sampler checkpoints round-trip it like any engine provenance
    (``engine_config_from_dict`` dispatches ``name == 'tree'`` here).

    Attributes:
      fanouts: the merge-tree shape (``TreeTopology.fanouts``).
      compress: candidate wire mode — ``'int8'`` (per-row block-quantized
        gathers) or ``'none'`` (fp32 escape hatch).
      local: the resolved *leaf* engine's ``EngineConfig.to_dict()`` —
        nested verbatim so the full execution path is recorded.
      degraded: True when the process driver completed under quorum
        degradation (DESIGN.md §12) — one or more leaves died and the
        selection covers only the surviving shards.
      missing_pids: the dead leaves' process indices (empty when clean).
      quorum: achieved surviving-leaf fraction (1.0 when clean).
    """

    name: ClassVar[str] = "tree"
    fanouts: tuple[int, ...] = (2,)
    compress: str = "int8"
    local: dict | None = None
    degraded: bool = False
    missing_pids: tuple[int, ...] = ()
    quorum: float = 1.0

    def __post_init__(self):
        object.__setattr__(self, "fanouts", tuple(int(f) for f in self.fanouts))
        object.__setattr__(
            self, "missing_pids", tuple(int(p) for p in self.missing_pids)
        )
        if self.compress not in WIRE_MODES:
            raise ValueError(
                f"compress={self.compress!r} is not a wire mode; "
                f"expected one of {WIRE_MODES}"
            )

    @property
    def topology(self) -> TreeTopology:
        return TreeTopology(self.fanouts)


# ---------------------------------------------------------------------------
# Candidate wire
# ---------------------------------------------------------------------------


def _through_wire(feats: jax.Array, compress: str) -> jax.Array:
    """What the receiving merge node sees of a shipped candidate matrix."""
    if compress == "int8":
        return dequantize_rows_int8(*quantize_rows_int8(feats))
    if compress == "none":
        return feats
    raise ValueError(
        f"compress={compress!r} is not a wire mode; expected one of "
        f"{WIRE_MODES}"
    )


def _payload_bytes(r: int, d: int, compress: str) -> int:
    """Wire bytes for one (r, d) candidate-feature payload."""
    if compress == "int8":
        return r * d + 4 * r  # int8 payload + fp32 per-row scales
    return 4 * r * d


def wire_bytes_plan(
    topology: TreeTopology,
    r_local: int,
    r_node: int,
    d: int,
    compress: str,
) -> dict:
    """Static bytes-on-wire accounting for one tree selection.

    Counts the candidate-FEATURE payloads every non-leaf gather ships
    (γ weights and global ids are identical small fp32/int32 sidecars in
    both modes and are excluded, like the scales' fp32 sidecar is
    *included* — it only exists in int8 mode).  Per level: every child
    node ships its candidate matrix once.
    """
    if compress not in WIRE_MODES:
        raise ValueError(
            f"compress={compress!r} is not a wire mode; expected one of "
            f"{WIRE_MODES}"
        )
    per_level = []
    r = r_local
    for level, fanout in enumerate(topology.fanouts):
        n_children = topology.nodes_at(level)  # shipping nodes at this level
        per_level.append(
            {
                "level": level + 1,
                "children": n_children,
                "r_child": r,
                "bytes": n_children * _payload_bytes(r, d, compress),
                "fp32_bytes": n_children * _payload_bytes(r, d, "none"),
            }
        )
        r = min(r_node, fanout * r)  # what each merged node forwards
    total = sum(lv["bytes"] for lv in per_level)
    fp32_total = sum(lv["fp32_bytes"] for lv in per_level)
    return {
        "compress": compress,
        "per_level": per_level,
        "gathered_feature_bytes": total,
        "fp32_feature_bytes": fp32_total,
        "reduction": fp32_total / max(total, 1),
    }


def default_r_node(r_local: int, r_final: int) -> int:
    """Intermediate merge budget: every non-root node forwards this many.

    ``max(r_local, r_final)`` keeps at least the final budget's worth of
    candidates alive at every level (the GreeDi composition needs ≥
    ``r_final`` distinct survivors per merge to preserve its factor) while
    never *expanding* a level's output past what a bigger leaf round would
    have shipped anyway.
    """
    return max(int(r_local), int(r_final))


class TreeSelection(NamedTuple):
    """Result of a hierarchical selection (same contract at any depth).

    Attributes:
      indices: (r_final,) int32 — global pool indices.
      weights: (r_final,) float32 — exact global γ, Σ == n.
      coverage: () float32 — exact global L(S) over the whole pool.
      wire: static bytes-on-wire accounting (:func:`wire_bytes_plan`).
      health: degradation record from the process driver (DESIGN.md §12):
        ``{'degraded', 'missing_pids', 'quorum', 'min_quorum', 'r_final',
        'level_deadline_s'}``.  None from the host/mesh drivers (no
        process failure domain), and under degradation ``r_final``/Σγ
        cover the *surviving* shards only.
    """

    indices: jax.Array
    weights: jax.Array
    coverage: jax.Array
    wire: dict
    health: dict | None = None


# ---------------------------------------------------------------------------
# Shared validation
# ---------------------------------------------------------------------------


def _check_tree_counts(
    leaf_sizes: list[int],
    topology: TreeTopology,
    r_local: int,
    r_node: int,
    r_final: int,
    *,
    where: str,
) -> None:
    """Candidate-count invariants at every level of the tree (the N-level
    generalization of ``check_candidate_counts``)."""
    if r_node < 1:
        raise ValueError(f"{where}: r_node={r_node} must be ≥ 1")
    depth = topology.depth
    level1_budget = r_final if depth == 1 else min(
        r_node, topology.fanouts[0] * r_local
    )
    check_candidate_counts(
        min(leaf_sizes), topology.fanouts[0], r_local, level1_budget,
        where=f"{where} (level 1)",
    )
    r = r_local
    for level, fanout in enumerate(topology.fanouts):
        budget = r_final if level == depth - 1 else min(r_node, fanout * r)
        if fanout * r < budget:
            raise ValueError(
                f"{where}: level {level + 1} merges only {fanout}×{r}="
                f"{fanout * r} candidates, fewer than its budget "
                f"{budget} — raise r_local/r_node or lower r_final"
            )
        r = budget


# ---------------------------------------------------------------------------
# Host driver
# ---------------------------------------------------------------------------


def tree_select_host(
    feats: jax.Array,
    topology: TreeTopology,
    r_local: int,
    r_final: int,
    *,
    r_node: int | None = None,
    local_engine: str | EngineConfig = "auto",
    compress: str = "int8",
    squared_coverage: bool = False,
) -> TreeSelection:
    """Single-process hierarchical selection over a global (n, d) pool.

    The pool splits into ``topology.n_leaves`` contiguous leaf shards
    (ragged splits supported — ``np.array_split`` semantics, no padding or
    truncation), each leaf runs :func:`leaf_round` with the resolved
    engine, and candidate sets merge up the tree with every non-leaf
    gather passed through the ``compress`` wire.  The final re-weighting
    assigns every pool point to its nearest final medoid, so ``weights``
    and ``coverage`` are exact regardless of depth or compression.

    This is the reference driver: :func:`tree_select_mesh` and the
    process driver produce bit-identical selections on the same pool.
    """
    if compress not in WIRE_MODES:
        raise ValueError(
            f"compress={compress!r} is not a wire mode; expected one of "
            f"{WIRE_MODES}"
        )
    feats = jnp.asarray(feats, jnp.float32)
    n, d = feats.shape
    n_leaves = topology.n_leaves
    if n_leaves > n:
        raise ValueError(
            f"tree_select_host: topology has {n_leaves} leaves but the "
            f"pool only has {n} points"
        )
    r_node = default_r_node(r_local, r_final) if r_node is None else int(r_node)
    leaf_slices = np.array_split(np.arange(n, dtype=np.int64), n_leaves)
    _check_tree_counts(
        [len(s) for s in leaf_slices], topology, r_local, r_node, r_final,
        where="tree_select_host",
    )
    engine_cfg = resolve_round1_config(
        local_engine, {}, min(len(s) for s in leaf_slices)
    )

    # Leaves: local selection, candidates carry exact local features.
    nodes = []  # (cand_feats, cand_w, cand_gidx) per live node, leaf order
    for sl in leaf_slices:
        leaf_feats = feats[jnp.asarray(sl)]
        idx, w = leaf_round(leaf_feats, r_local, engine_cfg)
        nodes.append((leaf_feats[idx], w, jnp.asarray(sl)[idx]))

    # Merge levels: children ship through the wire, parent re-greedies.
    for level, fanout in enumerate(topology.fanouts):
        budget = r_final if level == topology.depth - 1 else min(
            r_node, fanout * nodes[0][0].shape[0]
        )
        merged = []
        for lo in range(0, len(nodes), fanout):
            group = nodes[lo : lo + fanout]
            cand_feats = jnp.concatenate(
                [_through_wire(f, compress) for f, _, _ in group]
            )
            cand_w = jnp.concatenate([w for _, w, _ in group])
            cand_gidx = jnp.concatenate([g for _, _, g in group])
            res = merge_round(cand_feats, cand_w, budget)
            merged.append(
                (cand_feats[res.indices], res.weights, cand_gidx[res.indices])
            )
        nodes = merged
    (root_feats, _, root_gidx), = nodes

    # Exact global re-weighting + coverage, leaf order (matches the mesh
    # driver's psum over shards up to float-sum association).
    sqm = jnp.sum(root_feats * root_feats, axis=-1)
    counts = jnp.zeros((r_final,), jnp.float32)
    coverage = jnp.zeros((), jnp.float32)
    for sl in leaf_slices:
        leaf_feats = feats[jnp.asarray(sl)]
        sqx = jnp.sum(leaf_feats * leaf_feats, axis=-1)
        d2 = sqx[:, None] + sqm[None, :] - 2.0 * leaf_feats @ root_feats.T
        dist = jnp.sqrt(jnp.maximum(d2, 0.0))
        assign = jnp.argmin(dist, axis=1)
        counts = counts.at[assign].add(1.0)
        min_dist = jnp.min(dist, axis=1)
        residual = (
            jnp.square(min_dist) / 2.0 if squared_coverage else min_dist
        )
        coverage = coverage + jnp.sum(residual)
    wire = wire_bytes_plan(topology, r_local, r_node, d, compress)
    return TreeSelection(
        root_gidx.astype(jnp.int32), counts, coverage, wire
    )


# ---------------------------------------------------------------------------
# Mesh driver (one shard_map program, one axis per level)
# ---------------------------------------------------------------------------


def tree_mesh(topology: TreeTopology, devices=None):
    """Mesh with one axis per merge level: shape ``reversed(fanouts)``,
    axes ``('lvl{L-1}', …, 'lvl0')`` — ``lvl0`` minor, so the leaf-adjacent
    gathers group the closest devices.  Needs exactly ``n_leaves`` devices
    (pass ``devices`` to sub-select; defaults to ``jax.devices()``, which
    spans processes under ``jax.distributed``)."""
    if devices is None:
        devices = jax.devices()
    if len(devices) != topology.n_leaves:
        raise ValueError(
            f"tree_mesh: topology has {topology.n_leaves} leaves but "
            f"{len(devices)} devices are available — fan-outs must "
            "multiply to the device count"
        )
    shape = tuple(reversed(topology.fanouts))
    axes = tuple(reversed(topology.axis_names))
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def _tree_body(
    feats_local: jax.Array,
    topology: TreeTopology,
    r_local: int,
    r_node: int,
    r_final: int,
    engine_cfg: EngineConfig,
    compress: str,
    squared_coverage: bool,
):
    """shard_map body: one leaf per device, merges replicated per subtree.

    Gathering over axis ``lvl{l}`` collects exactly the ``fanouts[l]``
    distinct child nodes of this device's level-``l+1`` ancestor (all
    devices below a child carry identical replicated copies of its
    candidate set, so any fixed coordinate on the lower axes picks one
    representative) — the same replicated-merge design as the two-round
    path, generalized level over level.
    """
    n_local, _ = feats_local.shape
    axes = topology.axis_names

    # global leaf id from the axis coordinates, major → minor
    leaf_id = jnp.zeros((), jnp.int32)
    for ax in reversed(axes):
        leaf_id = leaf_id * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)

    local_idx, local_w = leaf_round(feats_local, r_local, engine_cfg)
    cand_feats = feats_local[local_idx]
    cand_w = local_w
    cand_gidx = leaf_id * n_local + local_idx

    for level, ax in enumerate(axes):
        fanout = topology.fanouts[level]
        # candidate features ship through the wire: int8 payload + fp32
        # per-row scales gathered, dequantized on arrival
        if compress == "int8":
            q, scale = quantize_rows_int8(cand_feats)
            q_g = jax.lax.all_gather(q, ax, tiled=True)
            s_g = jax.lax.all_gather(scale, ax, tiled=True)
            gathered_feats = dequantize_rows_int8(q_g, s_g)
        else:
            gathered_feats = jax.lax.all_gather(cand_feats, ax, tiled=True)
        gathered_w = jax.lax.all_gather(cand_w, ax, tiled=True)
        gathered_gidx = jax.lax.all_gather(cand_gidx, ax, tiled=True)

        budget = r_final if level == topology.depth - 1 else min(
            r_node, fanout * cand_feats.shape[0]
        )
        res = merge_round(gathered_feats, gathered_w, budget)
        cand_feats = gathered_feats[res.indices]
        cand_w = res.weights
        cand_gidx = gathered_gidx[res.indices]

    # Exact global re-weighting: assign local points to the final medoids
    # (replicated on every device), psum counts/coverage over every axis.
    sqx = jnp.sum(feats_local * feats_local, axis=-1)
    sqm = jnp.sum(cand_feats * cand_feats, axis=-1)
    d2 = sqx[:, None] + sqm[None, :] - 2.0 * feats_local @ cand_feats.T
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    assign = jnp.argmin(dist, axis=1)
    local_counts = jnp.zeros((r_final,), jnp.float32).at[assign].add(1.0)
    weights = jax.lax.psum(local_counts, axes)
    min_dist = jnp.min(dist, axis=1)
    residual = jnp.square(min_dist) / 2.0 if squared_coverage else min_dist
    coverage = jax.lax.psum(jnp.sum(residual), axes)
    return cand_gidx.astype(jnp.int32), weights, coverage


def tree_select_mesh(
    feats: jax.Array,
    mesh,
    topology: TreeTopology,
    r_local: int,
    r_final: int,
    *,
    r_node: int | None = None,
    local_engine: str | EngineConfig = "auto",
    compress: str = "int8",
    squared_coverage: bool = False,
) -> TreeSelection:
    """Hierarchical selection as ONE shard_map program over ``mesh``.

    ``mesh`` must carry the topology's level axes (build it with
    :func:`tree_mesh`); ``feats`` is the global (n, d) pool, n divisible
    by ``n_leaves``.  Each device is a leaf; outputs are fully replicated.
    Where XLA's collectives span processes (TPU/GPU pods bootstrapped via
    ``launch.tree.initialize_distributed``) this is the multi-host path;
    CPU multi-process runs use ``process_tree.tree_select_processes``.
    """
    if compress not in WIRE_MODES:
        raise ValueError(
            f"compress={compress!r} is not a wire mode; expected one of "
            f"{WIRE_MODES}"
        )
    for ax in topology.axis_names:
        if ax not in mesh.shape:
            raise ValueError(
                f"tree_select_mesh: mesh axes {tuple(mesh.shape)} are "
                f"missing level axis {ax!r} — build the mesh with "
                "tree_mesh(topology)"
            )
    feats = jnp.asarray(feats, jnp.float32)
    n, d = feats.shape
    n_leaves = topology.n_leaves
    check_even_shards(n, n_leaves, where="tree_select_mesh")
    n_local = n // n_leaves
    r_node = default_r_node(r_local, r_final) if r_node is None else int(r_node)
    _check_tree_counts(
        [n_local], topology, r_local, r_node, r_final,
        where="tree_select_mesh",
    )
    engine_cfg = resolve_round1_config(local_engine, {}, n_local)

    def body(x):
        return _tree_body(
            x, topology, r_local, r_node, r_final, engine_cfg, compress,
            squared_coverage,
        )

    # dim 0 sharded over every level axis, major → minor: global index
    # order is (lvl{L-1}, …, lvl0) row-major, matching the body's leaf_id
    flat_axes = tuple(reversed(topology.axis_names))
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P(flat_axes, None),),
        out_specs=(P(), P(), P()), check_vma=False,
    )
    idx, w, cov = fn(feats)
    wire = wire_bytes_plan(topology, r_local, r_node, d, compress)
    return TreeSelection(idx, w, cov, wire)
