"""Multi-process entry point for hierarchical tree selection.

Bootstraps the ``jax.distributed`` coordination service (process mesh)
and runs one tree selection over a synthetic clustered pool — the
smallest end-to-end exercise of the multi-host path, and what the tier-2
multi-process CI lane launches (2 real processes on CPU).

Launch line (one per process)::

    PYTHONPATH=src python -m repro.launch.tree \
        --coordinator 127.0.0.1:8476 --num-processes 2 --process-id $i \
        --fanouts 2 --n 256 --d 32 --r-local 8 --r-final 10

On a TPU host, one process drives all local chips with ``--driver mesh``
(the single-program ``tree_select_mesh`` over the device mesh; across
hosts, one such process per host).  ``--driver processes`` (one JAX
process per leaf, ``tree_select_processes`` over the KV-store wire) is
for CPU fleets only: XLA CPU has no cross-process collectives, and on a
TPU host a second process cannot reach a chip the first one holds.
"""
from __future__ import annotations

import argparse
import json
import os

__all__ = ["initialize_distributed", "make_tree_mesh", "main"]


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """``jax.distributed.initialize`` with explicit-args-else-environment
    semantics (env: ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
    ``JAX_PROCESS_ID``, or a cloud auto-detect where jax supports one).
    Must run before any other jax call in every process; idempotence is
    delegated to jax (re-initialization raises there)."""
    import jax

    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)


def make_tree_mesh(fanouts: tuple[int, ...]):
    """Level-axis mesh over ALL devices (spans processes under
    ``jax.distributed``) for ``tree_select_mesh``."""
    from repro.distributed.tree_select import TreeTopology, tree_mesh

    return tree_mesh(TreeTopology(fanouts))


def _synthetic_pool(n: int, d: int, seed: int):
    """Deterministic clustered pool — identical on every process (same
    seed), so each process can slice its own shard without any I/O."""
    import jax
    import jax.numpy as jnp

    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 3)
    centers = jax.random.normal(k0, (8, d)) * 5.0
    assign = jax.random.randint(k1, (n,), 0, 8)
    return centers[assign] + jax.random.normal(k2, (n, d)) * 0.3


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--coordinator", default=None,
                   help="host:port of process 0 (else env/auto-detect)")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--fanouts", default="2",
                   help="comma-separated leaf→root fan-outs, e.g. 4,2")
    p.add_argument("--n", type=int, default=256)
    p.add_argument("--d", type=int, default=32)
    p.add_argument("--r-local", type=int, default=8)
    p.add_argument("--r-final", type=int, default=10)
    p.add_argument("--compress", default="int8", choices=("int8", "none"))
    p.add_argument("--driver", default="processes",
                   choices=("processes", "mesh"))
    p.add_argument("--seed", type=int, default=0)
    # liveness/degradation knobs (processes driver; DESIGN.md §12)
    p.add_argument("--level-deadline-s", type=float, default=None,
                   help="per-level wait before a child subtree is declared "
                        "dead (default: $REPRO_KV_TIMEOUT_MS, 300 s)")
    p.add_argument("--min-quorum", type=float, default=1.0,
                   help="minimum surviving-leaf fraction; below it the "
                        "selection fails instead of degrading")
    p.add_argument("--heartbeat-interval-s", type=float, default=0.5)
    p.add_argument("--heartbeat-grace-s", type=float, default=5.0)
    args = p.parse_args(argv)

    from repro.launch.cache import init_compile_cache

    init_compile_cache()

    # chaos lanes arm per-process faults via $REPRO_FAULT_PLAN — installed
    # before any selection work so injected kills hit the intended site
    from repro.faults import install_from_env

    install_from_env()

    initialize_distributed(args.coordinator, args.num_processes, args.process_id)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed.tree_select import TreeTopology

    topology = TreeTopology(tuple(int(f) for f in args.fanouts.split(",")))
    feats = _synthetic_pool(args.n, args.d, args.seed)

    if args.driver == "mesh":
        from repro.distributed.tree_select import tree_mesh, tree_select_mesh

        sel = tree_select_mesh(
            feats, tree_mesh(topology), topology, args.r_local, args.r_final,
            compress=args.compress,
        )
    else:
        from repro.distributed.process_tree import (
            HealthConfig,
            tree_select_processes,
        )

        pid, nproc = jax.process_index(), jax.process_count()
        shard = np.array_split(np.arange(args.n), nproc)[pid]
        sel = tree_select_processes(
            feats[jnp.asarray(shard)], topology, args.r_local, args.r_final,
            compress=args.compress,
            health=HealthConfig(
                level_deadline_s=args.level_deadline_s,
                min_quorum=args.min_quorum,
                heartbeat_interval_s=args.heartbeat_interval_s,
                heartbeat_grace_s=args.heartbeat_grace_s,
            ),
        )

    record = {
        "process": int(jax.process_index()),
        "driver": args.driver,
        "fanouts": list(topology.fanouts),
        "compress": args.compress,
        "indices": np.asarray(sel.indices).tolist(),
        "r_final": int(np.asarray(sel.indices).shape[0]),
        "weight_sum": float(jnp.sum(sel.weights)),
        "coverage": float(sel.coverage),
        "wire_bytes": sel.wire["gathered_feature_bytes"],
        "wire_reduction": round(sel.wire["reduction"], 3),
        "health": sel.health,
    }
    print("TREE_SELECT_RESULT " + json.dumps(record), flush=True)

    if record["health"] and record["health"].get("degraded"):
        # the jax.distributed shutdown barrier needs EVERY task to check
        # in, and a degraded run by definition has dead tasks — skip the
        # barrier (and the eventual missed-heartbeat abort) instead of
        # blocking the survivors on peers that can never arrive
        if int(jax.process_index()) == 0:
            # pid 0 hosts the coordination service; closing it while other
            # survivors still run aborts their error-polling threads, so
            # the leader exits last (survivors only have local printing
            # left after the selection returns — seconds, not deadlines)
            import time

            time.sleep(5.0)
        os._exit(0)


if __name__ == "__main__":
    main()
