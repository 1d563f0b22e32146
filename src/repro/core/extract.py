"""Pipelined, device-resident proxy extraction (DESIGN.md §9).

CRAIG's refresh cost is extraction + selection (paper §3.4: the proxy is the
gradient of the loss w.r.t. the last layer's input, recomputed every refresh
because deep-net gradients drift with w).  The selection half has engine
tiers (DESIGN.md §3); this module is the extraction half: the sweep that
runs ``select_step`` over the candidate pool.  A naive host loop — one
jitted batch per dispatch, blocking on ``np.asarray`` per batch, features
bounced device→host→device before the jit-safe engines re-upload them — is
O(n_pool/B) python dispatches of pure overhead, and at scale the pool sweep
(not the greedy) dominates coreset cost (CREST, PAPERS.md).

``ProxyExtractor`` turns the sweep into a pipelined device program:

  * **Megabatch scan** — ``megabatch`` pool batches fold into ONE
    ``lax.scan`` dispatch over fixed-shape (M, B, ...) batches.  The tail is
    handled with a validity mask, not pad-then-drop: the last batch's index
    slots wrap around the pool (so batch *contents* match the per-batch
    baseline bit-for-bit), all padding lands at the flattened tail, and the
    invalid rows are cut with a device-side slice — the feature matrix never
    visits the host to be trimmed.
  * **Double-buffered host prefetch** — host batch assembly
    (``dataset.batch``) runs on a background thread
    (:class:`repro.data.pipeline.Prefetcher`, depth 2) so megabatch m+1 is
    assembled while the device runs megabatch m.
  * **Data-parallel shard_map** — with a ``mesh``, the (M, B, ...) batches
    shard over ``axis_name``, every shard scans its slice, and features
    all-gather ON DEVICE (``core.distributed.make_distributed_extract``) —
    the pool sweep scales with the data axis like the train step does.
  * **Device-resident handoff** — ``extract(..., device_resident=True)``
    (the default, and what the trainer always uses) returns a
    ``jax.Array``: with a jit-safe engine
    (``engines.Capabilities.jit_safe`` — matrix/features/device) features
    flow into ``CraigSelector.select`` without a single host transfer
    (tests/test_extract.py counts them); host-side engines pull to host
    only what their algorithm needs (the lazy heap its similarity matrix,
    the sparse walk its CSC graph), never the raw feature matrix.
    ``device_resident=False`` is for callers that genuinely want numpy.

Determinism contract: batch contents equal the per-batch baseline's, the
scan body is the same traced ``select_fn``, and the row order is the pool
order — so selections downstream are bit-identical to the per-batch path
for fixed params (benchmarks/bench_extract.py gates this).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import Prefetcher
from repro.faults import fault_value
from repro.obs import span

__all__ = ["ProxyExtractor", "make_scan_extract"]


def make_scan_extract(select_fn):
    """The ONE megabatch scan body: ``fn(params, (M, B, ...) batches) →
    (M·B, D)`` features via a single ``lax.scan`` over ``select_fn``.
    Shared by the single-device extractor and the shard_map path
    (``core.distributed.make_distributed_extract``) so the two can never
    diverge numerically — the bit-parity invariant the tier-2 shard test
    guards."""

    def scan_extract(params, batches):
        def step(_, b):
            return None, select_fn(params, b)

        _, feats = jax.lax.scan(step, None, batches)  # (M, B, D)
        return feats.reshape(-1, feats.shape[-1])

    return scan_extract


class ProxyExtractor:
    """Runs ``select_fn(params, batch) → (B, D)`` over a candidate pool.

    Args:
      select_fn: uncompiled proxy forward (``train.make_select_step``); the
        extractor owns the compilation (one jitted scan program, or one
        shard_map program with a mesh).
      dataset: index-addressable dataset (``batch(idx) → dict``).
      batch_size: per-batch pool slice B (the select step's batch shape).
      megabatch: pool batches folded into one device dispatch.  1 degrades
        to per-batch dispatch (the pre-pipeline baseline, kept for the
        benchmark ladder); the trainer default folds the whole default pool
        into one program.
      prefetch: assemble the next megabatch on a background thread while
        the device runs the current one (no-op for single-dispatch pools).
      mesh / axis_name: optional data-parallel mesh — batches shard over
        ``axis_name`` and features all-gather on device (DESIGN.md §6
        composition: extraction shards exactly like round-1 selection).
    """

    def __init__(
        self,
        select_fn: Callable[[Any, dict], jax.Array],
        dataset,
        batch_size: int,
        *,
        megabatch: int = 8,
        prefetch: bool = True,
        mesh=None,
        axis_name: str = "data",
    ):
        if batch_size < 1:
            raise ValueError(f"batch_size must be ≥ 1, got {batch_size}")
        if megabatch < 1:
            raise ValueError(f"megabatch must be ≥ 1, got {megabatch}")
        self.dataset = dataset
        self.batch_size = int(batch_size)
        self.megabatch = int(megabatch)
        self.prefetch = bool(prefetch)
        self.mesh = mesh
        self.axis_name = axis_name
        if mesh is not None:
            from repro.core.distributed import make_distributed_extract

            self._n_shards = int(mesh.shape[axis_name])
            self._scan = make_distributed_extract(select_fn, mesh, axis_name)
        else:
            self._n_shards = 1
            self._scan = jax.jit(make_scan_extract(select_fn))

    # -- host-side megabatch assembly ------------------------------------

    def _plan(self, n_pool: int) -> list[tuple[int, int]]:
        """Dispatch plan: [(batch_lo, n_batches)] per device program.

        Every dispatch's batch count is a multiple of the shard count (the
        shard_map path needs an even split); only the last dispatch may be
        smaller than ``megabatch`` — at most two compiled shapes per pool
        size.
        """
        b = self.batch_size
        m_total = -(-n_pool // b)  # ceil: total B-sized batches incl. tail
        per = self.megabatch + (-self.megabatch) % self._n_shards
        plan = []
        lo = 0
        while lo < m_total:
            m = min(per, m_total - lo)
            m += (-m) % self._n_shards  # pad batch count up to a shard multiple
            plan.append((lo, m))
            lo += m
        return plan

    def _assemble(self, pool_idx: np.ndarray, lo: int, m: int) -> dict:
        """Host work: one (m, B, ...) megabatch from ``dataset.batch``.

        Index slots past the pool wrap around to its head — identical batch
        contents to the per-batch baseline's pad-then-drop, but the drop is
        a device-side slice of the flattened feature rows (the validity
        mask: row i valid ⇔ i < n_pool, all padding at the tail).
        """
        b = self.batch_size
        with span("extract.assemble"):
            flat = np.arange(lo * b, lo * b + m * b) % len(pool_idx)
            batch = self.dataset.batch(np.asarray(pool_idx)[flat])
            return {
                k: np.asarray(v).reshape((m, b) + np.shape(v)[1:])
                for k, v in batch.items()
            }

    # -- public API -------------------------------------------------------

    def extract(
        self,
        params,
        pool_idx: np.ndarray,
        *,
        device_resident: bool = True,
    ) -> jax.Array | np.ndarray:
        """Proxy features (n_pool, D) for ``pool_idx``, in pool order.

        ``device_resident=True`` (default) returns a ``jax.Array`` — the
        zero-copy handoff into ``CraigSelector.select``; ``False``
        materializes a host copy for callers that want numpy.
        """
        pool_idx = np.asarray(pool_idx)
        n_pool = len(pool_idx)
        if n_pool == 0:
            raise ValueError("empty candidate pool")
        plan = self._plan(n_pool)
        outs = []
        if self.prefetch and len(plan) > 1:
            # double buffer: assemble megabatch m+1 while the device runs m.
            # Assembly errors are re-raised on this thread (a raw generator
            # exception would kill the Prefetcher worker silently and leave
            # the queue blocking forever).
            def _tagged():
                # Exception, not BaseException: a blanket catch would also
                # swallow the GeneratorExit thrown into the suspended
                # generator when an aborted extraction GCs it, and yielding
                # from that handler is a RuntimeError per PEP 342
                try:
                    for lo, m in plan:
                        yield None, self._assemble(pool_idx, lo, m)
                except Exception as e:  # re-raised on the caller's thread
                    yield e, None

            pf = Prefetcher(_tagged(), depth=2)
            try:
                for _ in plan:
                    err, mb = pf.next()
                    if err is not None:
                        raise err
                    outs.append(self._scan(params, mb))
            finally:
                # unblock/retire the worker even when the scan side raises —
                # an abandoned Prefetcher pins megabatch host memory in its
                # queue for the life of the process
                pf.close()
        else:
            for lo, m in plan:
                outs.append(self._scan(params, self._assemble(pool_idx, lo, m)))
        feats = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
        feats = feats[:n_pool]  # validity mask: cut padded tail rows on device
        # fault hook (DESIGN.md §12): lets tests corrupt extracted features
        # (kind='nan') to exercise the selector's validate_features guard
        feats = fault_value("extract.features", feats, n_pool=n_pool)
        return feats if device_resident else np.asarray(feats)
