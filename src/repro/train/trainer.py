"""Trainer: host loop tying CRAIG selection into the training schedule.

Responsibilities (DESIGN.md §4):
  * CRAIG refresh every ``select_every`` epochs (paper §3.4: deep-net proxies
    drift with w, so the subset is re-selected periodically; Fig 5 sweeps
    per-1 and per-5-epoch refresh), run *off the critical path*: params are
    snapshotted at the trigger boundary, proxy extraction + greedy selection
    run on a background thread (``core.refresh.AsyncRefresher``), and the
    published selection installs atomically at the next epoch boundary while
    training continues on the stale coreset (double buffering).
    ``refresh_mode='sync'`` runs the identical lifecycle inline — same
    install boundaries, so the two modes are step-for-step deterministic
    replicas and their steps/s delta is exactly the selection wall-clock
    removed from the critical path (benchmarks/bench_refresh.py);
  * warm-started selection: each refresh seeds the greedy engines with the
    previous selection's high-gain prefix (``warm_start_fraction``), whose
    cover state is replayed in O(r₀·n) instead of re-derived from scratch —
    every registered engine honors the prefix, including the
    device-resident fused greedy (``engines.DeviceConfig``, DESIGN.md
    §3.6), whose whole re-selection runs as one jitted device program on
    the worker thread.  The engine itself comes from ``craig.engine`` —
    a typed ``EngineConfig`` or ``'auto'`` (default), in which case the
    ``repro.core.engines`` policy picks per refresh-pool size/backend, and
    the resolved config is stamped into the refresh metadata/checkpoints;
  * pipelined, device-resident proxy extraction (``core.extract``,
    DESIGN.md §9): the pool sweep folds into O(1) ``lax.scan`` programs
    (``extract_megabatch``) with double-buffered host prefetch
    (``extract_prefetch``); features hand off to ``CraigSelector.select``
    as a ``jax.Array`` — with a jit-safe engine
    (``engines.Capabilities.jit_safe``) the feature matrix never visits
    the host, and host copies exist only for labels/provenance;
  * per-class stratification (paper §5): pool class labels are extracted
    alongside proxies (``dataset.class_labels``) and threaded into
    ``CraigSelector.select`` whenever ``craig.per_class=True``;
  * weighted-batch training between refreshes (γ weights ride in the batch);
  * checkpoint/restart: params + opt state + sampler cursor + active coreset
    + any published-but-not-installed refresh are one atomic unit
    (``_save`` drains the refresher first, so an in-flight selection always
    materializes into the sampler's back buffer before state capture);
    ``Trainer.restore_or_init`` resumes the exact stream, optionally onto a
    different mesh (elastic);
  * preemption: SIGTERM triggers an emergency checkpoint at the next step
    boundary (CPU-testable via ``request_preempt()``);
  * straggler policy: per-step wall-clock watchdog — on the single-host
    harness it only records violations; on a pod it feeds the
    restart-from-checkpoint path.
"""
from __future__ import annotations

import dataclasses
import signal
import time
import warnings
from typing import Any, Callable, Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core.craig import CraigConfig, CraigSelector
from repro.core.extract import ProxyExtractor
from repro.core.refresh import AsyncRefresher, RefreshResult
from repro.data.pipeline import CoresetSampler
from repro.faults import FailurePolicy
from repro.kernels.ops import resolve_impl
from repro.models.config import ModelConfig
from repro.optim.optimizers import Optimizer
from repro.train.train_step import make_select_step, make_train_step

__all__ = ["TrainerConfig", "Trainer"]


@dataclasses.dataclass
class TrainerConfig:
    batch_size: int = 8
    eval_every: int = 0  # steps between held-out evals (0 = never)
    eval_batches: int = 2
    select_every_epochs: int = 1  # CRAIG refresh cadence (0 = never)
    craig: CraigConfig = dataclasses.field(
        default_factory=lambda: CraigConfig(fraction=0.5, per_class=False)
    )
    use_craig: bool = True
    proxy_pool_batches: int = 8  # batches of the pool scanned per refresh
    proxy_impl: str = "auto"  # select-step CE head: auto|einsum|pallas
    extract_megabatch: int = 0  # pool batches per extraction dispatch
    # (0 = the whole pool in ONE lax.scan program — DESIGN.md §9)
    extract_prefetch: bool = True  # double-buffered host batch assembly
    refresh_mode: Literal["sync", "async"] = "async"  # DESIGN.md §4 lifecycle
    warm_start_fraction: float = 0.5  # share of the budget warm-started from
    # the previous refresh's high-gain prefix (0 = cold every refresh)
    streaming_ingest: bool = False  # grow-only corpora: feed docs appended
    # since the last boundary through AsyncRefresher.ingest (sieve-streaming,
    # O(Δn·k) per delta) instead of re-extracting the full pool per refresh
    # (DESIGN.md §10).  Budget is fixed at craig.fraction × the first delta.
    streaming_evict: bool = True  # bounded-memory sieve pool: drop rows no
    # sieve references after every drain (O(L·k·d) instead of O(n·d))
    checkpoint_every: int = 50
    checkpoint_dir: str | None = None
    keep_checkpoints: int = 3
    step_timeout_s: float | None = None  # straggler watchdog
    microbatches: int = 1
    seed: int = 0
    # Supervision for the refresh worker (DESIGN.md §12): retry/backoff per
    # job, then raise (default) / keep sampling the stale coreset
    # ('keep_stale' — the failure is logged as a craig_refresh_failed event)
    # / degrade to an inline synchronous refresh ('sync_fallback').
    refresh_failure_policy: FailurePolicy | None = None


class Trainer:
    """Single-controller trainer (CPU-testable; sharding-transparent)."""

    def __init__(
        self,
        cfg: ModelConfig,
        tcfg: TrainerConfig,
        dataset,
        optimizer: Optimizer,
        init_params_fn: Callable[[], Any],
        eval_dataset=None,
    ):
        self.cfg = cfg
        self.tcfg = tcfg
        self.dataset = dataset
        self.eval_dataset = eval_dataset
        self.optimizer = optimizer
        self.sampler = CoresetSampler(dataset.n_docs, tcfg.batch_size, tcfg.seed)
        # Params are not donated: the AsyncRefresher snapshots them by
        # reference (immutable jax.Arrays), so a donating update would
        # delete the worker's snapshot mid-refresh (core/refresh.py).  The
        # optimizer state has no other reader (checkpoints copy it to host
        # before returning), so it is donated: without that, the old and new
        # AdamW moments (2 × params in fp32) are live at once every step.
        self.train_step = jax.jit(
            make_train_step(cfg, optimizer, microbatches=tcfg.microbatches),
            donate_argnums=(1,),
        )
        # Pipelined pool sweep (DESIGN.md §9): O(1) scan programs, prefetch,
        # device-resident features.  The extractor owns the select-step
        # compilation; megabatch 0 folds the whole default pool into one.
        self.proxy_impl = resolve_impl(tcfg.proxy_impl, "einsum")
        self.extractor = ProxyExtractor(
            make_select_step(cfg, proxy_impl=self.proxy_impl),
            dataset,
            tcfg.batch_size,
            megabatch=tcfg.extract_megabatch or max(1, tcfg.proxy_pool_batches),
            prefetch=tcfg.extract_prefetch,
        )
        self.params = init_params_fn()
        self.opt_state = optimizer.init(self.params)
        self.step = 0
        self.metrics_log: list[dict] = []
        self.straggler_events: list[int] = []
        self._preempt = False
        self.ckpt = (
            CheckpointManager(tcfg.checkpoint_dir, tcfg.keep_checkpoints)
            if tcfg.checkpoint_dir
            else None
        )
        self._last_epoch_selected = -1
        if tcfg.use_craig and tcfg.streaming_ingest:
            # Streaming lifecycle (DESIGN.md §10): refreshes are coalesced
            # ingest drains — only docs appended since the last boundary are
            # extracted, and the sieve state absorbs them in O(Δn·k).
            self.refresher = AsyncRefresher(
                self._refresh_work,
                mode=tcfg.refresh_mode,
                on_complete=self._publish_stream,
                ingest_fn=self._stream_ingest_job,
                failure_policy=tcfg.refresh_failure_policy,
                on_failure=self._refresh_failed,
            )
        else:
            self.refresher = AsyncRefresher(
                self._refresh_work,
                mode=tcfg.refresh_mode,
                on_complete=self._publish_refresh,
                failure_policy=tcfg.refresh_failure_policy,
                on_failure=self._refresh_failed,
            )
        # Streaming-ingest state (streaming_ingest=True only): the selector
        # is built lazily at the first drain (budget = fraction × first
        # delta), and the pool/doc-id buffers are compacted in lockstep with
        # StreamingSelector.compact() when streaming_evict drops dead rows.
        self._stream_cursor = 0  # docs ingested so far (dataset prefix)
        self._stream_sel = None
        self._stream_pool: np.ndarray | None = None
        self._stream_doc_ids = np.zeros((0,), np.int64)
        # previous refresh's selection in pool coordinates (the pool is a
        # deterministic stride, identical across refreshes) — warm-start seed
        self._prev_selection = None
        if (
            tcfg.use_craig
            and tcfg.craig.per_class
            and not hasattr(dataset, "class_labels")
        ):
            warnings.warn(
                "craig.per_class=True but the dataset exposes no "
                "class_labels(idx); refreshes will fall back to flat "
                "(unstratified) selection",
                UserWarning,
                stacklevel=2,
            )
        from repro.models import loss_fn as _loss_fn

        self._eval_loss = jax.jit(
            lambda p, b: _loss_fn(p, cfg, b)[1]["loss"]
        )

    # -- preemption -----------------------------------------------------------

    def install_signal_handler(self) -> None:
        signal.signal(signal.SIGTERM, lambda *_: self.request_preempt())

    def request_preempt(self) -> None:
        self._preempt = True

    # -- CRAIG refresh ---------------------------------------------------------

    def _pool_indices(self) -> np.ndarray:
        """Deterministic candidate pool: stride over the corpus.  Depends
        only on (corpus size, config), so pool coordinates are stable across
        refreshes — which is what makes warm-start prefixes transferable."""
        n_pool = min(
            self.dataset.n_docs,
            self.tcfg.proxy_pool_batches * self.tcfg.batch_size,
        )
        stride = max(1, self.dataset.n_docs // n_pool)
        return np.arange(0, self.dataset.n_docs, stride)[:n_pool]

    def _pool_labels(self, pool_idx: np.ndarray) -> np.ndarray | None:
        """Class labels for the pool (host-side; the stratification key)."""
        if self.tcfg.craig.per_class and hasattr(self.dataset, "class_labels"):
            return np.asarray(self.dataset.class_labels(pool_idx))
        return None

    def _refresh_work(self, params):
        """Extraction + selection; runs on the refresher's worker thread in
        async mode (params is a snapshot — live params keep training).

        Device-resident handoff: features stay a ``jax.Array`` end to end
        through ``CraigSelector.select`` — with a jit-safe engine
        (``Capabilities.jit_safe``) the feature matrix never crosses to the
        host at all, and the host-side engines pull to host only what their
        algorithm needs (a pre-emptive numpy copy here would just be
        re-uploaded by the selector's ``jnp.asarray``)."""
        pool_idx = self._pool_indices()
        labels = self._pool_labels(pool_idx)
        selector = CraigSelector(self.tcfg.craig)
        feats = self.extractor.extract(params, pool_idx)
        init = None
        prev = self._prev_selection
        if self.tcfg.warm_start_fraction > 0 and prev is not None:
            r0 = int(round(self.tcfg.warm_start_fraction * prev.size))
            if r0 > 0:
                init = np.asarray(prev.indices[:r0])
        sel = selector.select(feats, labels=labels, init_selected=init)
        self._prev_selection = sel
        return sel, pool_idx

    def _publish_refresh(self, result: RefreshResult) -> None:
        """on_complete hook: stage the selection into the sampler's back
        buffer (worker thread in async mode).  Installation happens on the
        main thread at the next epoch boundary."""
        sel, pool_idx = result.value
        self.sampler.stage(
            np.asarray(pool_idx)[np.asarray(sel.indices)],
            sel.weights,
            version=result.version,
            meta={
                "coreset_size": sel.size,
                "epsilon_hat": float(sel.epsilon_hat),
                "select_time_s": result.wall_time_s,
                "per_class_sizes": sel.per_class_sizes,
                # resolved EngineConfig dict (provenance; restorable via
                # engines.EngineConfig.from_dict)
                "engine": sel.engine,
                # rows the validate_features='drop' guard removed (0 unless
                # the guard fired — surfaced so degraded refreshes are
                # visible in the metrics log, never silent)
                "dropped_rows": sel.n_dropped,
            },
        )

    def _refresh_failed(self, result: RefreshResult) -> None:
        """on_failure hook (``on_exhaustion='keep_stale'`` only): the job
        was abandoned — nothing staged, training keeps sampling the
        installed coreset.  Log it so the degradation is observable."""
        err = result.error
        self.metrics_log.append(
            {
                "event": "craig_refresh_failed",
                "step": self.step,
                "version": result.version,
                "attempts": result.attempts,
                "error": f"{type(err).__name__}: {err}",
            }
        )

    # -- streaming ingest (DESIGN.md §10) --------------------------------------

    def _stream_submit(self) -> None:
        """Refresh-boundary trigger in streaming mode: queue the docs the
        dataset grew by since the last boundary as one ingest delta.  A
        boundary with no new docs is a no-op — training continues on the
        installed coreset without re-selection (the sieve state is already
        a (1−ε)/2-approximation of what it has seen)."""
        n = self.dataset.n_docs
        if n <= self._stream_cursor:
            return
        new_idx = np.arange(self._stream_cursor, n, dtype=np.int64)
        self._stream_cursor = n
        # Same snapshot contract as submit(): jax.Array leaves by reference
        # (immutable; train_step does not donate), numpy leaves by copy.
        snap = jax.tree.map(
            lambda x: x.copy() if isinstance(x, np.ndarray) else x, self.params
        )
        self.refresher.ingest((snap, new_idx))

    def _stream_ingest_job(self, deltas: list):
        """One coalesced drain (refresher worker thread): extract proxies
        for the NEW docs only, feed them to the sieve, evict dead pool
        rows, finalize.  O(Δn) extraction instead of the submit path's
        full-pool re-extraction."""
        # Coalesced deltas: newest params snapshot wins, doc ranges concat
        # in arrival order (they are disjoint, cursor-ordered by _stream_submit)
        params = deltas[-1][0]
        new_idx = np.concatenate([np.asarray(d[1], np.int64) for d in deltas])
        feats = np.asarray(
            jax.device_get(self.extractor.extract(params, new_idx)), np.float32
        )
        labels = self._pool_labels(new_idx)
        if self._stream_sel is None:
            from repro.core.engines.streaming import StreamingSelector

            k = max(1, int(round(self.tcfg.craig.fraction * new_idx.size)))
            self._stream_sel = StreamingSelector(
                k,
                feats.shape[1],
                metric=self.tcfg.craig.metric,
                per_class=labels is not None,
                evict=self.tcfg.streaming_evict,
            )
            self._stream_pool = np.zeros((0, feats.shape[1]), np.float32)
        self._stream_sel.ingest(feats, labels=labels)
        self._stream_pool = np.concatenate([self._stream_pool, feats], axis=0)
        self._stream_doc_ids = np.concatenate([self._stream_doc_ids, new_idx])
        if self.tcfg.streaming_evict:
            keep = self._stream_sel.compact()
            self._stream_pool = np.ascontiguousarray(self._stream_pool[keep])
            self._stream_doc_ids = self._stream_doc_ids[keep]
        res = self._stream_sel.result(self._stream_pool)
        doc_ids = self._stream_doc_ids[np.asarray(res.indices, np.int64)]
        return (
            doc_ids,
            np.asarray(res.weights, np.float32),
            float(res.coverage),
            self._stream_sel.n_rows,
        )

    def _publish_stream(self, result: RefreshResult) -> None:
        """on_complete hook for ingest drains: same staging path as
        :meth:`_publish_refresh`, streaming provenance in the metadata."""
        doc_ids, weights, coverage, n_live = result.value
        self.sampler.stage(
            doc_ids,
            weights,
            version=result.version,
            meta={
                "coreset_size": int(doc_ids.size),
                "select_time_s": result.wall_time_s,
                "coverage": coverage,
                "n_seen": self._stream_sel.n_seen,
                "n_live": n_live,
                "engine": self._stream_sel.config.to_dict(),
            },
        )

    def _install_refresh(self) -> None:
        """Epoch-boundary install point: wait out any in-flight selection
        (the deterministic deadline — normally it finished an epoch ago) and
        atomically swap the staged coreset in."""
        t0 = time.time()
        self.refresher.wait()
        stall = time.time() - t0
        p = self.sampler.install_pending()
        if p is None:
            return
        meta = p.get("meta") or {}
        self.metrics_log.append(
            {
                "event": "craig_refresh",
                "step": self.step,
                "version": p["version"],
                "mode": self.tcfg.refresh_mode,
                "coreset_size": len(p["indices"]),
                "epsilon_hat": meta.get("epsilon_hat", float("nan")),
                "select_time_s": meta.get("select_time_s", float("nan")),
                "install_stall_s": stall,
                "engine": meta.get("engine"),
            }
        )

    # -- evaluation ------------------------------------------------------------

    def evaluate(self) -> float:
        """Mean held-out loss over ``eval_batches`` deterministic batches."""
        ds = self.eval_dataset or self.dataset
        bs = self.tcfg.batch_size
        total = 0.0
        for b in range(self.tcfg.eval_batches):
            idx = (np.arange(bs) + b * bs) % ds.n_docs
            batch = ds.batch(idx)
            batch.pop("indices", None)
            total += float(self._eval_loss(self.params, batch))
        loss = total / max(self.tcfg.eval_batches, 1)
        self.metrics_log.append(
            {"event": "eval", "step": self.step, "eval_loss": loss}
        )
        return loss

    # -- checkpoint -------------------------------------------------------------

    def _save(self, blocking: bool = True) -> None:
        if self.ckpt is None:
            return
        # An in-flight refresh must materialize before sampler state is
        # captured: a staged selection round-trips through state_dict(), a
        # running thread doesn't.  Bounded by one selection wall-clock.
        self.refresher.wait()
        tree = {"params": self.params, "opt": self.opt_state}
        prev = self._prev_selection  # warm-start seed (pool coordinates)
        extras = {
            "step": self.step,
            "sampler": self.sampler.state_dict(),
            "last_epoch_selected": self._last_epoch_selected,
            "prev_selection": None
            if prev is None
            else {
                "indices": np.asarray(prev.indices).tolist(),
                "weights": np.asarray(prev.weights).tolist(),
                "coverage": float(prev.coverage),
                "epsilon_hat": float(prev.epsilon_hat),
                # provenance must survive restart: the resolved EngineConfig
                # dict and the per-class stratification record (JSON keys
                # stringify; restore re-ints them)
                "engine": prev.engine,
                "per_class_sizes": None
                if prev.per_class_sizes is None
                else {str(k): int(v) for k, v in prev.per_class_sizes.items()},
            },
        }
        if self.tcfg.streaming_ingest:
            # Bounded by O(L·k·d) with streaming_evict: every drain compacts
            # the pool buffer before this snapshot can observe it.
            extras["stream"] = {
                "cursor": self._stream_cursor,
                "selector": None
                if self._stream_sel is None
                else self._stream_sel.state_dict(),
                "doc_ids": self._stream_doc_ids.tolist(),
                "pool": None
                if self._stream_pool is None
                else self._stream_pool.tolist(),
            }
        self.ckpt.save(self.step, tree, extras, blocking=blocking)

    def restore_or_init(self, shardings: Any | None = None) -> bool:
        """Returns True if restored from checkpoint."""
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return False
        template = {"params": self.params, "opt": self.opt_state}
        tree, extras = self.ckpt.restore(template, shardings=shardings)
        self.params = tree["params"]
        self.opt_state = tree["opt"]
        self.step = int(extras["step"])
        self.sampler.load_state_dict(extras["sampler"])
        self._last_epoch_selected = int(extras["last_epoch_selected"])
        # version monotonicity: _save drains the refresher, so the highest
        # version ever assigned is visible as installed-or-pending state
        self.refresher.reset_version(
            max(self.sampler.version, self.sampler.pending_version or 0)
        )
        ps = extras.get("prev_selection")
        if ps is not None:
            from repro.core.craig import CoresetSelection

            pcs = ps.get("per_class_sizes")
            self._prev_selection = CoresetSelection(
                indices=np.asarray(ps["indices"], np.int64),
                weights=np.asarray(ps["weights"], np.float32),
                order=np.arange(len(ps["indices"])),
                coverage=float(ps["coverage"]),
                epsilon_hat=float(ps["epsilon_hat"]),
                per_class_sizes=None
                if pcs is None
                else {int(k): int(v) for k, v in pcs.items()},
                engine=ps.get("engine"),
            )
        st = extras.get("stream")
        if st is not None:
            self._stream_cursor = int(st["cursor"])
            self._stream_doc_ids = np.asarray(st["doc_ids"], np.int64)
            if st["selector"] is not None:
                from repro.core.engines.streaming import StreamingSelector

                sd = st["selector"]
                self._stream_sel = StreamingSelector(sd["budget"], sd["dim"])
                self._stream_sel.load_state_dict(sd)
                self._stream_pool = np.asarray(st["pool"], np.float32).reshape(
                    -1, int(sd["dim"])
                )
        return True

    # -- main loop ----------------------------------------------------------------

    def run(self, n_steps: int) -> list[dict]:
        tc = self.tcfg
        for _ in range(n_steps):
            epoch = self.sampler.epoch
            # Refresh lifecycle, both modes at the same boundaries:
            # install the previous trigger's selection at this epoch
            # boundary, then (on cadence) snapshot params and kick off the
            # next selection — async: in the background while this epoch
            # trains on the stale coreset; sync: inline, blocking here.
            if (
                tc.use_craig
                and tc.select_every_epochs > 0
                and self.sampler.step_in_epoch == 0
            ):
                self._install_refresh()
                if (
                    epoch % tc.select_every_epochs == 0
                    and epoch != self._last_epoch_selected
                ):
                    if tc.streaming_ingest:
                        self._stream_submit()
                    else:
                        self.refresher.submit(self.params)
                    self._last_epoch_selected = epoch

            idx, w = self.sampler.next_batch()
            batch = self.dataset.batch(idx)
            batch["weights"] = w
            batch.pop("indices", None)
            t0 = time.time()
            self.params, self.opt_state, metrics = self.train_step(
                self.params, self.opt_state, batch
            )
            dt = time.time() - t0
            if tc.step_timeout_s is not None and dt > tc.step_timeout_s:
                self.straggler_events.append(self.step)
            self.step += 1
            self.metrics_log.append(
                {
                    "event": "step",
                    "step": self.step,
                    "loss": float(metrics["loss"]),
                    "epoch": epoch,
                    "time_s": dt,
                }
            )
            if tc.eval_every and self.step % tc.eval_every == 0:
                self.evaluate()
            if self.ckpt is not None and self.step % tc.checkpoint_every == 0:
                self._save(blocking=False)
            if self._preempt:
                self._save(blocking=True)
                break
        if self.ckpt is not None:
            self.ckpt.wait()
        return self.metrics_log
