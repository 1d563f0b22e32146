"""Asynchronous, warm-started coreset refresh (DESIGN.md §4, §12).

CRAIG's practical speedup (paper §5) requires periodic re-selection — deep-net
proxies drift with w (§3.4, Fig 5) — but a refresh that blocks the step loop
for the full proxy-extraction + greedy pass puts selection wall-clock straight
onto the training critical path.  This module moves it off:

    trigger boundary          install boundary (next epoch)
         │                          │
         ├─ snapshot params ───────►│
         │  (immutable device refs; │
         │   numpy leaves copied)   │
         │        background thread │
         │  proxy extract + greedy  │
         │  publish RefreshResult ─►│ atomic install into CoresetSampler
         │                          │
    training continues on the *stale* coreset in between (double buffering)

The selection inside a refresh is engine-agnostic: the refresher just runs
``work_fn``, and the trainer's work carries whatever typed ``EngineConfig``
its ``CraigConfig.engine`` resolves to (``'auto'`` by default — the
``repro.core.engines`` policy picks per pool size/backend; no
engine-specific kwargs are re-threaded here).  With
``engines.DeviceConfig`` the greedy loop is a single jitted device program
(DESIGN.md §3.6), so the worker thread spends its time in one XLA dispatch
instead of a per-round host loop — the cheapest engine to run concurrently
with training, since it never contends for the host between rounds.  The
resolved engine rides the published selection's metadata
(``CoresetSelection.engine``), so checkpoints record which engine produced
each staged/installed coreset.

``AsyncRefresher`` owns the worker thread and the publish slot; the trainer
owns the install points.  ``mode='sync'`` runs the identical lifecycle with
the work inline at submit time — same install boundaries, so sync and async
training are step-for-step deterministic replicas of each other
(tests/test_refresh.py), and the steps/s delta between the two modes is
exactly the selection wall-clock removed from the critical path
(benchmarks/bench_refresh.py).

At most one refresh is in flight (double buffering, not a queue): the stale
coreset is the front buffer, the in-flight selection the back buffer.
Checkpoint semantics: the trainer drains the refresher (``wait()``) before
capturing sampler state, so a published-but-not-installed selection
round-trips through ``CoresetSampler.state_dict()`` and an in-flight one
always materializes before the snapshot — a restart never loses a refresh.

Supervision (DESIGN.md §12): each job runs under a
:class:`~repro.faults.FailurePolicy` — per-attempt retry with exponential
backoff on the worker thread, then one of three exhaustion routes: re-raise
on the caller thread (``'raise'``, the default fail-fast contract),
abandon-and-log (``'keep_stale'`` — nothing publishes, the ``on_failure``
callback records the event, training keeps sampling the installed coreset),
or one inline re-run at the caller's next touch point
(``'sync_fallback'`` — degrade to a synchronous refresh instead of skipping
it).  Failure state is per *job*: an exhausted job never poisons the
refresher — the next submit/ingest runs normally.

Starting a job is a ``refresh.submit`` span on the caller's thread, the
job a ``refresh.job`` span on whichever thread runs it, and its
``on_complete`` a ``refresh.publish`` span, all three carrying the job's
``version``: on the profiler's timeline the job on the worker thread is
tied to its start and to the trainer's ``trainer.install`` of the same
version (span catalog: ``repro.obs``).  ``RefreshResult.wall_time_s`` is
read from ``time.perf_counter``, the clock that bounds the spans.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Callable, Literal

import jax
import numpy as np

from repro.faults import FailurePolicy, fault_point
from repro.obs import span

__all__ = ["AsyncRefresher", "RefreshResult"]


@dataclasses.dataclass
class RefreshResult:
    """A published refresh: whatever ``work_fn`` returned, plus provenance.

    ``version`` is a monotone counter assigned at submit time — the same
    counter the :class:`~repro.data.pipeline.CoresetSampler` uses for its
    staged/installed buffers, so logs, checkpoints, and benchmarks can
    correlate a selection with the params snapshot that produced it.
    ``attempts`` counts work attempts (1 = first try succeeded);
    ``fell_back`` marks a result produced by the ``'sync_fallback'`` inline
    re-run on the caller thread.
    """

    version: int
    value: Any
    wall_time_s: float
    error: BaseException | None = None
    attempts: int = 1
    fell_back: bool = False


class AsyncRefresher:
    """Runs ``work_fn(params_snapshot)`` off the training critical path.

    * ``mode='async'`` — ``submit`` snapshots params (immutable
      ``jax.Array`` leaves by reference — they stay device-resident for
      the worker's extraction scan; mutable numpy leaves by copy, since
      the live training params keep updating) and returns immediately;
      extraction + selection run on a background worker thread
      (non-daemon, so interpreter shutdown joins it rather than tearing
      down under an active XLA dispatch).
    * ``mode='sync'`` — the same lifecycle with the work inline in
      ``submit``; the deterministic on-critical-path baseline.

    One job in flight at a time (double buffering).  Results publish to a
    single slot, readable via :meth:`collect`; an optional ``on_complete``
    callback fires on the worker thread the moment a job succeeds (the
    trainer uses it to stage the selection into the sampler so checkpoints
    see it without polling).

    Failure handling is governed by ``failure_policy``
    (:class:`~repro.faults.FailurePolicy`): the worker retries the work
    with exponential backoff, and exhaustion routes to re-raise on the
    caller's thread at the next :meth:`wait`/:meth:`collect`/
    :meth:`submit` (``'raise'`` — a failed selection must fail training,
    not silently train on stale data forever), to abandon-and-log
    (``'keep_stale'`` — ``on_failure`` fires with the failed
    ``RefreshResult``; the refresher stays usable), or to one inline
    re-run at the caller's next touch point (``'sync_fallback'``).

    With an ``ingest_fn``, the refresher additionally serves the streaming
    path (DESIGN.md §10): :meth:`ingest` queues pool deltas and drains the
    queue as one coalesced ``ingest_fn(deltas)`` job whenever the worker
    is idle — same single job slot, same publish/install lifecycle, one
    version per drain.  The coreset service builds on this.
    """

    def __init__(
        self,
        work_fn: Callable[[Any], Any],
        mode: Literal["sync", "async"] = "async",
        on_complete: Callable[[RefreshResult], None] | None = None,
        ingest_fn: Callable[[list], Any] | None = None,
        failure_policy: FailurePolicy | None = None,
        on_failure: Callable[[RefreshResult], None] | None = None,
    ):
        if mode not in ("sync", "async"):
            raise ValueError(f"unknown refresh mode {mode!r}")
        self._work_fn = work_fn
        self._mode = mode
        self._on_complete = on_complete
        self._ingest_fn = ingest_fn
        self._policy = failure_policy or FailurePolicy()
        self._on_failure = on_failure
        self._version = 0
        self._thread: threading.Thread | None = None
        self._result: RefreshResult | None = None
        self._lock = threading.Lock()
        self._pending: list = []
        self._fallback: tuple[RefreshResult, Callable[[], Any]] | None = None
        self._last_failure: RefreshResult | None = None

    # -- state ---------------------------------------------------------------

    @property
    def mode(self) -> str:
        return self._mode

    @property
    def version(self) -> int:
        """Version of the most recently submitted refresh (0 = none yet)."""
        return self._version

    @property
    def busy(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    @property
    def failure_policy(self) -> FailurePolicy:
        return self._policy

    @property
    def last_failure(self) -> RefreshResult | None:
        """Most recent abandoned job (``on_exhaustion='keep_stale'`` only);
        informational — reading it does not consume anything."""
        with self._lock:
            return self._last_failure

    # -- lifecycle -----------------------------------------------------------

    def submit(self, params: Any, *, snapshot: bool = True) -> int:
        """Snapshot ``params`` and start (or run, in sync mode) the refresh.

        Returns the new version.  While a job is in flight ``submit`` is a
        *reject*, not a queue: it raises a ``RuntimeError`` naming the
        in-flight version — callers hold at most one back buffer, and a
        caller that wants coalescing wants the :meth:`ingest` path instead.
        A worker failure from a previous job is re-raised here first (as at
        :meth:`wait`/:meth:`collect`) — submitting new work must never
        silently overwrite an uncollected failure — and a pending
        ``sync_fallback`` re-run executes here first, for the same reason.

        Contract: ``jax.Array`` leaves are snapshotted by reference (they
        are immutable), so the caller's parameter *update* must not donate
        the submitted buffers (``jax.jit(donate_argnums=...)``) while a
        refresh is in flight — a donated update deletes them under the
        worker.  The trainer's ``train_step`` is jitted without donation
        for exactly this reason; callers that must donate should pass a
        ``jax.device_get`` copy instead.
        """
        self._run_fallback_if_pending()
        self._raise_if_failed()
        if self.busy:
            raise RuntimeError(
                f"refresh v{self._version} already in flight; collect it "
                "before submitting (use ingest() for coalescing semantics)"
            )
        self._version += 1
        version = self._version

        def snap_leaf(x):
            # jax.Arrays are immutable — holding the reference IS the
            # snapshot, and it keeps the params DEVICE-resident for the
            # worker's extraction scan (no device→host→device bounce of the
            # whole param tree per refresh; DESIGN.md §9).  Host numpy
            # leaves are mutable and must be copied, or the worker would
            # see the live training updates.
            if isinstance(x, np.ndarray):
                return x.copy()
            return x

        with span("refresh.submit", version=version):
            snap = jax.tree.map(snap_leaf, params) if snapshot else params
            self._start(version, lambda: self._work_fn(snap), "craig-refresh")
        return version

    def _start(self, version: int, fn: Callable[[], Any], name: str) -> None:
        """Run the job of ``version`` inline (sync mode) or start it on the
        worker thread ``<name>-v<version>`` (async mode)."""

        def job() -> None:
            try:
                with span("refresh.job", version=version):
                    self._run_job(version, fn)
            except BaseException as e:  # noqa: BLE001 — never die silently
                # _run_job routes everything through the policy; this outer
                # capture only exists so a bug in the routing itself still
                # surfaces on the caller thread instead of killing the
                # worker silently
                with self._lock:
                    self._result = RefreshResult(version, None, 0.0, error=e)

        if self._mode == "sync":
            job()
            self._run_fallback_if_pending()
            self._raise_if_failed()
        else:
            # non-daemon: the interpreter joins it at shutdown instead of
            # tearing down under a thread mid-XLA-dispatch (which aborts)
            self._thread = threading.Thread(
                target=job, name=f"{name}-v{version}", daemon=False
            )
            self._thread.start()

    # -- supervised job runner -----------------------------------------------

    def _run_job(self, version: int, fn: Callable[[], Any]) -> None:
        """One supervised job: retry the work per the policy, publish, or
        route the exhausted failure.  Runs on the worker thread in async
        mode, inline in sync mode."""
        policy = self._policy
        t0 = time.perf_counter()
        error: BaseException | None = None
        attempts = 0
        for attempt in range(policy.max_retries + 1):
            attempts += 1
            try:
                fault_point("refresh.worker", version=version, attempt=attempt)
                value = fn()
            except BaseException as e:  # noqa: BLE001 — routed via policy
                error = e
                if attempt < policy.max_retries:
                    time.sleep(policy.backoff_s(attempt))
                continue
            res = RefreshResult(
                version, value, time.perf_counter() - t0, attempts=attempts
            )
            try:
                if self._on_complete is not None:
                    # a failed publish must surface at wait()/collect(), not
                    # vanish on the worker thread — but it is NOT retryable:
                    # the work succeeded, and re-running it could stage the
                    # same version twice
                    with span("refresh.publish", version=version):
                        self._on_complete(res)
            except BaseException as e:  # noqa: BLE001 — routed via policy
                self._exhaust(
                    RefreshResult(
                        version, None, time.perf_counter() - t0, error=e,
                        attempts=attempts,
                    ),
                    fn,
                    retryable=False,
                )
                return
            with self._lock:
                self._result = res
            return
        self._exhaust(
            RefreshResult(
                version, None, time.perf_counter() - t0, error=error,
                attempts=attempts,
            ),
            fn,
            retryable=True,
        )

    def _exhaust(
        self,
        res: RefreshResult,
        fn: Callable[[], Any],
        *,
        retryable: bool,
    ) -> None:
        """Route a job whose every attempt failed per the policy's
        exhaustion mode.  ``retryable=False`` (a publish failure) always
        takes the raise route — re-running the work could double-stage."""
        mode = self._policy.on_exhaustion
        if mode == "sync_fallback" and retryable:
            with self._lock:
                self._fallback = (res, fn)
            return
        if mode == "keep_stale":
            with self._lock:
                self._last_failure = res
            cb = self._on_failure
            if cb is None:
                return
            try:
                cb(res)
            except BaseException as e:  # noqa: BLE001 — must not die silently
                with self._lock:
                    self._result = dataclasses.replace(res, error=e)
            return
        with self._lock:
            self._result = res

    def _run_fallback_if_pending(self) -> None:
        """Run an exhausted job's one-shot synchronous re-run inline on the
        calling thread (``on_exhaustion='sync_fallback'``).  Success
        publishes through the normal ``on_complete``/result path with
        ``fell_back=True``; a second failure is stored and re-raised like
        any worker failure."""
        with self._lock:
            pending, self._fallback = self._fallback, None
        if pending is None:
            return
        failed, fn = pending
        with span("refresh.job", version=failed.version):
            t0 = time.perf_counter()
            try:
                value = fn()
                res = RefreshResult(
                    failed.version,
                    value,
                    failed.wall_time_s + time.perf_counter() - t0,
                    attempts=failed.attempts + 1,
                    fell_back=True,
                )
                if self._on_complete is not None:
                    with span("refresh.publish", version=failed.version):
                        self._on_complete(res)
                with self._lock:
                    self._result = res
            except BaseException as e:  # noqa: BLE001 — re-raised at wait()
                with self._lock:
                    self._result = RefreshResult(
                        failed.version,
                        None,
                        failed.wall_time_s + time.perf_counter() - t0,
                        error=e,
                        attempts=failed.attempts + 1,
                        fell_back=True,
                    )

    # -- streaming ingest (coalescing) ---------------------------------------

    @property
    def pending_deltas(self) -> int:
        """Deltas queued for the next coalesced ingest drain."""
        with self._lock:
            return len(self._pending)

    def ingest(self, *deltas: Any) -> int | None:
        """Queue pool deltas and drain them through ``ingest_fn``.

        The streaming counterpart of :meth:`submit` (DESIGN.md §10): where
        submit *rejects* while a job is in flight, ``ingest`` *coalesces* —
        deltas enqueue unconditionally, and whenever no job is in flight
        the whole queue drains as ONE job, ``ingest_fn(deltas)``,
        publishing a single ``RefreshResult`` through the same slot /
        ``on_complete`` path (one version per drain, not per delta).

        Returns the drained version, or ``None`` if the deltas were queued
        behind an in-flight job — they drain at the next
        ingest/:meth:`wait`/:meth:`collect` touch point.  Worker failures
        surface exactly like submit's: routed per the failure policy, with
        the ``'raise'`` mode re-raising on the caller's thread at the next
        drain attempt, ``wait``, or ``collect``.
        """
        if self._ingest_fn is None:
            raise RuntimeError(
                "this refresher has no ingest_fn; pass one at construction "
                "to use the streaming ingest path"
            )
        if not deltas:
            raise ValueError("ingest() needs at least one delta")
        with self._lock:
            self._pending.extend(deltas)
        return self._drain()

    def _drain(self) -> int | None:
        """Start one coalesced ingest job if idle and deltas are queued."""
        if self.busy:
            return None
        self._run_fallback_if_pending()
        self._raise_if_failed()
        with self._lock:
            if not self._pending:
                return None
            batch, self._pending = self._pending, []
        self._version += 1
        version = self._version
        with span("refresh.submit", version=version):
            self._start(version, lambda: self._ingest_fn(batch), "craig-ingest")
        return version

    def reset_version(self, version: int) -> None:
        """Fast-forward the version counter (monotonicity across restarts:
        a restored trainer seeds this from the checkpointed sampler state so
        post-restore refreshes never collide with already-staged/installed
        versions)."""
        if self.busy:
            raise RuntimeError("cannot reset version while a refresh runs")
        self._version = max(self._version, int(version))

    def wait(self, timeout: float | None = None) -> None:
        """Block until no job is in flight, no queued deltas remain and no
        sync fallback is pending; re-raise a worker failure.

        ``timeout`` is a TOTAL deadline across everything outstanding
        (thread join + any coalesced drains it unblocks), not a per-join
        budget.  On expiry a ``TimeoutError`` raises and the refresher
        stays fully usable: the in-flight job keeps running, ``busy`` stays
        true, and the job's eventual outcome — including a failure —
        surfaces exactly once at the next
        ``wait``/``collect``/``submit``/``ingest`` touch point
        (tests/test_refresh.py pins this regression).
        """
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            t = self._thread
            if t is not None:
                remaining = (
                    None if deadline is None else deadline - time.monotonic()
                )
                if remaining is None or remaining > 0:
                    t.join(remaining)
                if t.is_alive():
                    raise TimeoutError(
                        f"refresh still running after {timeout}s"
                    )
                self._thread = None
            self._run_fallback_if_pending()
            self._raise_if_failed()
            if self._ingest_fn is not None and self._drain() is not None:
                continue
            return

    def collect(self, block: bool = False) -> RefreshResult | None:
        """Pop the published result, if any.  ``block=True`` waits first."""
        if block:
            self.wait()
        else:
            self._raise_if_failed()
        with self._lock:
            res, self._result = self._result, None
        return res

    def _raise_if_failed(self) -> None:
        with self._lock:
            res = self._result
            if res is not None and res.error is not None:
                self._result = None
            else:
                res = None
        if res is not None:
            raise RuntimeError(
                f"coreset refresh v{res.version} failed after "
                f"{res.attempts} attempt(s)"
            ) from res.error
