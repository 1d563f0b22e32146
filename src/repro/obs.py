"""Program spans on the profiler's timeline.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``.  With no profiler session running it is an inactive
TraceMe (about a microsecond); under ``jax.profiler.start_trace`` the
profiler buffers the spans in memory and writes them at ``stop_trace``, on
the same timeline as the device's operations, so a device idle gap can be
put down to what the program's host threads were doing at that moment.
The ids come through as event stats (``('version', 3)``).

Spans nest on their own thread, and a parent is the enclosing span.  Work
on another thread is tied to its cause by a shared id: one refresh carries
its ``version`` from ``refresh.submit``, through ``refresh.job`` on the
worker, to the ``trainer.install`` that installs it.  Every span wraps code
that runs without it, in the same order: none adds a host↔device sync.

Catalog (each name below is prefixed with ``repro.``; ids in brackets):

``trainer.step`` [step]
    ``Trainer.run``, one loop iteration, all of it.  ``step`` is the number
    the iteration's ``metrics_log`` step event carries.
``trainer.install`` [version]
    ``Trainer._install_refresh``: ``refresher.wait()`` and
    ``install_pending``.  ``version`` is the one it waits for.
``trainer.batch``
    ``Trainer.run``: ``sampler.next_batch`` and ``dataset.batch``.
``trainer.dispatch``
    ``Trainer.run``: the ``train_step`` call, which returns once enqueued.
``trainer.sync``
    ``Trainer.run``: ``float(metrics["loss"])``, the wait for the step's
    result.
``refresh.submit`` [version]
    ``AsyncRefresher.submit`` and the ``ingest`` drain, on the caller's
    thread: the snapshot and the worker's start (the whole job in sync
    mode).  ``version`` is the job's, as the refresher numbers it.
``refresh.job`` [version]
    ``AsyncRefresher._start``'s job and ``_run_fallback_if_pending``: the
    retries, the work and the publish, on whichever thread runs them.
``refresh.extract``
    ``Trainer._refresh_work`` and ``_stream_ingest_job``:
    ``ProxyExtractor.extract``, host assembly and the scan dispatch.
``extract.assemble``
    ``ProxyExtractor._assemble``: host batch assembly (on the prefetch
    thread when the extractor prefetches).
``refresh.select``
    ``Trainer._refresh_work``: ``CraigSelector.select``, which waits for the
    extraction, runs the greedy and pulls the indices.
``refresh.publish`` [version]
    ``AsyncRefresher._run_job`` and ``_run_fallback_if_pending``:
    ``on_complete``, which stages the coreset in the sampler.
"""
from __future__ import annotations

import jax

__all__ = ["span"]


def span(name: str, **ids: int) -> jax.profiler.TraceAnnotation:
    """A host span ``repro.<name>`` with ``ids`` as its event stats."""
    return jax.profiler.TraceAnnotation("repro." + name, **ids)
