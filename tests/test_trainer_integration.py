"""Trainer loop: CRAIG refresh schedule, preemption, restart equivalence."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.craig import CraigConfig
from repro.data.synthetic import TokenStream
from repro.models import ModelConfig, init_params
from repro.optim import adamw, constant
from repro.train import Trainer, TrainerConfig

CFG = ModelConfig(
    name="tiny", family="dense", n_layers=2, d_model=32, n_heads=2,
    n_kv_heads=2, d_ff=64, vocab_size=128, logit_chunk=16,
)


def _trainer(tmp, seed=0, **kw):
    ds = TokenStream(n_docs=48, seq_len=24, vocab_size=128, n_topics=6)
    tcfg = TrainerConfig(
        batch_size=8,
        select_every_epochs=kw.pop("select_every_epochs", 2),
        checkpoint_dir=str(tmp) if tmp else None,
        checkpoint_every=kw.pop("checkpoint_every", 4),
        craig=kw.pop("craig", CraigConfig(fraction=0.5, per_class=False)),
        **kw,
    )
    return Trainer(
        CFG, tcfg, ds, adamw(constant(2e-3)),
        lambda: init_params(jax.random.PRNGKey(seed), CFG),
    )


def test_loss_decreases_with_craig(tmp_path):
    t = _trainer(None)
    log = t.run(14)
    steps = [m["loss"] for m in log if m["event"] == "step"]
    refreshes = [m for m in log if m["event"] == "craig_refresh"]
    assert len(refreshes) >= 1
    assert refreshes[0]["coreset_size"] == 24  # 50% of 48
    assert np.mean(steps[-4:]) < np.mean(steps[:4])


def test_device_engine_refresh_during_training():
    """engine='device' rides the async refresh path end to end: the fused
    device greedy runs on the worker thread, selections install at epoch
    boundaries, and the warm-start prefix threads through (DESIGN.md §3.6)."""
    t = _trainer(
        None,
        craig=CraigConfig(
            fraction=0.5, per_class=False, engine="device", device_q=4
        ),
        refresh_mode="async",
        warm_start_fraction=0.5,
    )
    log = t.run(14)
    refreshes = [m for m in log if m["event"] == "craig_refresh"]
    assert len(refreshes) >= 1
    assert refreshes[0]["coreset_size"] == 24
    # the warm-start seed was recorded for the next refresh
    assert t._prev_selection is not None
    assert len(np.unique(t._prev_selection.indices)) == 24


def test_auto_engine_refresh_records_resolved_engine():
    """engine='auto' (the CraigConfig default) resolves per refresh-pool
    size — the dense matrix engine at this scale — and the resolved
    EngineConfig dict is stamped into the refresh event and sampler meta,
    surviving the sampler's state_dict round trip."""
    from repro.core.engines import EngineConfig

    t = _trainer(
        None, craig=CraigConfig(fraction=0.5, per_class=False, engine="auto")
    )
    log = t.run(14)
    refreshes = [m for m in log if m["event"] == "craig_refresh"]
    assert refreshes and refreshes[0]["coreset_size"] == 24
    assert refreshes[0]["engine"]["name"] == "matrix"
    # the provenance dict restores to a typed config
    assert EngineConfig.from_dict(refreshes[0]["engine"]).name == "matrix"
    # and a staged-but-not-installed refresh keeps it through state_dict
    import json

    json.dumps(t.sampler.state_dict())  # meta (incl. engine) is JSON-able


def test_typed_engine_config_in_trainer():
    """A typed EngineConfig threads end to end through TrainerConfig."""
    from repro.core.engines import DeviceConfig

    t = _trainer(
        None,
        craig=CraigConfig(
            fraction=0.5, per_class=False, engine=DeviceConfig(q=4)
        ),
    )
    log = t.run(14)
    refreshes = [m for m in log if m["event"] == "craig_refresh"]
    assert refreshes and refreshes[0]["coreset_size"] == 24
    assert refreshes[0]["engine"]["name"] == "device"
    assert refreshes[0]["engine"]["q"] == 4


def test_device_engine_sync_equals_async_refresh():
    """refresh_mode sync/async remain step-for-step replicas with the
    device engine doing the selection."""
    logs = {}
    for mode in ("sync", "async"):
        t = _trainer(
            None,
            craig=CraigConfig(fraction=0.5, per_class=False, engine="device"),
            refresh_mode=mode,
        )
        logs[mode] = [
            m["loss"] for m in t.run(10) if m["event"] == "step"
        ]
    np.testing.assert_allclose(logs["sync"], logs["async"], rtol=1e-6)


def test_preemption_saves_and_restart_resumes(tmp_path):
    t1 = _trainer(tmp_path)
    t1.run(6)
    t1.request_preempt()
    t1.run(1)  # triggers emergency save and stops
    saved_step = t1.step

    t2 = _trainer(tmp_path, seed=99)  # different init — must be overwritten
    assert t2.restore_or_init()
    assert t2.step == saved_step
    # params identical to the preempted trainer's
    for a, b in zip(jax.tree.leaves(t1.params), jax.tree.leaves(t2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    # the data stream continues exactly where it stopped
    i1, _ = t1.sampler.next_batch()
    i2, _ = t2.sampler.next_batch()
    np.testing.assert_array_equal(i1, i2)


def test_restart_training_continuation_matches(tmp_path):
    """Uninterrupted run == run that checkpoints, dies, and restores."""
    t_full = _trainer(tmp_path / "a", checkpoint_every=100)
    log_full = t_full.run(10)

    t_a = _trainer(tmp_path / "b", checkpoint_every=5)
    t_a.run(5)
    t_a.ckpt.wait()
    t_b = _trainer(tmp_path / "b", seed=7)
    assert t_b.restore_or_init()
    log_b = t_b.run(5)

    full_losses = [m["loss"] for m in log_full if m["event"] == "step"][5:]
    resumed = [m["loss"] for m in log_b if m["event"] == "step"]
    np.testing.assert_allclose(full_losses, resumed, rtol=2e-3, atol=2e-4)


def test_straggler_watchdog_records():
    t = _trainer(None, step_timeout_s=0.0)  # everything is a "straggler"
    t.run(3)
    assert len(t.straggler_events) == 3


class _SlowLoss:
    """A loss whose value takes 50 ms to arrive, as a slow device step's."""

    def __init__(self, loss):
        self.loss = loss

    def __float__(self):
        time.sleep(0.05)
        return float(self.loss)


def test_straggler_watchdog_sees_a_slow_device_step():
    """The watchdog times the dispatch and the wait for the step's loss:
    a step whose result is slow to arrive is a straggler, though its
    dispatch returns at once."""
    t = _trainer(None, step_timeout_s=0.02, use_craig=False)
    t.run(1)  # compiles
    jitted = t.train_step

    def slow_result(params, opt_state, batch):
        params, opt_state, metrics = jitted(params, opt_state, batch)
        return params, opt_state, {**metrics, "loss": _SlowLoss(metrics["loss"])}

    t.train_step = slow_result
    t.straggler_events.clear()
    t.run(2)
    assert t.straggler_events == [1, 2]
    times = [m["time_s"] for m in t.metrics_log if m["event"] == "step"][1:]
    assert len(times) == 2 and min(times) >= 0.05


def test_no_craig_mode_plain_training():
    t = _trainer(None, use_craig=False)
    log = t.run(6)
    assert not [m for m in log if m["event"] == "craig_refresh"]
    assert t.sampler.active_size == 48


def test_refresh_passes_labels_for_per_class_selection():
    """Regression: the refresh path used to drop labels, silently disabling
    the paper-§5 per-class mode during training.  With a labeled dataset the
    installed coreset must be stratified across every topic."""
    ds = TokenStream(n_docs=48, seq_len=24, vocab_size=128, n_topics=6)
    tcfg = TrainerConfig(
        batch_size=8,
        select_every_epochs=1,
        craig=CraigConfig(fraction=0.5, per_class=True),
    )
    t = Trainer(CFG, tcfg, ds, adamw(constant(2e-3)),
                lambda: init_params(jax.random.PRNGKey(0), CFG))
    t.run(8)  # epoch 0 full data, install at step 6
    refreshes = [m for m in t.metrics_log if m["event"] == "craig_refresh"]
    assert refreshes and refreshes[0]["coreset_size"] == 24
    sel = t._prev_selection
    assert sel is not None and sel.per_class_sizes is not None
    assert sum(sel.per_class_sizes.values()) == 24
    # budgets ∝ topic frequency: every topic (8 docs each) is represented
    assert set(sel.per_class_sizes) == set(range(6))
    assert all(v == 4 for v in sel.per_class_sizes.values())


def test_refresh_warns_when_labels_unavailable():
    class NoLabelStream:
        """Index-addressable dataset without a class_labels() accessor."""

        def __init__(self, inner):
            self._inner = inner
            self.n_docs = inner.n_docs

        def batch(self, idx):
            return self._inner.batch(idx)

    ds = NoLabelStream(TokenStream(n_docs=48, seq_len=24, vocab_size=128))
    tcfg = TrainerConfig(
        batch_size=8,
        select_every_epochs=1,
        craig=CraigConfig(fraction=0.5, per_class=True),
    )
    with pytest.warns(UserWarning, match="class_labels"):
        t = Trainer(CFG, tcfg, ds, adamw(constant(2e-3)),
                    lambda: init_params(jax.random.PRNGKey(0), CFG))
    t.run(8)  # still trains; selection falls back to flat mode
    refreshes = [m for m in t.metrics_log if m["event"] == "craig_refresh"]
    assert refreshes and refreshes[0]["coreset_size"] == 24
    assert t._prev_selection.per_class_sizes is None


class GrowingStream:
    """A corpus that grows between epochs: ``n_docs`` exposes a prefix of
    the inner stream, extended by :meth:`grow` — the streaming-ingest
    trainer must pick up exactly the appended suffix at each boundary."""

    def __init__(self, inner, visible):
        self._inner = inner
        self.n_docs = int(visible)

    def batch(self, idx):
        return self._inner.batch(idx)

    def class_labels(self, idx):
        return self._inner.class_labels(idx)

    def grow(self, n):
        self.n_docs = min(self._inner.n_docs, self.n_docs + int(n))


def test_streaming_ingest_growing_corpus():
    """streaming_ingest=True: refreshes ride AsyncRefresher.ingest — only
    docs appended since the last boundary are extracted (O(Δn), not the
    full pool), the sieve pool buffer stays compacted in lockstep with
    eviction, and installed coresets index the whole grown corpus."""
    inner = TokenStream(n_docs=48, seq_len=24, vocab_size=128, n_topics=6)
    ds = GrowingStream(inner, visible=24)
    tcfg = TrainerConfig(
        batch_size=8,
        select_every_epochs=1,
        refresh_mode="sync",
        streaming_ingest=True,
        craig=CraigConfig(fraction=0.5, per_class=False),
    )
    t = Trainer(CFG, tcfg, ds, adamw(constant(2e-3)),
                lambda: init_params(jax.random.PRNGKey(0), CFG))
    t.run(4)  # boundary 0 ingests docs [0, 24); install at epoch 1
    assert t._stream_cursor == 24
    assert t._stream_sel is not None and t._stream_sel.n_seen == 24
    # budget fixed at fraction × first delta
    assert t._stream_sel.budget == 12

    ds.grow(24)
    t.run(8)  # next boundary ingests exactly the appended [24, 48)
    assert t._stream_cursor == 48
    assert t._stream_sel.n_seen == 48
    refreshes = [m for m in t.metrics_log if m["event"] == "craig_refresh"]
    assert len(refreshes) >= 2
    assert all(r["coreset_size"] == 12 for r in refreshes)
    # pool buffer and doc-id map stay in lockstep with eviction
    n_rows = t._stream_sel.n_rows
    assert t._stream_pool.shape[0] == n_rows
    assert t._stream_doc_ids.shape[0] == n_rows
    assert n_rows <= t._stream_sel.n_seen
    # the installed coreset indexes the corpus directly (doc ids, unique)
    idx = t.sampler._indices
    assert idx is not None and len(idx) == 12
    assert len(np.unique(idx)) == 12 and idx.min() >= 0 and idx.max() < 48
    # γ covers the live pool
    np.testing.assert_allclose(np.sum(t.sampler._weights), n_rows)


def test_streaming_ingest_restart_resumes(tmp_path):
    """Streaming state (cursor, sieve states, compacted pool + doc ids)
    round-trips through the checkpoint — a restarted trainer continues the
    stream without re-ingesting or double-counting docs."""
    inner = TokenStream(n_docs=48, seq_len=24, vocab_size=128, n_topics=6)

    def make(seed=0):
        ds = GrowingStream(inner, visible=24)
        tcfg = TrainerConfig(
            batch_size=8,
            select_every_epochs=1,
            refresh_mode="sync",
            streaming_ingest=True,
            checkpoint_dir=str(tmp_path),
            craig=CraigConfig(fraction=0.5, per_class=False),
        )
        return ds, Trainer(CFG, tcfg, ds, adamw(constant(2e-3)),
                           lambda: init_params(jax.random.PRNGKey(seed), CFG))

    _, t1 = make()
    t1.run(4)
    t1._save(blocking=True)

    ds2, t2 = make(seed=9)
    assert t2.restore_or_init()
    assert t2._stream_cursor == t1._stream_cursor == 24
    assert t2._stream_sel.n_seen == t1._stream_sel.n_seen
    np.testing.assert_array_equal(t2._stream_doc_ids, t1._stream_doc_ids)
    np.testing.assert_allclose(t2._stream_pool, t1._stream_pool)
    # and the resumed stream keeps growing without double-ingesting
    ds2.grow(24)
    t2.run(6)
    assert t2._stream_cursor == 48
    assert t2._stream_sel.n_seen == 48


@pytest.mark.tier2
def test_eval_harness_tracks_heldout_loss():
    ds_train = TokenStream(n_docs=48, seq_len=24, vocab_size=128, n_topics=6)
    ds_eval = TokenStream(n_docs=16, seq_len=24, vocab_size=128, n_topics=6,
                          seed=99)
    tcfg = TrainerConfig(batch_size=8, eval_every=4, eval_batches=2,
                         select_every_epochs=0, use_craig=False)
    t = Trainer(CFG, tcfg, ds_train, adamw(constant(2e-3)),
                lambda: init_params(jax.random.PRNGKey(0), CFG),
                eval_dataset=ds_eval)
    log = t.run(9)
    evals = [m for m in log if m["event"] == "eval"]
    assert len(evals) == 2  # steps 4 and 8
    assert all(np.isfinite(e["eval_loss"]) for e in evals)
    # eval loss should improve as training progresses
    assert evals[-1]["eval_loss"] <= evals[0]["eval_loss"] + 0.05


def test_restore_keeps_selection_provenance(tmp_path):
    """Regression: restore_or_init used to drop ``engine`` and
    ``per_class_sizes`` when rebuilding the warm-start CoresetSelection
    from checkpoint extras — a restarted trainer lost the provenance of
    the selection it warm-starts from."""
    craig = CraigConfig(fraction=0.5, per_class=True)
    t1 = _trainer(tmp_path, craig=craig, select_every_epochs=1)
    t1.run(8)  # ≥1 refresh; prev_selection carries engine + class sizes
    t1._save(blocking=True)
    prev1 = t1._prev_selection
    assert prev1 is not None
    assert prev1.engine is not None and prev1.per_class_sizes is not None

    t2 = _trainer(tmp_path, seed=9, craig=craig, select_every_epochs=1)
    assert t2.restore_or_init()
    prev2 = t2._prev_selection
    assert prev2.engine == prev1.engine
    # JSON stringifies int keys; restore must re-int them
    assert prev2.per_class_sizes == prev1.per_class_sizes
    np.testing.assert_array_equal(prev2.indices, prev1.indices)
