"""ProxyExtractor (DESIGN.md §9): megabatch scan, prefetch, shard_map,
device-resident handoff with zero host transfers of the feature matrix."""
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.extract import ProxyExtractor
from repro.data.synthetic import TokenStream
from repro.models import ModelConfig, init_params
from repro.train import make_select_step

CFG = ModelConfig(
    name="tiny", family="dense", n_layers=2, d_model=32, n_heads=2,
    n_kv_heads=2, d_ff=64, vocab_size=128, logit_chunk=16,
)
BS = 8


@pytest.fixture(scope="module")
def setup():
    ds = TokenStream(n_docs=100, seq_len=24, vocab_size=128, n_topics=8)
    params = init_params(jax.random.PRNGKey(0), CFG)
    step = make_select_step(CFG)
    return ds, params, step


def _per_batch_baseline(step, ds, params, pool, bs=BS):
    """The pre-pipeline extraction loop: one jitted dispatch per batch,
    blocking host copy each time, pad-then-drop on the tail."""
    jstep = jax.jit(step)
    feats = []
    for lo in range(0, len(pool), bs):
        chunk = pool[lo : lo + bs]
        if len(chunk) < bs:
            chunk = np.concatenate([chunk, pool[: bs - len(chunk)]])
        feats.append(np.asarray(jstep(params, ds.batch(chunk))))
    return np.concatenate(feats)[: len(pool)]


def test_megabatch_bit_identical_to_per_batch_baseline(setup):
    """The scan path's batch contents equal the baseline's (tail wraps the
    pool), so features are bit-identical — the refresh-parity invariant
    bench_extract gates."""
    ds, params, step = setup
    pool = np.arange(100)[:52]  # 6 full batches + a 4-row tail
    base = _per_batch_baseline(step, ds, params, pool)
    for mb, pf in [(1, False), (3, False), (8, True), (64, True)]:
        ex = ProxyExtractor(step, ds, BS, megabatch=mb, prefetch=pf)
        got = ex.extract(params, pool)
        assert isinstance(got, jax.Array)
        np.testing.assert_array_equal(np.asarray(got), base)


def test_whole_pool_is_one_dispatch(setup):
    """megabatch ≥ n_batches folds the sweep into O(1) programs."""
    ds, _, step = setup
    ex = ProxyExtractor(step, ds, BS, megabatch=64)
    assert ex._plan(52) == [(0, 7)]  # one program, 7 batches (tail padded)


def test_plan_invariants():
    ds = TokenStream(n_docs=100, seq_len=8, vocab_size=32)
    ex = ProxyExtractor(lambda p, b: None, ds, BS, megabatch=3)
    for n_pool in (1, 7, 8, 52, 100):
        plan = ex._plan(n_pool)
        m_total = -(-n_pool // BS)
        assert sum(m for _, m in plan) >= m_total  # covers the pool
        assert [lo for lo, _ in plan] == list(
            np.cumsum([0] + [m for _, m in plan])[:-1]
        )  # contiguous
        assert len({m for _, m in plan}) <= 2  # at most 2 compiled shapes


def test_device_resident_flag(setup):
    ds, params, step = setup
    ex = ProxyExtractor(step, ds, BS, megabatch=4)
    pool = np.arange(24)
    dev = ex.extract(params, pool)
    host = ex.extract(params, pool, device_resident=False)
    assert isinstance(dev, jax.Array) and isinstance(host, np.ndarray)
    np.testing.assert_array_equal(np.asarray(dev), host)


def test_prefetch_assembly_error_propagates(setup):
    """A dataset failure on the prefetch thread must raise on the caller,
    not leave the queue blocking forever."""
    _, params, step = setup

    class Exploding:
        n_docs = 100

        def __init__(self):
            self.calls = 0
            self._inner = TokenStream(n_docs=100, seq_len=24, vocab_size=128)

        def batch(self, idx):
            self.calls += 1
            if self.calls > 1:
                raise RuntimeError("disk on fire")
            return self._inner.batch(idx)

    ex = ProxyExtractor(step, Exploding(), BS, megabatch=1, prefetch=True)
    with pytest.raises(RuntimeError, match="disk on fire"):
        ex.extract(params, np.arange(40))


def test_pallas_select_step_close_to_einsum(setup):
    """The fused ce_proxy select path (interpret mode on CPU) agrees with
    the chunked einsum path within bf16 tolerance."""
    ds, params, _ = setup
    batch = ds.batch(np.arange(BS))
    f_e = np.asarray(jax.jit(make_select_step(CFG, proxy_impl="einsum"))(params, batch))
    f_p = np.asarray(jax.jit(make_select_step(CFG, proxy_impl="pallas"))(params, batch))
    np.testing.assert_allclose(f_p, f_e, rtol=0.05, atol=3e-3)
    with pytest.raises(ValueError, match="proxy_impl"):
        make_select_step(CFG, proxy_impl="nope")


# ---------------------------------------------------------------------------
# Device-resident handoff: zero host transfers of the feature matrix
# ---------------------------------------------------------------------------


@pytest.fixture
def transfer_guard(monkeypatch):
    """Counts host materializations (np.asarray / jax.device_get) of any
    jax.Array whose shape is being watched — the feature matrix, here."""
    watched: set[tuple] = set()
    hits: list[tuple] = []
    real_asarray, real_get = np.asarray, jax.device_get

    def _check(kind, x):
        for leaf in jax.tree_util.tree_leaves(x):
            if isinstance(leaf, jax.Array) and tuple(leaf.shape) in watched:
                hits.append((kind, tuple(leaf.shape)))

    def guard_asarray(a, *args, **kw):
        _check("np.asarray", a)
        return real_asarray(a, *args, **kw)

    def guard_get(x):
        _check("jax.device_get", x)
        return real_get(x)

    monkeypatch.setattr(np, "asarray", guard_asarray)
    monkeypatch.setattr(jax, "device_get", guard_get)

    class Guard:
        def watch(self, *shape):
            watched.add(tuple(shape))

        @property
        def hits(self):
            return list(hits)

    return Guard()


def _refresh_trainer(engine):
    from repro.core.craig import CraigConfig
    from repro.optim import adamw, constant
    from repro.train import Trainer, TrainerConfig

    ds = TokenStream(n_docs=48, seq_len=24, vocab_size=128, n_topics=6)
    tcfg = TrainerConfig(
        batch_size=BS,
        select_every_epochs=1,
        refresh_mode="sync",
        craig=CraigConfig(fraction=0.5, per_class=False, engine=engine),
    )
    return Trainer(
        CFG, tcfg, ds, adamw(constant(2e-3)),
        lambda: init_params(jax.random.PRNGKey(0), CFG),
    )


def test_jit_safe_refresh_never_lands_features_on_host(transfer_guard):
    """On the jit-safe engine path the (n_pool, D) feature matrix stays a
    jax.Array end to end through extract → CraigSelector.select — zero
    np.asarray / device_get calls see it."""
    from repro.core.engines import FeaturesConfig

    t = _refresh_trainer(FeaturesConfig())
    n_pool = len(t._pool_indices())
    transfer_guard.watch(n_pool, CFG.d_model)
    t.run(8)  # ≥1 full refresh lifecycle
    refreshes = [m for m in t.metrics_log if m["event"] == "craig_refresh"]
    assert refreshes, "refresh never ran — guard proved nothing"
    assert transfer_guard.hits == []


def test_host_engine_refresh_guard_control(transfer_guard):
    """Control proving the guard catches real transfers: the host-side lazy
    engine materializes its (n, n) similarity matrix (never the raw
    (n, D) feature matrix — features hand off device-resident to every
    engine) once per submitted refresh."""
    from repro.core.engines import LazyConfig

    t = _refresh_trainer(LazyConfig())
    n_pool = len(t._pool_indices())
    transfer_guard.watch(n_pool, CFG.d_model)  # the feature matrix...
    transfer_guard.watch(n_pool, n_pool)  # ...and the lazy host similarity
    t.run(8)
    n_submitted = t.refresher.version  # one selection per submitted refresh
    assert n_submitted >= 1
    feat_hits = [h for h in transfer_guard.hits if h[1] == (n_pool, CFG.d_model)]
    sim_hits = [
        h for h in transfer_guard.hits
        if h[0] == "np.asarray" and h[1] == (n_pool, n_pool)
    ]
    assert feat_hits == [], feat_hits  # feature matrix never crosses
    assert len(sim_hits) == n_submitted, transfer_guard.hits


def test_trainer_refresh_selection_matches_manual_baseline():
    """Selections from the ProxyExtractor refresh path are bit-identical to
    a manual per-batch extraction + selection on the same params."""
    from repro.core.craig import CraigConfig, CraigSelector

    t = _refresh_trainer("auto")
    pool = t._pool_indices()
    base_feats = _per_batch_baseline(
        make_select_step(CFG), t.dataset, t.params, pool
    )
    want = CraigSelector(CraigConfig(fraction=0.5, per_class=False)).select(
        base_feats
    )
    sel, got_pool = t._refresh_work(t.params)
    np.testing.assert_array_equal(got_pool, pool)
    np.testing.assert_array_equal(sel.indices, want.indices)
    np.testing.assert_allclose(sel.weights, want.weights, rtol=1e-6)


# ---------------------------------------------------------------------------
# shard_map data-parallel extraction (simulated devices, subprocess)
# ---------------------------------------------------------------------------

SHARD_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from repro.core.extract import ProxyExtractor
    from repro.data.synthetic import TokenStream
    from repro.models import ModelConfig, init_params
    from repro.train import make_select_step

    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=128,
                      logit_chunk=16)
    ds = TokenStream(n_docs=100, seq_len=24, vocab_size=128, n_topics=8)
    params = init_params(jax.random.PRNGKey(0), cfg)
    step = make_select_step(cfg)
    pool = np.arange(100)[:52]

    base = np.asarray(
        ProxyExtractor(step, ds, 8, megabatch=8, prefetch=False)
        .extract(params, pool)
    )
    mesh = jax.make_mesh((4,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    for mb in (1, 8):  # plan rounds batch counts up to shard multiples
        ex = ProxyExtractor(step, ds, 8, megabatch=mb, prefetch=True,
                            mesh=mesh)
        got = ex.extract(params, pool)
        assert got.shape == (52, 32), got.shape
        np.testing.assert_allclose(np.asarray(got), base,
                                   rtol=1e-6, atol=1e-7)
    print("OK")
    """
)


@pytest.mark.tier2
def test_sharded_extract_matches_single_device():
    r = subprocess.run(
        [sys.executable, "-c", SHARD_SCRIPT],
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout
