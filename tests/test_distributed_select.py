"""Two-round distributed CRAIG selection (8 simulated devices, subprocess).

The collective run lives in a subprocess because the device-count flag
must be set before jax initializes and the main test process must keep
seeing 1 device.  Covers both round-1 engines: dense ``matrix`` and the
O(n_local·k) ``sparse`` top-k path.  The candidate-count/ragged-shard
audits (``check_candidate_counts``/``check_even_shards``) are pure-Python
trace-time checks and run in tier 1 directly.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os, warnings
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.distributed import distributed_select
    from repro.core.craig import CraigConfig, CraigSelector
    from repro.core.engines import DeviceConfig, MatrixConfig, SparseConfig

    mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    k = jax.random.PRNGKey(0)
    centers = jax.random.normal(k, (32, 16)) * 5
    assign = jax.random.randint(jax.random.PRNGKey(1), (1024,), 0, 32)
    feats = centers[assign] + 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (1024, 16))

    # default local_engine='auto': n_local=128 resolves to the dense exact
    # matrix round 1 via the documented policy
    res = distributed_select(feats, mesh, r_local=16, r_final=32)
    w = np.asarray(res.weights)
    assert w.sum() == 1024.0, w.sum()
    assert res.indices.shape == (32,)

    # recovers (nearly) all clusters
    sel_clusters = set(np.asarray(assign)[np.asarray(res.indices)].tolist())
    assert len(sel_clusters) >= 30, len(sel_clusters)

    # quality parity vs centralized selection: coverage within 1.5x
    cen = CraigSelector(CraigConfig(fraction=32 / 1024, per_class=False,
                                    engine="matrix")).select(feats)
    ratio = float(res.coverage) / max(cen.coverage, 1e-9)
    assert ratio < 1.5, ratio

    # determinism: same result twice; explicit typed config == 'auto' pick
    res2 = distributed_select(feats, mesh, r_local=16, r_final=32)
    assert np.array_equal(np.asarray(res.indices), np.asarray(res2.indices))
    resm = distributed_select(feats, mesh, r_local=16, r_final=32,
                              local_engine=MatrixConfig())
    assert np.array_equal(np.asarray(res.indices), np.asarray(resm.indices))

    # sparse round-1: same contract, O(n_local·k) memory, near-dense
    # quality; the legacy flat-kwarg surface must warn and match the typed
    # SparseConfig surface bit for bit
    with warnings.catch_warnings(record=True) as wrec:
        warnings.simplefilter("always")
        sp = distributed_select(feats, mesh, r_local=16, r_final=32,
                                local_engine="sparse", topk_k=32)
    assert any(issubclass(x.category, DeprecationWarning) for x in wrec), (
        "legacy flat kwargs must emit a DeprecationWarning")
    spt = distributed_select(feats, mesh, r_local=16, r_final=32,
                             local_engine=SparseConfig(k=32))
    assert np.array_equal(np.asarray(sp.indices), np.asarray(spt.indices))
    wsp = np.asarray(sp.weights)
    assert wsp.sum() == 1024.0, wsp.sum()
    sp_clusters = set(np.asarray(assign)[np.asarray(sp.indices)].tolist())
    assert len(sp_clusters) >= 30, len(sp_clusters)
    sp_ratio = float(sp.coverage) / max(cen.coverage, 1e-9)
    assert sp_ratio < 1.5, sp_ratio
    sp2 = distributed_select(feats, mesh, r_local=16, r_final=32,
                             local_engine="sparse", topk_k=32)
    assert np.array_equal(np.asarray(sp.indices), np.asarray(sp2.indices))

    # selector-level wiring: engine='sparse' flips round 1 to the graph path
    sel = CraigSelector(CraigConfig(fraction=32 / 1024, engine="sparse",
                                    topk_k=32, per_class=False))
    cs = sel.select_distributed(feats, mesh)
    assert cs.weights.sum() == 1024.0, cs.weights.sum()

    # device round-1: matrix-free AND exact — identical selections to the
    # dense matrix round-1 (both are exact greedy on each shard)
    dv = distributed_select(feats, mesh, r_local=16, r_final=32,
                            local_engine="device")
    assert np.array_equal(np.asarray(dv.indices), np.asarray(res.indices))
    assert np.asarray(dv.weights).sum() == 1024.0
    # block greedy (q=4) keeps round-1 quality: same contract at the
    # same r_local as the dense run, coverage parity with it; legacy
    # flat kwargs == typed DeviceConfig bit for bit
    dv4 = distributed_select(feats, mesh, r_local=16, r_final=32,
                             local_engine="device", device_q=4)
    assert np.asarray(dv4.weights).sum() == 1024.0
    dv_ratio = float(dv4.coverage) / max(cen.coverage, 1e-9)
    assert dv_ratio < 1.5, dv_ratio
    dv4t = distributed_select(
        feats, mesh, r_local=16, r_final=32,
        local_engine=DeviceConfig(q=4, gains_impl="jax"))
    assert np.array_equal(np.asarray(dv4.indices), np.asarray(dv4t.indices))
    # selector-level wiring for the device engine (same r_local heuristic
    # as the sparse selector path; contract checks only)
    sel_dv = CraigSelector(CraigConfig(fraction=32 / 1024, per_class=False,
                                       engine=DeviceConfig(q=4)))
    cs_dv = sel_dv.select_distributed(feats, mesh)
    assert cs_dv.weights.sum() == 1024.0, cs_dv.weights.sum()
    assert cs_dv.engine["name"] == "device", cs_dv.engine
    # selector engine='auto' (the default): round 1 resolved per shard
    # pool size — dense matrix at n_local=128, identical to the dense run
    cs_auto = CraigSelector(CraigConfig(fraction=32 / 1024,
                                        per_class=False)).select_distributed(
        feats, mesh)
    assert cs_auto.engine["name"] == "matrix", cs_auto.engine
    cs_mat = CraigSelector(
        CraigConfig(fraction=32 / 1024, per_class=False,
                    engine=MatrixConfig())).select_distributed(feats, mesh)
    assert np.array_equal(np.asarray(cs_auto.indices),
                          np.asarray(cs_mat.indices))
    # ragged pool on a real 8-shard mesh: loud audit error, no silent pad
    try:
        distributed_select(feats[:1021], mesh, r_local=16, r_final=32)
        raise SystemExit("expected ValueError for ragged pool")
    except ValueError as e:
        assert "not divisible" in str(e), e
    # shard smaller than r_local on a real mesh (n_local=128 < 200)
    try:
        distributed_select(feats, mesh, r_local=200, r_final=32)
        raise SystemExit("expected ValueError for r_local > n_local")
    except ValueError as e:
        assert "exceeds the shard pool size" in str(e), e
    print("DISTRIBUTED_OK", ratio, sp_ratio, dv_ratio)
    """
)


# -- candidate-count / ragged-shard audits (tier 1: trace-time checks) --------


def test_candidate_count_invariants():
    """The silent failure modes these guard: a greedy run past its pool
    size selects duplicates, and a merge with fewer candidates than
    r_final degenerates — both must be loud ValueErrors with the remedy
    in the message."""
    from repro.core.distributed import check_candidate_counts

    check_candidate_counts(128, 8, 16, 32)  # the happy path is silent
    check_candidate_counts(16, 8, 16, 128)  # boundary: exactly enough
    with pytest.raises(ValueError, match="budgets must be"):
        check_candidate_counts(128, 8, 0, 32)
    with pytest.raises(ValueError, match="budgets must be"):
        check_candidate_counts(128, 8, 16, 0)
    with pytest.raises(ValueError, match="exceeds the shard pool size"):
        check_candidate_counts(10, 8, 16, 32)
    with pytest.raises(ValueError, match=r"8×2=16 candidates, fewer"):
        check_candidate_counts(128, 8, 2, 32)
    # the message names the fix: the minimal sufficient r_local
    with pytest.raises(ValueError, match="raise r_local to ≥ 4"):
        check_candidate_counts(128, 8, 2, 32)


def test_even_shard_audit():
    from repro.core.distributed import check_even_shards

    check_even_shards(1024, 8, where="t")
    with pytest.raises(ValueError, match="not divisible"):
        check_even_shards(1023, 8, where="t")
    with pytest.raises(ValueError, match="tree_select_host"):
        # the remedy names the ragged-capable driver
        check_even_shards(1023, 8, where="t")


def test_distributed_select_rejects_bad_counts_before_tracing():
    """distributed_select raises the informative audit errors even on a
    1-device mesh — they fire before shard_map ever traces."""
    import jax
    import jax.numpy as jnp

    from repro.core.distributed import distributed_select

    mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    feats = jnp.zeros((64, 4))
    with pytest.raises(ValueError, match="exceeds the shard pool size"):
        distributed_select(feats, mesh, r_local=65, r_final=8)
    with pytest.raises(ValueError, match="fewer than r_final"):
        distributed_select(feats, mesh, r_local=4, r_final=8)
    with pytest.raises(ValueError, match="budgets must be"):
        distributed_select(feats, mesh, r_local=4, r_final=0)


@pytest.mark.tier2  # 8-device subprocess run, >60 s
def test_distributed_select_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        env=env,
        capture_output=True,
        text=True,
        timeout=480,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DISTRIBUTED_OK" in out.stdout
