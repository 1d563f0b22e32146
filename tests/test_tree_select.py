"""Hierarchical tree selection (distributed/tree_select, DESIGN.md §6).

Tier 1 exercises the host driver (single-process, ragged-capable) plus
topology/config/wire units — no mesh needed.  The tier-2 subprocess runs
the N-axis mesh driver on 8 simulated devices and pins the load-bearing
identities: depth-1 fp32 tree ≡ ``local_then_merge`` bit for bit, and
mesh ≡ host at every depth/wire mode.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.craig import CraigConfig, CraigSelector
from repro.core.engines import engine_config_from_dict
from repro.distributed.tree_select import (
    TreeSelectConfig,
    TreeTopology,
    default_r_node,
    tree_select_host,
    wire_bytes_plan,
)


def _clustered(n, d, seed=0):
    rng = np.random.RandomState(seed)
    c = rng.randn(8, d).astype(np.float32) * 5.0
    assign = rng.randint(0, 8, n)
    return (c[assign] + 0.3 * rng.randn(n, d)).astype(np.float32), assign


# ---------------------------------------------------------------------------
# topology + config + wire units
# ---------------------------------------------------------------------------


def test_topology_shape():
    t = TreeTopology((4, 2))
    assert t.depth == 2 and t.n_leaves == 8
    assert t.nodes_at(0) == 8 and t.nodes_at(1) == 2 and t.nodes_at(2) == 1
    assert t.axis_names == ("lvl0", "lvl1")
    assert TreeTopology.from_dict(t.to_dict()) == t


def test_topology_rejects_degenerate():
    with pytest.raises(ValueError, match="at least one fan-out"):
        TreeTopology(())
    with pytest.raises(ValueError, match="≥ 1"):
        TreeTopology((4, 0))
    with pytest.raises(ValueError, match="degenerate"):
        TreeTopology((1, 1, 1))
    # a 1-fan-out level inside a non-degenerate tree is fine (pass-through)
    assert TreeTopology((1, 4)).n_leaves == 4


def test_tree_config_provenance_roundtrip():
    cfg = TreeSelectConfig(fanouts=(4, 2), compress="int8",
                           local={"name": "matrix"})
    d = cfg.to_dict()
    assert d["name"] == "tree"
    restored = engine_config_from_dict(d)
    assert restored == cfg and restored.topology.n_leaves == 8
    # JSON round trip turns the fanouts tuple into a list; the config
    # normalizes it back
    import json

    rejson = engine_config_from_dict(json.loads(json.dumps(d)))
    assert rejson == cfg
    with pytest.raises(ValueError, match="wire mode"):
        TreeSelectConfig(fanouts=(2,), compress="fp8")


def test_wire_bytes_plan_math():
    # depth-2, r uniform: every child ships once per level; int8 payload is
    # r·d + 4r (scales) vs 4·r·d fp32 → reduction 4d/(d+4)
    t = TreeTopology((4, 2))
    plan = wire_bytes_plan(t, r_local=8, r_node=8, d=64, compress="int8")
    per_payload = 8 * 64 + 4 * 8
    assert plan["per_level"][0]["bytes"] == 8 * per_payload
    assert plan["per_level"][1]["bytes"] == 2 * per_payload
    assert plan["fp32_feature_bytes"] == (8 + 2) * 4 * 8 * 64
    np.testing.assert_allclose(plan["reduction"], 4 * 64 / (64 + 4))
    # forwarded size is min(r_node, fanout·r), not r_node blindly
    shrunk = wire_bytes_plan(t, r_local=2, r_node=100, d=16, compress="none")
    assert shrunk["per_level"][1]["r_child"] == 8  # 4·2, not 100
    assert default_r_node(8, 32) == 32 and default_r_node(64, 32) == 64


# ---------------------------------------------------------------------------
# host driver
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fanouts", [(4,), (4, 2), (2, 2, 2)])
@pytest.mark.parametrize("compress", ["int8", "none"])
def test_host_driver_contract(fanouts, compress):
    feats, assign = _clustered(256, 16)
    sel = tree_select_host(
        jnp.asarray(feats), TreeTopology(fanouts), r_local=6, r_final=8,
        compress=compress,
    )
    idx = np.asarray(sel.indices)
    assert idx.shape == (8,) and len(set(idx.tolist())) == 8
    assert (0 <= idx).all() and (idx < 256).all()
    w = np.asarray(sel.weights)
    assert w.sum() == 256.0 and (w >= 0).all()  # exact γ partition
    # well-separated clusters: the selection spans most of them
    assert len(set(assign[idx].tolist())) >= 7


def test_host_driver_ragged_leaves():
    """n not divisible by n_leaves: array_split semantics, no phantom or
    dropped points — Σγ still equals the exact pool size."""
    feats, _ = _clustered(251, 12, seed=3)
    sel = tree_select_host(
        jnp.asarray(feats), TreeTopology((4, 2)), r_local=5, r_final=8
    )
    assert float(np.asarray(sel.weights).sum()) == 251.0
    assert len(set(np.asarray(sel.indices).tolist())) == 8


def test_host_driver_int8_matches_fp32_on_separated_clusters():
    """The int8 wire perturbs candidate features by ≤ scale/2 per row —
    on well-separated clusters the selected medoid set is unchanged."""
    feats, _ = _clustered(256, 32, seed=1)
    t = TreeTopology((4, 2))
    a = tree_select_host(jnp.asarray(feats), t, r_local=6, r_final=8,
                         compress="int8")
    b = tree_select_host(jnp.asarray(feats), t, r_local=6, r_final=8,
                         compress="none")
    assert set(np.asarray(a.indices).tolist()) == set(
        np.asarray(b.indices).tolist())


def test_host_driver_deeper_tree_stays_close():
    """Depth-2/3 coverage stays within a small factor of the depth-1 tree
    (the GreeDi-composition loss is empirically tiny)."""
    feats, _ = _clustered(512, 16, seed=2)
    covs = {}
    for fo in [(8,), (4, 2), (2, 2, 2)]:
        covs[fo] = float(
            tree_select_host(jnp.asarray(feats), TreeTopology(fo),
                             r_local=8, r_final=10).coverage
        )
    assert covs[(4, 2)] <= 1.3 * covs[(8,)], covs
    assert covs[(2, 2, 2)] <= 1.3 * covs[(8,)], covs


def test_host_driver_error_paths():
    feats, _ = _clustered(64, 8)
    t = TreeTopology((4,))
    with pytest.raises(ValueError, match="wire mode"):
        tree_select_host(jnp.asarray(feats), t, 4, 8, compress="fp16")
    with pytest.raises(ValueError, match="exceeds the shard pool"):
        tree_select_host(jnp.asarray(feats), t, 40, 8)
    with pytest.raises(ValueError, match="fewer than"):
        tree_select_host(jnp.asarray(feats), t, 1, 8)
    with pytest.raises(ValueError, match="r_node"):
        tree_select_host(jnp.asarray(feats), TreeTopology((2, 2)), 4, 4,
                         r_node=0)
    with pytest.raises(ValueError, match="leaves"):
        tree_select_host(jnp.asarray(feats), TreeTopology((65,)), 1, 8)
    with pytest.raises(ValueError, match="budgets must be"):
        tree_select_host(jnp.asarray(feats), t, 4, 0)


def test_selector_select_tree_contract_and_provenance():
    feats, _ = _clustered(300, 24)
    sel = CraigSelector(CraigConfig(fraction=0.05, per_class=False))
    cs = sel.select_tree(jnp.asarray(feats), (4, 2))
    assert cs.size == 15
    np.testing.assert_allclose(cs.weights.sum(), 300.0)
    assert cs.engine["name"] == "tree"
    assert tuple(cs.engine["fanouts"]) == (4, 2)
    assert cs.engine["local"]["name"] == "matrix"  # auto at n_local=75
    restored = engine_config_from_dict(cs.engine)
    assert isinstance(restored, TreeSelectConfig)
    # cover mode has no tree path (needs exact prefix coverages)
    with pytest.raises(ValueError, match="budget"):
        CraigSelector(
            CraigConfig(mode="cover", epsilon=1.0, per_class=False)
        ).select_tree(jnp.asarray(feats), (2,))


def test_selector_select_tree_cosine_units():
    """metric='cosine' reports coverage in 1−cosθ units (same invariant
    as select/select_distributed): bounded by n·max(1−cosθ) ≤ 2n."""
    feats, _ = _clustered(200, 16, seed=5)
    cs = CraigSelector(
        CraigConfig(fraction=0.05, per_class=False, metric="cosine")
    ).select_tree(jnp.asarray(feats), (2, 2))
    assert 0.0 <= cs.coverage <= 2.0 * 200


# ---------------------------------------------------------------------------
# tier 2: mesh driver on 8 simulated devices (subprocess — XLA_FLAGS must
# be set before jax initializes; the main process keeps seeing 1 device)
# ---------------------------------------------------------------------------

MESH_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.distributed import distributed_select
    from repro.distributed.tree_select import (
        TreeTopology, tree_mesh, tree_select_host, tree_select_mesh)
    data_mesh = jax.make_mesh((8,), ("data",),
                              axis_types=(jax.sharding.AxisType.Auto,))

    k = jax.random.PRNGKey(0)
    centers = jax.random.normal(k, (8, 16)) * 5.0
    assign = jax.random.randint(jax.random.PRNGKey(1), (512,), 0, 8)
    feats = centers[assign] + 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), (512, 16))

    # depth-1 fp32 tree ≡ the existing two-round path, bit for bit
    topo1 = TreeTopology((8,))
    ds = distributed_select(feats, data_mesh, r_local=6, r_final=10)
    th = tree_select_host(feats, topo1, 6, 10, compress="none")
    tm = tree_select_mesh(feats, tree_mesh(topo1), topo1, 6, 10,
                          compress="none")
    for t in (th, tm):
        assert np.array_equal(np.asarray(t.indices), np.asarray(ds.indices))
        assert np.array_equal(np.asarray(t.weights), np.asarray(ds.weights))
        np.testing.assert_allclose(float(t.coverage), float(ds.coverage),
                                   rtol=1e-5)

    # mesh ≡ host at depth 2 and 3, int8 wire (same leaf order, same
    # wire codec, same merge budgets → identical selections)
    for fo in [(4, 2), (2, 2, 2), (2, 4)]:
        topo = TreeTopology(fo)
        m = tree_select_mesh(feats, tree_mesh(topo), topo, 6, 10,
                             compress="int8")
        h = tree_select_host(feats, topo, 6, 10, compress="int8")
        assert np.array_equal(np.asarray(m.indices), np.asarray(h.indices)), fo
        assert np.array_equal(np.asarray(m.weights), np.asarray(h.weights)), fo
        assert np.asarray(m.weights).sum() == 512.0
        np.testing.assert_allclose(float(m.coverage), float(h.coverage),
                                   rtol=1e-5)

    # determinism of the mesh program
    topo = TreeTopology((4, 2))
    a = tree_select_mesh(feats, tree_mesh(topo), topo, 6, 10)
    b = tree_select_mesh(feats, tree_mesh(topo), topo, 6, 10)
    assert np.array_equal(np.asarray(a.indices), np.asarray(b.indices))

    # ragged pool is rejected with the informative error (no silent pad)
    try:
        tree_select_mesh(feats[:509], tree_mesh(topo1), topo1, 6, 10)
        raise SystemExit("expected ValueError for ragged mesh pool")
    except ValueError as e:
        assert "not divisible" in str(e), e
    # mesh without the level axes is rejected
    try:
        tree_select_mesh(feats, data_mesh, topo1, 6, 10)
        raise SystemExit("expected ValueError for missing level axis")
    except ValueError as e:
        assert "missing level axis" in str(e), e
    print("TREE_MESH_OK")
    """
)


@pytest.mark.tier2
def test_tree_mesh_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT],
        env=env, capture_output=True, text=True, timeout=480,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "TREE_MESH_OK" in out.stdout
