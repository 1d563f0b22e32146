"""Checkpoint manager: atomicity, keep-k, async, extras, elastic restore."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 4)), "b": jnp.zeros(4)},
        "opt": (jnp.zeros((), jnp.int32), [jnp.ones(3)]),
    }


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = _tree()
    mgr.save(5, tree, extras={"cursor": 42})
    got, extras = mgr.restore(jax.tree.map(jnp.zeros_like, tree))
    assert extras == {"cursor": 42}
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_latest_pointer_and_keep_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s))
    assert mgr.latest_step() == 4
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert dirs == ["step_00000003", "step_00000004"]


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(1, _tree(), blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 1


def test_atomicity_no_partial_dirs(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    mgr.save(7, _tree())
    # no .tmp leftovers
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]
    # manifest parses
    with open(tmp_path / "step_00000007" / "manifest.json") as f:
        m = json.load(f)
    assert m["step"] == 7 and len(m["leaves"]) == 4


def test_restore_specific_step(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(1, _tree(1))
    mgr.save(2, _tree(2))
    got, _ = mgr.restore(jax.tree.map(jnp.zeros_like, _tree()), step=1)
    want = _tree(1)
    np.testing.assert_allclose(
        np.asarray(got["params"]["w"]), np.asarray(want["params"]["w"])
    )


def test_elastic_restore_with_shardings(tmp_path):
    """Restore places arrays with the provided (new-mesh) shardings."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    mgr = CheckpointManager(str(tmp_path), keep=1)
    tree = _tree()
    mgr.save(3, tree)
    shardings = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
    got, _ = mgr.restore(jax.tree.map(jnp.zeros_like, tree), shardings=shardings)
    assert got["params"]["w"].sharding == NamedSharding(mesh, P())


def test_missing_checkpoint_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        mgr.restore({"x": jnp.zeros(1)})


def test_streaming_selector_rides_extras_kill_and_resume(tmp_path):
    """Mid-stream sieve state checkpoints through the extras channel and a
    'killed' service resumes bit-identically against the uninterrupted run
    (engines.streaming state is JSON-able by construction)."""
    from repro.core.engines.streaming import StreamingSelector

    rng = np.random.RandomState(0)
    deltas = [rng.randn(30, 5).astype(np.float32) for _ in range(4)]
    pool = np.concatenate(deltas)

    straight = StreamingSelector(12, 5)
    for d in deltas:
        straight.ingest(d)

    sel = StreamingSelector(12, 5)
    sel.ingest(deltas[0])
    sel.ingest(deltas[1])
    mgr = CheckpointManager(str(tmp_path), keep=1)
    mgr.save(2, _tree(), extras={"streaming": sel.state_dict()})
    del sel  # the "kill"

    _, extras = CheckpointManager(str(tmp_path)).restore(
        jax.tree.map(jnp.zeros_like, _tree())
    )
    resumed = StreamingSelector(12, 5)
    resumed.load_state_dict(extras["streaming"])
    assert resumed.n_seen == 60
    resumed.ingest(deltas[2])
    resumed.ingest(deltas[3])

    ra, rb = straight.result(pool), resumed.result(pool)
    np.testing.assert_array_equal(np.asarray(ra.indices), np.asarray(rb.indices))
    np.testing.assert_array_equal(np.asarray(ra.weights), np.asarray(rb.weights))
    assert float(np.asarray(rb.weights).sum()) == pytest.approx(120.0)
