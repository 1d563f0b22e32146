"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

# the kernel module (the package re-exports its op under the same name)
ce = importlib.import_module("repro.kernels.ce_proxy")


@pytest.mark.parametrize("n,m,d", [(64, 32, 8), (300, 150, 37), (513, 100, 130), (128, 128, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_l2(n, m, d, dtype):
    kx, ky = jax.random.split(jax.random.PRNGKey(n + m))
    x = jax.random.normal(kx, (n, d), dtype)
    y = jax.random.normal(ky, (m, d), dtype)
    got = ops.pairwise_l2(x, y)
    want = ref.pairwise_l2_ref(x, y)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("n,m,d", [(64, 64, 16), (250, 90, 33), (512, 256, 128), (80, 300, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fl_gains(n, m, d, dtype):
    keys = jax.random.split(jax.random.PRNGKey(n * 3 + m), 3)
    x = jax.random.normal(keys[0], (n, d), dtype)
    e = jax.random.normal(keys[1], (m, d), dtype)
    cur_max = jax.random.uniform(keys[2], (n,), jnp.float32, 0.0, 3.0)
    d_max = jnp.float32(12.0)
    x32, e32 = x.astype(jnp.float32), e.astype(jnp.float32)
    got = ops.fl_gains(
        x32, e32, cur_max, jnp.sum(x32 * x32, 1), jnp.sum(e32 * e32, 1), d_max
    )
    want = ref.fl_gains_ref(x32, e32, cur_max, d_max)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)


@pytest.mark.parametrize(
    "t,d,v,bt,bv",
    [
        (32, 16, 64, 16, 16),
        (70, 33, 96, 32, 32),
        (128, 64, 512, 64, 128),
        (16, 8, 1000, 16, 8),  # block_v fallback: 1000 % 8 == 0
        (40, 24, 200, 16, 64),  # v not a block_v multiple: last block padded
        (48, 96, 130, 16, 32),  # d not a power of two, v one block + 2
        (24, 16, 64, 128, 16),  # block_t larger than T
        (40, 48, 300, None, 128),  # token tile from the VMEM rule
    ],
)
def test_ce_proxy(t, d, v, bt, bv):
    keys = jax.random.split(jax.random.PRNGKey(t + v), 3)
    h = jax.random.normal(keys[0], (t, d)) * 0.5
    w = jax.random.normal(keys[1], (d, v)) * 0.1
    y = jax.random.randint(keys[2], (t,), 0, v)
    # labels at both ends of the vocab and at the start of its last block,
    # which holds the block padding when v % bv != 0
    y = y.at[:3].set(jnp.array([0, v - 1, (v - 1) // bv * bv]))
    got = ops.ce_proxy(h, w, y, block_t=bt, block_v=bv)
    want = ref.ce_proxy_ref(h, w, y)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_ce_proxy_bf16_hidden():
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    h = (jax.random.normal(keys[0], (64, 32)) * 0.5).astype(jnp.bfloat16)
    w = jax.random.normal(keys[1], (32, 128)) * 0.1
    y = jax.random.randint(keys[2], (64,), 0, 128)
    got = ops.ce_proxy(h, w, y, block_t=32, block_v=32)
    want = ref.ce_proxy_ref(h, w, y)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-3)


def test_ce_proxy_label_term_is_onehot_product():
    """The gathered label column, cast to bf16 and read as fp32, equals the
    one-hot product the kernel's MXU used to compute (bf16 operands, fp32
    accumulation over the vocab blocks) bit for bit."""
    t, d, v, bv = 64, 32, 300, 64
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    w = jax.random.normal(keys[0], (d, v)) * 0.1  # not bf16-representable
    y = jax.random.randint(keys[1], (t,), 0, v).at[:2].set(jnp.array([0, v - 1]))
    wp = jnp.pad(w, ((0, 0), (0, -v % bv))).astype(jnp.bfloat16)
    onehot = jax.nn.one_hot(y, wp.shape[1], dtype=jnp.bfloat16)
    old = jnp.zeros((t, d), jnp.float32)
    for lo in range(0, wp.shape[1], bv):
        old = old + jax.lax.dot_general(
            onehot[:, lo:lo + bv], wp[:, lo:lo + bv], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    new = jnp.take(w.T, y, axis=0).astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


def _pallas_grids(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["grid_mapping"].grid
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_grids(sub)


@pytest.mark.parametrize("d", [2048, 4096, 6144])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_ce_proxy_tile_rule(d, dtype):
    """With no block_t, the token tile is the largest power of two ≤ 512
    whose VMEM count fits the budget, at least 8, and ops.ce_proxy runs
    the kernel on it."""
    itemsize = jnp.dtype(dtype).itemsize
    bt = ce.pick_block_t(d, 512, itemsize)
    assert bt >= 8 and bt & (bt - 1) == 0
    assert ce.vmem_bytes(bt, 512, d, itemsize) <= ce.VMEM_BUDGET
    assert bt == 512 or ce.vmem_bytes(2 * bt, 512, d, itemsize) > ce.VMEM_BUDGET
    if d == 2048 and dtype == jnp.bfloat16:
        assert bt == 512
    t, v = 2048, 4096
    jaxpr = jax.make_jaxpr(
        lambda h, w, y: ops.ce_proxy(h, w, y, compute_dtype=dtype, interpret=True)
    )(
        jax.ShapeDtypeStruct((t, d), dtype),
        jax.ShapeDtypeStruct((d, v), jnp.float32),
        jax.ShapeDtypeStruct((t,), jnp.int32),
    )
    assert list(_pallas_grids(jaxpr.jaxpr)) == [(t // bt, v // 512)]


def test_fl_gains_inside_greedy_matches_matrix_engine():
    """End-to-end: the Pallas gains path yields identical greedy selections."""
    from repro.core import facility_location as fl

    feats = jax.random.normal(jax.random.PRNGKey(5), (200, 24))
    r_jax = fl.greedy_fl_features(feats, 16, gains_impl="jax")
    r_pal = fl.greedy_fl_features(feats, 16, gains_impl="pallas")
    np.testing.assert_array_equal(
        np.asarray(r_jax.indices), np.asarray(r_pal.indices)
    )
    np.testing.assert_allclose(
        np.asarray(r_jax.weights), np.asarray(r_pal.weights)
    )
