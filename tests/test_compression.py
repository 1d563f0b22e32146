"""Gradient compression: quantization error bounds + error-feedback SGD.
Plus the 2-D per-row feature-payload path (tree-selection candidate wire)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.compression import (
    dequantize_int8,
    dequantize_rows_int8,
    make_error_feedback,
    quantize_int8,
    quantize_rows_int8,
)


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3.0
    q, s = quantize_int8(x)
    y = dequantize_int8(q, s, x.shape)
    # per-block absmax scaling: |err| ≤ scale/2 = absmax/254 per block
    err = np.abs(np.asarray(x - y))
    bound = np.repeat(np.asarray(s) / 2 + 1e-9, 256)[:1000]
    assert (err <= bound + 1e-7).all()


def test_quantize_shapes_and_dtype():
    x = jax.random.normal(jax.random.PRNGKey(1), (7, 33))
    q, s = quantize_int8(x)
    assert q.dtype == jnp.int8
    y = dequantize_int8(q, s, x.shape)
    assert y.shape == x.shape


def test_quantize_rows_roundtrip_error_bound():
    """Per-row absmax scaling: |err| ≤ scale_i/2 within each row — a row
    with a large-magnitude outlier must not degrade other rows."""
    x = jax.random.normal(jax.random.PRNGKey(3), (33, 48)) * 2.0
    x = x.at[5].multiply(100.0)  # outlier row: only its own bound widens
    q, s = quantize_rows_int8(x)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert s.shape == (33,) and s.dtype == jnp.float32
    y = dequantize_rows_int8(q, s)
    assert y.dtype == jnp.float32
    err = np.abs(np.asarray(x - y))
    bound = np.asarray(s)[:, None] / 2 + 1e-6
    assert (err <= bound).all()
    # the outlier row's scale did not leak into its neighbors
    assert np.asarray(s)[4] < np.asarray(s)[5] / 10


def test_quantize_rows_bf16_input():
    """bf16 feature payloads quantize through fp32: the round trip is
    bounded by the bf16 row's absmax scale and returns fp32."""
    x32 = jax.random.normal(jax.random.PRNGKey(4), (17, 64))
    x = x32.astype(jnp.bfloat16)
    q, s = quantize_rows_int8(x)
    y = dequantize_rows_int8(q, s)
    assert y.dtype == jnp.float32
    err = np.abs(np.asarray(x.astype(jnp.float32) - y))
    assert (err <= np.asarray(s)[:, None] / 2 + 1e-6).all()


def test_quantize_rows_rejects_non_2d():
    with pytest.raises(ValueError, match="2-D"):
        quantize_rows_int8(jnp.zeros((8,)))
    with pytest.raises(ValueError, match="2-D"):
        quantize_rows_int8(jnp.zeros((2, 3, 4)))


def test_quantize_rows_jit_safe():
    """The row codec runs under jit (it rides inside shard_map gathers)."""
    x = jax.random.normal(jax.random.PRNGKey(5), (9, 16))
    y = jax.jit(lambda v: dequantize_rows_int8(*quantize_rows_int8(v)))(x)
    yr = dequantize_rows_int8(*quantize_rows_int8(x))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))


def test_gradient_path_bit_identical():
    """The 1-D gradient codec is untouched by the 2-D generalization:
    block layout, scales, and payload bytes are exactly the legacy ones."""
    x = jax.random.normal(jax.random.PRNGKey(6), (777,)) * 0.3
    q, s = quantize_int8(x)
    # legacy reference, computed inline: pad to 256, per-block absmax
    flat = np.zeros(1024, np.float32)
    flat[:777] = np.asarray(x, np.float32)
    blocks = flat.reshape(-1, 256)
    ref_s = np.abs(blocks).max(axis=1) / 127.0 + 1e-12
    ref_q = np.clip(np.round(blocks / ref_s[:, None]), -127, 127).astype(np.int8)
    np.testing.assert_array_equal(np.asarray(q), ref_q)
    np.testing.assert_array_equal(np.asarray(s), ref_s.astype(np.float32))


def test_error_feedback_unbiased_over_time():
    """EF compensates quantization: the running delivered sum tracks the true
    gradient sum much better than naive quantization."""
    grads = {"w": jax.random.normal(jax.random.PRNGKey(2), (512,)) * 0.01}
    init_res, apply = make_error_feedback(grads)
    res = init_res()
    total_delivered = jnp.zeros(512)
    total_true = jnp.zeros(512)
    for i in range(20):
        g = {"w": jax.random.normal(jax.random.PRNGKey(i), (512,)) * 0.01}
        delivered, res = apply(g, res)
        total_delivered += delivered["w"]
        total_true += g["w"]
    # residual carries the outstanding error: delivered + residual == true sum
    np.testing.assert_allclose(
        np.asarray(total_delivered + res["w"]),
        np.asarray(total_true),
        rtol=1e-4,
        atol=1e-6,
    )


def test_compressed_sgd_converges():
    """SGD with EF-compressed gradients still reaches the optimum."""
    A = jnp.diag(jnp.array([1.0, 4.0, 9.0]))
    b = jnp.array([1.0, 2.0, 3.0])
    w_star = jnp.linalg.solve(A, b)
    w = {"w": jnp.zeros(3)}
    init_res, apply = make_error_feedback(w)
    res = init_res()
    for _ in range(300):
        g = {"w": A @ w["w"] - b}
        delivered, res = apply(g, res)
        w = {"w": w["w"] - 0.05 * delivered["w"]}
    assert float(jnp.linalg.norm(w["w"] - w_star)) < 1e-2


def test_compressed_psum_multidevice_subprocess():
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum

        mesh = jax.make_mesh((4,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 1024))

        f = jax.shard_map(
            lambda v: compressed_psum(v[0], "pod")[None],
            mesh=mesh, in_specs=(P("pod", None),),
            out_specs=P("pod", None), check_vma=False)
        got = f(x)  # every shard returns the mean
        want = jnp.mean(x, axis=0)
        err = float(jnp.max(jnp.abs(got[0] - want)))
        scale = float(jnp.max(jnp.abs(want)))
        assert err / scale < 0.02, (err, scale)
        print("PSUM_OK", err / scale)
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", script],
        env=env, capture_output=True, text=True, timeout=480,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PSUM_OK" in out.stdout
