"""Pallas TPU kernel: blocked pairwise L2 distance matrix (CRAIG matrix mode).

Computes D[i, j] = ‖x_i − y_j‖ for x (n, d), y (m, d), tiled so each
(block_n × block_m) output tile is produced from one MXU matmul plus rank-1
squared-norm corrections, with the proxy dim d resident in VMEM.

Used by the `matrix` selection engine when the per-shard pool is small enough
to hold (n, m) in HBM (per-class selection typically is); the matrix-free
`fl_gains` kernel covers the large-pool regime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TPU_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel")
)

__all__ = ["pairwise_l2_pallas"]


def _pairwise_kernel(x_ref, y_ref, sqx_ref, sqy_ref, out_ref):
    dots = jax.lax.dot_general(
        x_ref[...],
        y_ref[...],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    d2 = sqx_ref[...] + sqy_ref[...] - 2.0 * dots
    out_ref[...] = jnp.sqrt(jnp.maximum(d2, 0.0))


@functools.partial(jax.jit, static_argnames=("block_n", "block_m", "interpret"))
def pairwise_l2_pallas(
    x: jax.Array,
    y: jax.Array,
    *,
    block_n: int = 256,
    block_m: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Blocked pairwise distances. n, m must be block-aligned; d % 128 == 0.

    Returns (n, m) fp32 distances.
    """
    n, d = x.shape
    m = y.shape[0]
    assert n % block_n == 0 and m % block_m == 0, (n, m, block_n, block_m)
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    sqx = jnp.sum(x * x, axis=1, keepdims=True)  # (n, 1)
    sqy = jnp.sum(y * y, axis=1, keepdims=True).T  # (1, m)
    grid = (n // block_n, m // block_m)
    return pl.pallas_call(
        _pairwise_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda ni, mi: (ni, 0)),
            pl.BlockSpec((block_m, d), lambda ni, mi: (mi, 0)),
            pl.BlockSpec((block_n, 1), lambda ni, mi: (ni, 0)),
            pl.BlockSpec((1, block_m), lambda ni, mi: (0, mi)),
        ],
        out_specs=pl.BlockSpec((block_n, block_m), lambda ni, mi: (ni, mi)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        compiler_params=_TPU_PARAMS,
        interpret=interpret,
    )(x, y, sqx, sqy)
