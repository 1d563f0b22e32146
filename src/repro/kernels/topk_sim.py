"""Pallas TPU kernel: blockwise top-k similarity neighbor builder (sparse CRAIG).

The sparse selection engine (DESIGN.md §3.5) replaces the dense (n, n)
similarity structure with a k-nearest-neighbor graph: for every point i it
keeps only the k largest similarities s_ij = d_max − ‖x_i − x_j‖ together
with their column indices.  This kernel builds that graph by streaming
(block_n × block_m) similarity tiles — the same MXU matmul + rank-1
squared-norm epilogue as ``pairwise_l2`` / ``fl_gains`` — and folding each
tile into a per-row running top-k that stays resident in the output tiles
across the column sweep ("revisiting" accumulation, fl_gains-style).  The
dense (n, n) matrix is never materialized: peak memory is
O(block_n · block_m) VMEM per tile plus the O(n · k) output.

The in-tile merge is selection-sort shaped: k unrolled iterations, each a
max-reduce over the carry row and the tile row, a first-hit index extraction
(broadcasted_iota + min-reduce — no 1D iota, no argmax primitive), and a
mask-out of the winner.  All ops are plain VPU compares/reductions, so the
kernel lowers on Mosaic without lax.top_k/sort support; cost per tile is
O(k · block_n · (k + block_m)), small next to the MXU term for k ≲ 128.

Inputs are pre-arranged by :mod:`repro.kernels.ops`:
  x      (n, d)   row-block features (fp32), d padded to a lane multiple
  y      (m, d)   column-block features (= x padded; m ≥ n)
  sqx    (n, 1)   ‖x_i‖²
  sqy    (1, m)   ‖y_j‖²; padded columns carry +1e30 so their similarity is
                  ≈ −1e15 and they never enter a top-k (requires k ≤ n)
  dmax   (1, 1)   similarity offset: s = dmax − dist ≥ 0 for real columns
Outputs:
  vals   (n, k)   fp32 top-k similarities per row, sorted descending
  idx    (n, k)   int32 column indices aligned with ``vals``
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TPU_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary")
)

__all__ = ["topk_sim_pallas"]

_NEG = -1e30  # top-k init / mask-out value (−inf is unsafe on some backends)


def _first_hit(values: jax.Array, target: jax.Array) -> jax.Array:
    """Lowest column position where ``values`` equals per-row ``target``.

    values: (bn, w); target: (bn, 1).  Returns (bn, 1) int32 positions.
    """
    w = values.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, values.shape, 1)
    return jnp.min(jnp.where(values == target, pos, w), axis=1, keepdims=True)


def _make_topk_kernel(k: int, block_m: int):
    def kernel(x_ref, y_ref, sqx_ref, sqy_ref, dmax_ref, vals_ref, idx_ref):
        mi = pl.program_id(1)

        @pl.when(mi == 0)
        def _init():
            vals_ref[...] = jnp.full_like(vals_ref, _NEG)
            idx_ref[...] = jnp.zeros_like(idx_ref)

        dots = jax.lax.dot_general(
            x_ref[...],
            y_ref[...],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bn, bm)
        d2 = sqx_ref[...] + sqy_ref[...] - 2.0 * dots
        tile_v = dmax_ref[...] - jnp.sqrt(jnp.maximum(d2, 0.0))
        tile_i = mi * block_m + jax.lax.broadcasted_iota(
            jnp.int32, tile_v.shape, 1
        )

        carry_v = vals_ref[...]  # (bn, k) — previous blocks' top-k
        carry_i = idx_ref[...]
        # Selection-sort merge: carry wins ties (its entries come from
        # earlier column blocks, i.e. lower indices — matches lax.top_k's
        # stable index-ascending tie-break).
        for t in range(k):
            c_best = jnp.max(carry_v, axis=1, keepdims=True)  # (bn, 1)
            t_best = jnp.max(tile_v, axis=1, keepdims=True)
            use_carry = c_best >= t_best
            c_pos = _first_hit(carry_v, c_best)
            t_pos = _first_hit(tile_v, t_best)
            c_cols = jax.lax.broadcasted_iota(jnp.int32, carry_v.shape, 1)
            t_cols = jax.lax.broadcasted_iota(jnp.int32, tile_v.shape, 1)
            c_val = jnp.sum(
                jnp.where(c_cols == c_pos, carry_i, 0), axis=1, keepdims=True
            )
            t_val = jnp.sum(
                jnp.where(t_cols == t_pos, tile_i, 0), axis=1, keepdims=True
            )
            vals_ref[:, t : t + 1] = jnp.where(use_carry, c_best, t_best)
            idx_ref[:, t : t + 1] = jnp.where(use_carry, c_val, t_val)
            # Knock the winner out of its source array.
            carry_v = jnp.where(
                use_carry & (c_cols == c_pos), _NEG, carry_v
            )
            tile_v = jnp.where(
                (~use_carry) & (t_cols == t_pos), _NEG, tile_v
            )

    return kernel


@functools.partial(
    jax.jit, static_argnames=("k", "block_n", "block_m", "interpret")
)
def topk_sim_pallas(
    x: jax.Array,
    y: jax.Array,
    sqx: jax.Array,
    sqy: jax.Array,
    dmax: jax.Array,
    *,
    k: int,
    block_n: int = 256,
    block_m: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Blocked top-k similarity rows.  Shapes must already be block-aligned.

    Args:
      x: (n, d) fp32, n % block_n == 0, d % 128 == 0.
      y: (m, d) fp32, m % block_m == 0 (the column/candidate features).
      sqx: (n, 1) fp32 squared norms of x.
      sqy: (1, m) fp32 squared norms of y (+1e30 on padded columns).
      dmax: (1, 1) fp32 similarity offset.
      k: neighbors kept per row (static; k ≤ #valid columns).
    Returns:
      vals (n, k) fp32 descending, idx (n, k) int32.
    """
    n, d = x.shape
    m = y.shape[0]
    assert n % block_n == 0 and m % block_m == 0, (n, m, block_n, block_m)
    grid = (n // block_n, m // block_m)
    vals, idx = pl.pallas_call(
        _make_topk_kernel(k, block_m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda ni, mi: (ni, 0)),
            pl.BlockSpec((block_m, d), lambda ni, mi: (mi, 0)),
            pl.BlockSpec((block_n, 1), lambda ni, mi: (ni, 0)),
            pl.BlockSpec((1, block_m), lambda ni, mi: (0, mi)),
            pl.BlockSpec((1, 1), lambda ni, mi: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, k), lambda ni, mi: (ni, 0)),
            pl.BlockSpec((block_n, k), lambda ni, mi: (ni, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, k), jnp.float32),
            jax.ShapeDtypeStruct((n, k), jnp.int32),
        ],
        compiler_params=_TPU_PARAMS,
        interpret=interpret,
    )(
        x.astype(jnp.float32),
        y.astype(jnp.float32),
        sqx.astype(jnp.float32),
        sqy.astype(jnp.float32),
        dmax.astype(jnp.float32),
    )
    return vals, idx
