"""Pallas TPU kernels: fused facility-location marginal gains (CRAIG hot-spot).

One greedy step of CRAIG (paper Alg. 1 line 3) evaluates, for every candidate
e, the marginal gain

    gain(e) = Σ_i relu( s_ie − cur_max_i ),     s_ie = d_max − ‖x_i − x_e‖

over the whole pool i ∈ V.  Done naively this materializes an (n, m)
similarity matrix in HBM per step.  ``fl_gains_pallas`` fuses

    pairwise-distance (MXU matmul x·eᵀ + rank-1 squared-norm terms)
      → similarity → subtract running max → relu → reduce over n

entirely in VMEM, tiled (block_n × block_m), accumulating the n-reduction
across grid steps into the (1, block_m) output tile.  Arithmetic intensity is
that of a matmul with a free epilogue — the MXU term dominates.

``fl_gains_argmax_pallas`` (DESIGN.md §2, §3.6) extends the same sweep with a
fused argmax epilogue for the device-resident greedy engine: the gains tile
accumulates in a VMEM scratch buffer instead of the output, and on the last
n-step each candidate block reduces itself to a single
``(best_gain, best_index)`` partial (max-reduce + first-hit index extraction —
no argmax primitive, same idiom as ``topk_sim``).  One kernel launch per
greedy round replaces the gains-materialize + separate argmax pair; the
host-side finalize is an O(m/block_m) reduction over the partials.
Already-selected candidates are excluded *inside* the epilogue via an
additive ``penalty`` row (−1e30 on chosen/padded columns), so no masked
(1, m) gains vector ever exists.

Inputs are pre-arranged by :mod:`repro.kernels.ops`:
  x      (n, d)   pool proxy features (fp32 or bf16), d padded to a lane
                  multiple
  e      (m, d)   candidate features (same dtype as x)
  madj   (n, 1)   d_max − cur_max_i   (similarity headroom per point, fp32)
  sqx    (n, 1)   ‖x_i‖²  (fp32)
  sqe    (1, m)   ‖x_e‖²  (fp32)
  penalty (1, m)  0 for live candidates, −1e30 for chosen/padded columns
                  (argmax variant only)
Outputs:
  gains  (1, m)   fp32                       (fl_gains_pallas)
  gains (1, m) + best_g (1, m_blocks) fp32 + best_i (1, m_blocks) int32
                                             (fl_gains_argmax_pallas)

TPU mapping notes (DESIGN.md §2): block shapes default to (512, 256) with the
full proxy dim d resident (d ≤ 8·128 after padding); all matmul dims are
multiples of 128 so the 128×128 MXU tiles are dense.  The n-grid axis is the
inner (fastest) axis so the output tile (or the scratch accumulator) stays
resident while the reduction accumulates ("revisiting" accumulation pattern).
Tiles may be bf16 (MXU-native) while distances, gains, and the running
accumulation stay fp32 (``preferred_element_type``).
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
_REPLAY_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary")
)

__all__ = ["fl_gains_pallas", "fl_gains_argmax_pallas", "fl_replay_pallas"]


def _first_hit(values: jax.Array, target: jax.Array) -> jax.Array:
    """Lowest column position where ``values`` equals per-row ``target``.

    values: (r, w); target: (r, 1).  Returns (r, 1) int32 positions — the
    no-argmax-primitive idiom shared with ``topk_sim`` (DESIGN.md §2).
    """
    w = values.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, values.shape, 1)
    return jnp.min(jnp.where(values == target, pos, w), axis=1, keepdims=True)


def _fl_gains_kernel(x_ref, e_ref, madj_ref, sqx_ref, sqe_ref, out_ref):
    """Grid = (m_blocks, n_blocks); n is the inner reduction axis."""
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...]  # (bn, d)
    e = e_ref[...]  # (bm, d)
    # Squared distance via the MXU: ‖x−e‖² = ‖x‖² + ‖e‖² − 2 x·e
    dots = jax.lax.dot_general(
        x,
        e,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (bn, bm)
    d2 = sqx_ref[...] + sqe_ref[...] - 2.0 * dots
    dist = jnp.sqrt(jnp.maximum(d2, 0.0))
    # gain contribution: relu((d_max − cur_max) − dist)
    contrib = jnp.maximum(madj_ref[...] - dist, 0.0)  # (bn, bm)
    out_ref[...] += jnp.sum(contrib, axis=0, keepdims=True)  # (1, bm)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "interpret")
)
def fl_gains_pallas(
    x: jax.Array,
    e: jax.Array,
    madj: jax.Array,
    sqx: jax.Array,
    sqe: jax.Array,
    *,
    block_n: int = 512,
    block_m: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Blocked fused FL gains. Shapes must already be block-aligned.

    Args:
      x: (n, d) fp32, n % block_n == 0, d % 128 == 0.
      e: (m, d) fp32, m % block_m == 0.
      madj: (n, 1) fp32 = d_max − cur_max.
      sqx: (n, 1) fp32 squared norms of x.
      sqe: (1, m) fp32 squared norms of e.
    Returns:
      (m,) fp32 gains.
    """
    n, d = x.shape
    m = e.shape[0]
    assert n % block_n == 0 and m % block_m == 0, (n, m, block_n, block_m)
    grid = (m // block_m, n // block_n)
    out = pl.pallas_call(
        _fl_gains_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda mi, ni: (ni, 0)),
            pl.BlockSpec((block_m, d), lambda mi, ni: (mi, 0)),
            pl.BlockSpec((block_n, 1), lambda mi, ni: (ni, 0)),
            pl.BlockSpec((block_n, 1), lambda mi, ni: (ni, 0)),
            pl.BlockSpec((1, block_m), lambda mi, ni: (0, mi)),
        ],
        out_specs=pl.BlockSpec((1, block_m), lambda mi, ni: (0, mi)),
        out_shape=jax.ShapeDtypeStruct((1, m), jnp.float32),
        compiler_params=_TPU_PARAMS,
        interpret=interpret,
    )(
        x.astype(jnp.float32),
        e.astype(jnp.float32),
        madj.astype(jnp.float32),
        sqx.astype(jnp.float32),
        sqe.astype(jnp.float32),
    )
    return out[0]


def _make_argmax_kernel(block_m: int):
    def kernel(
        x_ref, e_ref, madj_ref, sqx_ref, sqe_ref, pen_ref,
        gains_ref, bg_ref, bi_ref,
    ):
        """Grid = (m_blocks, n_blocks); n inner.  The gains tile accumulates
        across the n sweep ("revisiting"); the last n step fuses the per-block
        argmax epilogue and emits this candidate block's (best_gain, best_idx)
        partial."""
        mi = pl.program_id(0)
        ni = pl.program_id(1)

        @pl.when(ni == 0)
        def _init():
            gains_ref[...] = jnp.zeros_like(gains_ref)

        dots = jax.lax.dot_general(
            x_ref[...],
            e_ref[...],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bn, bm) fp32 even for bf16 tiles
        d2 = sqx_ref[...] + sqe_ref[...] - 2.0 * dots
        dist = jnp.sqrt(jnp.maximum(d2, 0.0))
        contrib = jnp.maximum(madj_ref[...] - dist, 0.0)
        gains_ref[...] += jnp.sum(contrib, axis=0, keepdims=True)

        @pl.when(ni == pl.num_programs(1) - 1)
        def _epilogue():
            total = gains_ref[...] + pen_ref[...]  # (1, bm)
            best = jnp.max(total, axis=1, keepdims=True)  # (1, 1)
            pos = _first_hit(total, best)  # (1, 1) int32, lowest tie
            bg_ref[...] = best
            bi_ref[...] = mi * block_m + pos

    return kernel


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "interpret")
)
def fl_gains_argmax_pallas(
    x: jax.Array,
    e: jax.Array,
    madj: jax.Array,
    sqx: jax.Array,
    sqe: jax.Array,
    penalty: jax.Array,
    *,
    block_n: int = 512,
    block_m: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused gains sweep + per-block argmax partials (device greedy engine).

    Args:
      x: (n, d) fp32/bf16, n % block_n == 0, d % 128 == 0.
      e: (m, d) candidates, m % block_m == 0, same dtype as x.
      madj: (n, 1) fp32 = d_max − cur_max (−1e30 on padded pool rows).
      sqx: (n, 1) fp32 squared norms of x.
      sqe: (1, m) fp32 squared norms of e.
      penalty: (1, m) fp32 — 0 for live candidates, −1e30 for columns that
        must not win (already-selected or padding).
    Returns:
      (gains (m,) fp32, best_g (m_blocks,) fp32, best_i (m_blocks,) int32):
      the full un-penalized gains vector (the device engine keeps it as its
      Minoux upper bounds between sweeps) plus each candidate block's top
      penalized gain and its global candidate index (lowest index on ties).
      The caller finalizes the winner with an O(m_blocks) argmax / top-k.
    """
    n, d = x.shape
    m = e.shape[0]
    assert n % block_n == 0 and m % block_m == 0, (n, m, block_n, block_m)
    assert x.dtype == e.dtype, (x.dtype, e.dtype)
    n_blocks = n // block_n
    m_blocks = m // block_m
    grid = (m_blocks, n_blocks)
    gains, bg, bi = pl.pallas_call(
        _make_argmax_kernel(block_m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda mi, ni: (ni, 0)),
            pl.BlockSpec((block_m, d), lambda mi, ni: (mi, 0)),
            pl.BlockSpec((block_n, 1), lambda mi, ni: (ni, 0)),
            pl.BlockSpec((block_n, 1), lambda mi, ni: (ni, 0)),
            pl.BlockSpec((1, block_m), lambda mi, ni: (0, mi)),
            pl.BlockSpec((1, block_m), lambda mi, ni: (0, mi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m), lambda mi, ni: (0, mi)),
            pl.BlockSpec((pl.squeezed, 1, 1), lambda mi, ni: (mi, 0, 0)),
            pl.BlockSpec((pl.squeezed, 1, 1), lambda mi, ni: (mi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, m), jnp.float32),
            jax.ShapeDtypeStruct((m_blocks, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((m_blocks, 1, 1), jnp.int32),
        ],
        compiler_params=_TPU_PARAMS,
        interpret=interpret,
    )(
        x,
        e,
        madj.astype(jnp.float32),
        sqx.astype(jnp.float32),
        sqe.astype(jnp.float32),
        penalty.astype(jnp.float32),
    )
    return gains[0], bg[:, 0, 0], bi[:, 0, 0]


def _replay_kernel(
    x_ref, e_ref, sqx_ref, sqe_ref, valid_ref, dm_ref, cur0_ref,
    gains_ref, cur_ref, bv_ref, bi_ref,
    cur_s, bv_s, bi_s,
):
    """Grid = (n_blocks, m_blocks); m (candidate order) is the inner axis.

    Each row block sweeps the ordered candidate blocks sequentially: the
    cover state ``cur`` and running per-row argmax ``(best_val, best_pos)``
    live in (block_n, 1) VMEM scratch across the inner sweep.  Within a
    block the candidates replay one column at a time (``fori_loop`` over
    the bm lanes — the greedy recurrence is inherently sequential), but the
    similarity tile itself comes from one MXU matmul.  Gains partials are
    written per (ni, mi) block — distinct output blocks, no revisiting —
    into an (n_blocks, 1, m) array whose leading axis the BlockSpec
    squeezes, and the caller sums the n_blocks partial rows.
    """
    mi = pl.program_id(1)
    bn = x_ref.shape[0]
    bm = e_ref.shape[0]

    @pl.when(mi == 0)
    def _init_row_state():
        cur_s[...] = cur0_ref[...]
        bv_s[...] = jnp.full((bn, 1), -1e30, jnp.float32)
        bi_s[...] = jnp.zeros((bn, 1), jnp.int32)

    dots = jax.lax.dot_general(
        x_ref[...],
        e_ref[...],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (bn, bm)
    d2 = sqx_ref[...] + sqe_ref[...] - 2.0 * dots
    s = dm_ref[...] - jnp.sqrt(jnp.maximum(d2, 0.0))
    # dead columns (padding / caller-masked) must neither gain nor cover
    s_cov = jnp.where(valid_ref[...] > 0.0, s, -1e30)

    col_pos = jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1)

    def step(t, carry):
        cur, gacc = carry
        hit = col_pos == t  # (1, bm) one-hot lane mask
        col = jnp.max(jnp.where(hit, s_cov, -1e30), axis=1, keepdims=True)
        g = jnp.sum(jnp.maximum(col - cur, 0.0))  # dead col → relu 0
        gacc = gacc + jnp.where(hit, g, 0.0)
        return jnp.maximum(cur, col), gacc

    cur_fin, gblk = jax.lax.fori_loop(
        0, bm, step, (cur_s[...], jnp.zeros((1, bm), jnp.float32))
    )
    cur_s[...] = cur_fin
    gains_ref[...] = gblk

    # per-row argmax over candidate columns (γ assignment): strict > keeps
    # the earlier block on ties; _first_hit keeps the lowest lane in-block —
    # together exactly jnp.argmax's lowest-index tie rule over the full list
    bval = jnp.max(s_cov, axis=1, keepdims=True)  # (bn, 1)
    bpos = _first_hit(s_cov, bval)
    upd = bval > bv_s[...]
    bv_new = jnp.where(upd, bval, bv_s[...])
    bi_new = jnp.where(upd, mi * bm + bpos, bi_s[...])
    bv_s[...] = bv_new
    bi_s[...] = bi_new
    cur_ref[...] = cur_fin
    bv_ref[...] = bv_new
    bi_ref[...] = bi_new


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "interpret")
)
def fl_replay_pallas(
    x: jax.Array,
    e: jax.Array,
    sqx: jax.Array,
    sqe: jax.Array,
    valid: jax.Array,
    dm: jax.Array,
    cur0: jax.Array,
    *,
    block_n: int = 512,
    block_m: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Blocked sequential facility-location replay of an ordered candidate
    list (the streaming finalize sweep, DESIGN.md §10).

    Replays candidates ``e`` (rows, in selection order) against pool ``x``:
    gains[t] = Σ_i relu(s_it − max(cur0_i, max_{t'<t} s_it')), plus the
    final cover state and each pool row's best candidate (value, position)
    for γ assignment.  One MXU matmul per (block_n, block_m) tile replaces
    the per-candidate dense matvec of the naive replay.

    Args:
      x: (n, d) fp32 pool, n % block_n == 0, d % 128 == 0.
      e: (m, d) fp32 ordered candidates, m % block_m == 0.
      sqx: (n, 1) fp32 squared norms of x (pad rows: see cur0).
      sqe: (1, m) fp32 squared norms of e.
      valid: (1, m) fp32 — 1 for live candidate columns, 0 for padding
        (dead columns contribute no gain, no cover, never win assignment).
      dm: (1, 1) fp32 similarity offset (s = dm − dist).
      cur0: (n, 1) fp32 initial cover state; padded pool rows carry +1e30
        so they contribute 0 to every gain.
    Returns:
      (gains (n_blocks, 1, m) fp32 partials — sum axes (0, 1) for the
       totals,
       cur (n, 1) fp32, best_v (n, 1) fp32, best_i (n, 1) int32).
    """
    n, d = x.shape
    m = e.shape[0]
    assert n % block_n == 0 and m % block_m == 0, (n, m, block_n, block_m)
    n_blocks = n // block_n
    m_blocks = m // block_m
    grid = (n_blocks, m_blocks)
    return pl.pallas_call(
        _replay_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda ni, mi: (ni, 0)),
            pl.BlockSpec((block_m, d), lambda ni, mi: (mi, 0)),
            pl.BlockSpec((block_n, 1), lambda ni, mi: (ni, 0)),
            pl.BlockSpec((1, block_m), lambda ni, mi: (0, mi)),
            pl.BlockSpec((1, block_m), lambda ni, mi: (0, mi)),
            pl.BlockSpec((1, 1), lambda ni, mi: (0, 0)),
            pl.BlockSpec((block_n, 1), lambda ni, mi: (ni, 0)),
        ],
        out_specs=[
            pl.BlockSpec(
                (pl.squeezed, 1, block_m), lambda ni, mi: (ni, 0, mi)
            ),
            pl.BlockSpec((block_n, 1), lambda ni, mi: (ni, 0)),
            pl.BlockSpec((block_n, 1), lambda ni, mi: (ni, 0)),
            pl.BlockSpec((block_n, 1), lambda ni, mi: (ni, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, 1, m), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),  # cover state
            pltpu.VMEM((block_n, 1), jnp.float32),  # best value
            pltpu.VMEM((block_n, 1), jnp.int32),  # best position
        ],
        compiler_params=_REPLAY_PARAMS,
        interpret=interpret,
    )(
        x.astype(jnp.float32),
        e.astype(jnp.float32),
        sqx.astype(jnp.float32),
        sqe.astype(jnp.float32),
        valid.astype(jnp.float32),
        dm.astype(jnp.float32),
        cur0.astype(jnp.float32),
    )
