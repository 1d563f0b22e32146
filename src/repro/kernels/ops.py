"""Public jit'd wrappers for the Pallas kernels.

Handles shape padding to block/lane multiples, backend selection (interpret
mode on CPU so the kernels are CI-testable without a TPU), and the
feature-space bookkeeping CRAIG's greedy loop needs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ce_proxy as _ce
from repro.kernels import fl_gains as _fl
from repro.kernels import pairwise_l2 as _pw
from repro.kernels import topk_sim as _tk

__all__ = [
    "fl_gains",
    "fl_gains_argmax",
    "fl_replay",
    "pairwise_l2",
    "ce_proxy",
    "topk_sim",
    "interpret_default",
    "resolve_impl",
]

_LANE = 128
# Double-buffered (block_n, d) + (block_m, d) input tiles of the fl_gains
# kernels must leave room for their fp32 (block_n, block_m) intermediates in
# the 16 MiB of VMEM Mosaic scopes to a kernel by default (v5e).
_VMEM_TILE_BYTES = 12 * 2**20


def interpret_default() -> bool:
    """Pallas interpret mode unless running on a real TPU."""
    return jax.default_backend() != "tpu"


def resolve_impl(impl: str, fallback: str) -> str:
    """``'auto'`` → ``'pallas'`` where the kernels compile for the chip
    (a TPU backend), else ``fallback``; any other value passes through."""
    if impl != "auto":
        return impl
    return fallback if interpret_default() else "pallas"


def _fit_tiles(bn: int, bm: int, d: int, itemsize: int) -> tuple[int, int]:
    """Halve the larger tile until both input tiles, double-buffered, fit
    ``_VMEM_TILE_BYTES`` at feature width ``d`` (e.g. (512, 2048) → (512,
    256) for fp32 at d = 2048, → (512, 1024) for bf16)."""
    while 2 * (bn + bm) * d * itemsize > _VMEM_TILE_BYTES:
        if bm >= bn and bm > _LANE:
            bm //= 2
        elif bn > 8:
            bn //= 2
        else:
            break
    return bn, bm


def _pad_dim(a: jax.Array, axis: int, mult: int, value: float = 0.0) -> jax.Array:
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "interpret")
)
def fl_gains(
    x: jax.Array,
    e: jax.Array,
    cur_max: jax.Array,
    sqx: jax.Array,
    sqe: jax.Array,
    d_max: jax.Array,
    *,
    block_n: int = 512,
    block_m: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Marginal FL gains of candidates ``e`` against pool ``x``.

    gains[c] = Σ_i relu((d_max − ‖x_i − e_c‖) − cur_max_i).

    Padding: pool rows are padded with duplicates of row 0 but their
    contribution is cancelled by setting padded madj = −inf → relu 0.
    Candidate padding produces garbage gains that the caller slices off.
    """
    if interpret is None:
        interpret = interpret_default()
    n, d = x.shape
    m = e.shape[0]
    bn = min(block_n, max(_LANE, 1 << (n - 1).bit_length()))
    bm = min(block_m, max(_LANE, 1 << (m - 1).bit_length()))
    bn, bm = _fit_tiles(bn, bm, -(-d // _LANE) * _LANE, 4)
    xp = _pad_dim(_pad_dim(x, 0, bn), 1, _LANE)
    ep = _pad_dim(_pad_dim(e, 0, bm), 1, _LANE)
    madj = d_max - cur_max.astype(jnp.float32)
    madj = _pad_dim(madj.reshape(n, 1), 0, bn, value=-1e30)
    sqxp = _pad_dim(sqx.astype(jnp.float32).reshape(n, 1), 0, bn)
    sqep = _pad_dim(sqe.astype(jnp.float32).reshape(1, m), 1, bm)
    out = _fl.fl_gains_pallas(
        xp, ep, madj, sqxp, sqep, block_n=bn, block_m=bm, interpret=interpret
    )
    return out[:m]


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_m", "tile_dtype", "interpret"),
)
def fl_gains_argmax(
    x: jax.Array,
    e: jax.Array,
    cur_max: jax.Array,
    sqx: jax.Array,
    sqe: jax.Array,
    d_max: jax.Array,
    chosen_e: jax.Array,
    *,
    block_n: int = 512,
    block_m: int = 256,
    tile_dtype: str = "float32",
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One fused greedy round: gains sweep + per-block argmax partials.

    For the device-resident greedy engine (DESIGN.md §3.6): a single kernel
    launch computes every candidate's marginal gain *and* reduces each
    candidate block to a ``(best_gain, best_index)`` partial, with
    already-selected candidates excluded inside the kernel.  The caller
    finalizes the winner over the O(m/block_m) partials; the full gains
    vector rides along as the engine's Minoux upper bounds between sweeps
    (block-greedy mode).

    Padding contract (DESIGN.md §2): pool rows pad with madj = −1e30 → relu 0
    (inert through the reduction); candidate padding and ``chosen_e`` columns
    carry an additive −1e30 penalty so they can only win a block in which
    every candidate is dead — such blocks report best_gain ≤ −1e29 and the
    caller must ignore them (real gains are always ≥ 0).

    Args:
      x: (n, d) pool features.
      e: (m, d) candidate features.
      cur_max: (n,) fp32 running cover state max_{j∈S} s_ij.
      sqx: (n,) fp32 squared norms of x.
      sqe: (m,) fp32 squared norms of e.
      d_max: traced fp32 scalar similarity offset.
      chosen_e: (m,) bool — candidates to exclude (already selected).
      tile_dtype: 'float32' | 'bfloat16' — dtype of the feature tiles fed to
        the MXU; distances/gains always accumulate in fp32.
    Returns:
      (gains (m,) fp32, part_g (m_blocks,) fp32, part_i (m_blocks,) int32) —
      every candidate's un-penalized gain, plus per-block best penalized
      gain and its candidate index (lowest index on ties).
    """
    if interpret is None:
        interpret = interpret_default()
    td = jnp.dtype(tile_dtype)
    if td not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        raise ValueError(f"unsupported tile_dtype {tile_dtype!r}")
    n, d = x.shape
    m = e.shape[0]
    bn = min(block_n, max(_LANE, 1 << (n - 1).bit_length()))
    bm = min(block_m, max(_LANE, 1 << (m - 1).bit_length()))
    bn, bm = _fit_tiles(bn, bm, -(-d // _LANE) * _LANE, td.itemsize)
    xp = _pad_dim(_pad_dim(x.astype(td), 0, bn), 1, _LANE)
    ep = _pad_dim(_pad_dim(e.astype(td), 0, bm), 1, _LANE)
    madj = d_max - cur_max.astype(jnp.float32)
    madj = _pad_dim(madj.reshape(n, 1), 0, bn, value=-1e30)
    sqxp = _pad_dim(sqx.astype(jnp.float32).reshape(n, 1), 0, bn)
    sqep = _pad_dim(sqe.astype(jnp.float32).reshape(1, m), 1, bm)
    pen = jnp.where(chosen_e, -1e30, 0.0).astype(jnp.float32)
    pen = _pad_dim(pen.reshape(1, m), 1, bm, value=-1e30)
    gains, part_g, part_i = _fl.fl_gains_argmax_pallas(
        xp, ep, madj, sqxp, sqep, pen,
        block_n=bn, block_m=bm, interpret=interpret,
    )
    return gains[:m], part_g, part_i


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "interpret")
)
def fl_replay(
    x: jax.Array,
    e: jax.Array,
    valid: jax.Array,
    cur0: jax.Array,
    d_max: jax.Array,
    *,
    block_n: int = 512,
    block_m: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sequential FL replay of an ordered candidate list (streaming finalize).

    gains[t] = Σ_i relu(s_it − max(cur0_i, max_{t'<t} s_it')) with
    s_it = d_max − ‖x_i − e_t‖, i.e. the marginal-gain sequence a greedy
    run would record if it accepted candidates in exactly row order of
    ``e``.  Also returns the final cover state and each pool row's best
    candidate (value, row position of ``e``) for γ assignment —
    lowest-position on ties, matching ``jnp.argmax``.

    Padding: pool rows pad with cur0 = +1e30 (inert: relu 0 in every gain,
    garbage best sliced off); candidate rows pad with valid = 0 (no gain,
    no cover, can never win assignment).

    Args:
      x: (n, d) pool features.
      e: (m, d) candidates, rows in selection order.
      valid: (m,) bool — False masks a candidate out entirely.
      cur0: (n,) fp32 initial cover state (zeros for a cold replay).
      d_max: traced fp32 scalar similarity offset.
    Returns:
      (gains (m,) fp32, cur (n,) fp32, best_v (n,) fp32, best_i (n,) int32).
    """
    if interpret is None:
        interpret = interpret_default()
    n, d = x.shape
    m = e.shape[0]
    x = x.astype(jnp.float32)
    e = e.astype(jnp.float32)
    sqx = jnp.sum(x * x, axis=1)
    sqe = jnp.sum(e * e, axis=1)
    bn = min(block_n, max(8, 1 << (n - 1).bit_length()))
    bm = min(block_m, max(_LANE, 1 << max(m - 1, 0).bit_length()))
    bn, bm = _fit_tiles(bn, bm, -(-d // _LANE) * _LANE, 4)
    xp = _pad_dim(_pad_dim(x, 0, bn), 1, _LANE)
    ep = _pad_dim(_pad_dim(e, 0, bm), 1, _LANE)
    sqxp = _pad_dim(sqx.reshape(n, 1), 0, bn)
    sqep = _pad_dim(sqe.reshape(1, m), 1, bm)
    vp = _pad_dim(
        valid.astype(jnp.float32).reshape(1, m), 1, bm, value=0.0
    )
    curp = _pad_dim(
        cur0.astype(jnp.float32).reshape(n, 1), 0, bn, value=1e30
    )
    dm = jnp.asarray(d_max, jnp.float32).reshape(1, 1)
    gains, cur, bv, bi = _fl.fl_replay_pallas(
        xp, ep, sqxp, sqep, vp, dm, curp,
        block_n=bn, block_m=bm, interpret=interpret,
    )
    return (
        jnp.sum(gains, axis=(0, 1))[:m],
        cur[:n, 0],
        bv[:n, 0],
        bi[:n, 0],
    )


@functools.partial(
    jax.jit, static_argnames=("k", "block_n", "block_m", "interpret")
)
def topk_sim(
    x: jax.Array,
    k: int,
    d_max: jax.Array | None = None,
    *,
    block_n: int = 256,
    block_m: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Top-k similarity graph rows of the pool against itself.

    Returns (vals (n, k) fp32 descending, idx (n, k) int32) where
    vals[i, t] = d_max − ‖x_i − x_{idx[i, t]}‖ over the k most similar
    columns (self included: idx[i, 0] == i).  O(n·k) output memory; the
    dense (n, n) similarity matrix is never materialized.

    Padding: pool rows pad with zeros and are sliced off; column padding
    carries sqy = +1e30 so padded similarities (≈ −1e15) never beat a real
    candidate — sound because k ≤ n and real similarities are ≥ 0.

    Args:
      x: (n, d) features.
      k: neighbors per row (static); clamped to n by the caller.
      d_max: similarity offset (traced scalar).  Defaults to the
        2·max‖x‖ + ε upper bound on the pairwise distance (triangle
        inequality), the same convention as ``greedy_fl_features``.
    """
    if interpret is None:
        interpret = interpret_default()
    n, d = x.shape
    assert 1 <= k <= n, (k, n)
    x = x.astype(jnp.float32)
    sq = jnp.sum(x * x, axis=1)
    if d_max is None:
        d_max = 2.0 * jnp.sqrt(jnp.max(sq)) + 1e-6
    bn = min(block_n, max(8, 1 << (n - 1).bit_length()))
    bm = min(block_m, max(_LANE, 1 << (n - 1).bit_length()))
    xp = _pad_dim(_pad_dim(x, 0, bn), 1, _LANE)
    yp = _pad_dim(_pad_dim(x, 0, bm), 1, _LANE)
    sqxp = _pad_dim(sq.reshape(n, 1), 0, bn)
    sqyp = _pad_dim(sq.reshape(1, n), 1, bm, value=1e30)
    dm = jnp.asarray(d_max, jnp.float32).reshape(1, 1)
    vals, idx = _tk.topk_sim_pallas(
        xp, yp, sqxp, sqyp, dm, k=k, block_n=bn, block_m=bm,
        interpret=interpret,
    )
    return vals[:n], idx[:n]


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "interpret")
)
def pairwise_l2(
    x: jax.Array,
    y: jax.Array,
    *,
    block_n: int = 256,
    block_m: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """(n, m) pairwise L2 distances via the blocked Pallas kernel."""
    if interpret is None:
        interpret = interpret_default()
    n = x.shape[0]
    m = y.shape[0]
    bn = min(block_n, max(8, 1 << (n - 1).bit_length()))
    bm = min(block_m, max(_LANE, 1 << (m - 1).bit_length()))
    xp = _pad_dim(_pad_dim(x, 0, bn), 1, _LANE)
    yp = _pad_dim(_pad_dim(y, 0, bm), 1, _LANE)
    out = _pw.pairwise_l2_pallas(
        xp, yp, block_n=bn, block_m=bm, interpret=interpret
    )
    return out[:n, :m]


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "block_v", "interpret", "valid_v",
                     "compute_dtype"),
)
def ce_proxy(
    hidden: jax.Array,
    unembed: jax.Array,
    labels: jax.Array,
    *,
    block_t: int | None = None,
    block_v: int = 512,
    interpret: bool | None = None,
    valid_v: int | None = None,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Fused per-token CRAIG proxy (softmax(hW) − y) @ Wᵀ → (T, D) fp32.

    Vocab padding is exact: V is zero-padded up to a ``block_v`` multiple
    and the padded columns (plus any caller-declared pad past ``valid_v``)
    are −∞-masked inside the kernel — the same padded-vocab bias
    ``core.proxy.lm_unembed_input_proxy`` applies, so the two proxy paths
    agree on vocab-padded configs.  ``compute_dtype=bf16`` runs the MXU
    matmuls in bf16 with fp32 accumulation (softmax state stays fp32).

    The label term y @ Wᵀ is a gather here, outside the kernel: the rows
    ``Wᵀ[y_t]``, cast to ``compute_dtype`` and subtracted in the kernel's
    finalize.  With a tied head W is the (V, D) embedding's transpose, so
    XLA reads them as a row gather of the embedding.
    ``block_t=None`` sizes the token tile from D and the compute dtype
    (``ce_proxy.pick_block_t``: the largest ≤ 512 whose VMEM fits).
    """
    if interpret is None:
        interpret = interpret_default()
    T, D = hidden.shape
    V = unembed.shape[1]
    vv = V if valid_v is None else valid_v
    bv = min(block_v, max(8, 1 << (V - 1).bit_length()))
    if block_t is None:
        block_t = _ce.pick_block_t(
            D + (-D) % _LANE, bv, jnp.dtype(compute_dtype).itemsize
        )
    bt = min(block_t, max(8, 1 << (T - 1).bit_length()))
    wy = jnp.take(unembed.T, labels.reshape(T), axis=0).astype(compute_dtype)
    hp = _pad_dim(_pad_dim(hidden, 0, bt), 1, _LANE)
    wp = _pad_dim(_pad_dim(unembed, 0, _LANE), 1, bv)
    wyp = _pad_dim(_pad_dim(wy, 0, bt), 1, _LANE)
    out = _ce.ce_proxy_pallas(
        hp, wp, wyp, block_t=bt, block_v=bv, interpret=interpret,
        # mask everything past the real vocab, incl. the block padding,
        # unless nothing was padded at all
        valid_v=None if vv == wp.shape[1] else vv,
        compute_dtype=compute_dtype,
    )
    return out[:T, :D]
