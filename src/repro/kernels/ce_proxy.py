"""Pallas TPU kernel: fused CRAIG gradient-proxy for token streams.

Computes, for a chunk of T tokens with hidden states h_t ∈ R^d, labels y_t and
unembedding W ∈ R^{d×V}, the gradient of per-token CE w.r.t. the unembedding
input:

    g_t = (softmax(h_t W) − onehot(y_t)) @ Wᵀ = softmax(h_t W) @ Wᵀ − W[:, y_t]

without ever materializing the (T, V) logits/softmax: the vocab axis is
blocked and reduced online flash-style.  Per vocab block v:

    z = h W_v                        (MXU, (bt, bv))
    m' = max(m, rowmax(z)); c = exp(m − m')
    l  = l·c + rowsum(exp(z − m'))
    acc = acc·c + exp(z − m') @ W_vᵀ            (MXU)

final:  g = acc / l − wy,  with wy_t = W[:, y_t] gathered by the caller
(``ops.ce_proxy``) as a (T, d) input: the label term is a gather of T·d
elements, not a third (bt, bv, d) matmul per block.

This is the paper's §3.4 "gradient of the loss w.r.t. the input to the last
layer" (Eq. 16) for LMs (DESIGN.md §2): the only extra work on top of a
forward pass, fused so CRAIG's proxy extraction is compute-, not
memory-capacity-, limited even at V = 256k.

Grid = (t_blocks, v_blocks), v inner; running (m, l, acc) live in VMEM
scratch across the v sweep of each t block, and the t block's wy rows are
fetched once per t block.  W is re-read once per t block, so the token tile
sets the arithmetic intensity (2·block_t FLOP per bf16 byte of W);
``pick_block_t`` takes the largest tile whose VMEM count (``vmem_bytes``)
fits ``VMEM_BUDGET``, and the kernel declares its scoped VMEM to match.

Vocab padding (``valid_v``): configs whose unembedding is padded to a tile
multiple (V_padded > vocab_size) mask the padded logit columns to −∞ inside
the kernel — the same padded-vocab bias ``lm_unembed_input_proxy`` applies —
so the two proxy paths agree bit-for-bit on vocab-padded configs.

Mixed precision (``compute_dtype``): the two MXU matmuls per block (h·W_v and
p·W_vᵀ) run in ``compute_dtype`` (bf16 on the production select path) with
fp32 accumulation via ``preferred_element_type``; the online softmax state
(m, l) and the accumulator stay fp32 — mirroring the
``lm_unembed_input_proxy`` contract.  wy arrives in ``compute_dtype`` too,
so the label term is the bf16-rounded column, exactly what a one-hot
product on the MXU (bf16 operands, fp32 accumulate) would give.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ce_proxy_pallas", "pick_block_t", "vmem_bytes", "VMEM_BUDGET"]

_NEG_INF = -1e30
_LANE = 128
_BLOCK_T_MAX = 512
# Scoped VMEM the counted buffers of one ce_proxy call may take: 512-token
# tiles at d 2048 and 256 at d 4096 in bf16 fit (v5e has 128 MiB of VMEM;
# Mosaic scopes 16 MiB to a kernel unless the call declares more).
VMEM_BUDGET = 48 * 2**20
# Declared on top of the count, for Mosaic's internal scratch.
_VMEM_HEADROOM = 4 * 2**20


def vmem_bytes(block_t: int, block_v: int, d: int, itemsize: int) -> int:
    """VMEM one ``ce_proxy_pallas`` call holds at these tiles: the
    double-buffered h, W and wy blocks (``itemsize`` = compute dtype) and
    fp32 out blocks, the fp32 acc scratch, the (block_t, 1) m and l scratch
    (a full 128-lane row each), four fp32 (block_t, block_v) temporaries
    (z, p, the column iota, p in the compute dtype) and one W block
    transposed for p @ W_vᵀ.  An upper bound of what Mosaic allocates for
    v5e at d 2048–6144 in bf16 and fp32."""
    tiles = 2 * (2 * block_t * d + d * block_v) * itemsize
    out = 2 * block_t * d * 4
    scratch = block_t * d * 4 + 2 * block_t * _LANE * 4
    temps = 4 * block_t * block_v * 4 + d * block_v * itemsize
    return tiles + out + scratch + temps


def pick_block_t(d: int, block_v: int, itemsize: int) -> int:
    """The largest power-of-two token tile ≤ 512 (and ≥ 8) whose
    ``vmem_bytes`` fit ``VMEM_BUDGET``: 512 at d 2048 in bf16, 256 at
    d 4096, 128 at d 6144."""
    bt = _BLOCK_T_MAX
    while bt > 8 and vmem_bytes(bt, block_v, d, itemsize) > VMEM_BUDGET:
        bt //= 2
    return bt


def _ce_proxy_kernel(
    h_ref, w_ref, wy_ref, out_ref, m_scr, l_scr, acc_scr,
    *, block_v, valid_v, compute_dtype
):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    h = h_ref[...]  # (bt, d) in compute_dtype
    w = w_ref[...]  # (d, bv) in compute_dtype
    z = jax.lax.dot_general(
        h, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bt, bv) fp32
    if valid_v is not None:
        # padded-vocab bias (lm_unembed_input_proxy's pad_bias): columns
        # past the real vocab get −∞ logits → zero probability mass
        cols = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
        z = jnp.where(cols + vi * block_v < valid_v, z, _NEG_INF)

    m_prev = m_scr[...]  # (bt, 1)
    m_new = jnp.maximum(m_prev, jnp.max(z, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)  # (bt, 1)
    p = jnp.exp(z - m_new)  # (bt, bv) unnormalized, fp32
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    # acc ← acc·c + p @ Wᵀ  (MXU matmul in compute_dtype, fp32 accumulate)
    pw = jax.lax.dot_general(
        p.astype(compute_dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (bt, d)
    acc_scr[...] = acc_scr[...] * corr + pw
    m_scr[...] = m_new

    @pl.when(vi == nv - 1)
    def _finalize():
        out_ref[...] = (
            acc_scr[...] / l_scr[...] - wy_ref[...].astype(jnp.float32)
        )


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "block_v", "interpret", "valid_v",
                     "compute_dtype"),
)
def ce_proxy_pallas(
    hidden: jax.Array,
    unembed: jax.Array,
    label_cols: jax.Array,
    *,
    block_t: int = 128,
    block_v: int = 512,
    interpret: bool = False,
    valid_v: int | None = None,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Fused softmax(hW) @ Wᵀ − W[:, y] over vocab blocks.

    Args:
      hidden: (T, D), T % block_t == 0, D % 128 == 0.
      unembed: (D, V), V % block_v == 0.
      label_cols: (T, D) label columns, row t = W[:, y_t] (``ops.ce_proxy``
        gathers them).
      valid_v: real vocab size when V is tile-padded (1 ≤ valid_v ≤ V);
        padded columns are −∞-masked in-kernel, matching
        ``lm_unembed_input_proxy``'s pad bias.  None means all V columns
        are real.
      compute_dtype: dtype of the MXU matmuls and of the label columns
        (fp32 accumulation; softmax state stays fp32) — bf16 on the
        production select path.
    Returns:
      (T, D) fp32 per-token proxy gradients.
    """
    T, D = hidden.shape
    V = unembed.shape[1]
    assert T % block_t == 0 and V % block_v == 0, (T, V, block_t, block_v)
    assert label_cols.shape == (T, D), (label_cols.shape, T, D)
    if valid_v is not None and not 1 <= valid_v <= V:
        raise ValueError(f"valid_v={valid_v} outside [1, V={V}]")
    grid = (T // block_t, V // block_v)
    kernel = functools.partial(
        _ce_proxy_kernel, block_v=block_v, valid_v=valid_v,
        compute_dtype=compute_dtype,
    )
    itemsize = jnp.dtype(compute_dtype).itemsize
    vmem = vmem_bytes(block_t, block_v, D, itemsize) + _VMEM_HEADROOM
    scratch_shapes = [
        pltpu.VMEM((block_t, 1), jnp.float32),  # running max m
        pltpu.VMEM((block_t, 1), jnp.float32),  # running denom l
        pltpu.VMEM((block_t, D), jnp.float32),  # softmax@Wᵀ accumulator
    ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, D), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((D, block_v), lambda ti, vi: (0, vi)),
            pl.BlockSpec((block_t, D), lambda ti, vi: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, D), lambda ti, vi: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem,
        ),
        interpret=interpret,
    )(
        hidden.astype(compute_dtype),
        unembed.astype(compute_dtype),
        label_cols.astype(compute_dtype),
    )
