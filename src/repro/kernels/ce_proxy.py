"""Pallas TPU kernel: fused CRAIG gradient-proxy for token streams.

Computes, for a chunk of T tokens with hidden states h_t ∈ R^d, labels y_t and
unembedding W ∈ R^{d×V}, the gradient of per-token CE w.r.t. the unembedding
input:

    g_t = (softmax(h_t W) − onehot(y_t)) @ Wᵀ      ∈ R^d

without ever materializing the (T, V) logits/softmax: the vocab axis is
blocked and reduced online flash-style.  Per vocab block v:

    z = h W_v                        (MXU, (bt, bv))
    m' = max(m, rowmax(z)); c = exp(m − m')
    l  = l·c + rowsum(exp(z − m'))
    acc  = acc·c + exp(z − m') @ W_vᵀ           (MXU)
    accy += onehot_v(y) @ W_vᵀ  (label column, unscaled)

final:  g = acc / l − accy.

This is the paper's §3.4 "gradient of the loss w.r.t. the input to the last
layer" (Eq. 16) for LMs (DESIGN.md §2): the only extra work on top of a
forward pass, fused so CRAIG's proxy extraction is bandwidth-, not
memory-capacity-, limited even at V = 256k.

Grid = (t_blocks, v_blocks), v inner; running (m, l, acc, accy) live in VMEM
scratch across the v sweep of each t block.

Vocab padding (``valid_v``): configs whose unembedding is padded to a tile
multiple (V_padded > vocab_size) mask the padded logit columns to −∞ inside
the kernel — the same padded-vocab bias ``lm_unembed_input_proxy`` applies —
so the two proxy paths agree bit-for-bit on vocab-padded configs.

Mixed precision (``compute_dtype``): the two MXU matmuls per block (h·W_v and
p·W_vᵀ / onehot·W_vᵀ) run in ``compute_dtype`` (bf16 on the production select
path) with fp32 accumulation via ``preferred_element_type``; the online
softmax state (m, l) and both accumulators stay fp32 — mirroring the
``lm_unembed_input_proxy`` contract.
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

__all__ = ["ce_proxy_pallas"]

_NEG_INF = -1e30


def _ce_proxy_kernel(
    h_ref, w_ref, y_ref, out_ref, m_scr, l_scr, acc_scr, accy_scr,
    *, block_v, valid_v, compute_dtype
):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        accy_scr[...] = jnp.zeros_like(accy_scr)

    h = h_ref[...]  # (bt, d) in compute_dtype
    w = w_ref[...]  # (d, bv) in compute_dtype
    z = jax.lax.dot_general(
        h, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bt, bv) fp32
    cols = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)  # (bt, bv) local
    if valid_v is not None:
        # padded-vocab bias (lm_unembed_input_proxy's pad_bias): columns
        # past the real vocab get −∞ logits → zero probability mass
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

    # Label columns: onehot within this vocab block.
    y = y_ref[...]  # (bt, 1) int32 global vocab ids
    local = y - vi * block_v  # (bt, 1)
    onehot = (cols == local).astype(compute_dtype)  # rows w/ label elsewhere: 0
    yw = jax.lax.dot_general(
        onehot, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    accy_scr[...] += yw

    @pl.when(vi == nv - 1)
    def _finalize():
        out_ref[...] = acc_scr[...] / l_scr[...] - accy_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=("block_t", "block_v", "interpret", "valid_v",
                     "compute_dtype"),
)
def ce_proxy_pallas(
    hidden: jax.Array,
    unembed: jax.Array,
    labels: jax.Array,
    *,
    block_t: int = 128,
    block_v: int = 512,
    interpret: bool = False,
    valid_v: int | None = None,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Fused (softmax(hW) − onehot(y)) @ Wᵀ over vocab blocks.

    Args:
      hidden: (T, D), T % block_t == 0, D % 128 == 0.
      unembed: (D, V), V % block_v == 0.
      labels: (T,) int32 in [0, valid_v or V).
      valid_v: real vocab size when V is tile-padded (1 ≤ valid_v ≤ V);
        padded columns are −∞-masked in-kernel, matching
        ``lm_unembed_input_proxy``'s pad bias.  None means all V columns
        are real.
      compute_dtype: dtype of the MXU matmuls (fp32 accumulation; softmax
        state stays fp32) — bf16 on the production select path.
    Returns:
      (T, D) fp32 per-token proxy gradients.
    """
    T, D = hidden.shape
    V = unembed.shape[1]
    assert T % block_t == 0 and V % block_v == 0, (T, V, block_t, block_v)
    if valid_v is not None and not 1 <= valid_v <= V:
        raise ValueError(f"valid_v={valid_v} outside [1, V={V}]")
    grid = (T // block_t, V // block_v)
    kernel = functools.partial(
        _ce_proxy_kernel, block_v=block_v, valid_v=valid_v,
        compute_dtype=compute_dtype,
    )
    scratch_shapes = [
        pltpu.VMEM((block_t, 1), jnp.float32),  # running max m
        pltpu.VMEM((block_t, 1), jnp.float32),  # running denom l
        pltpu.VMEM((block_t, D), jnp.float32),  # softmax@Wᵀ accumulator
        pltpu.VMEM((block_t, D), jnp.float32),  # label-column accumulator
    ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, D), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((D, block_v), lambda ti, vi: (0, vi)),
            pl.BlockSpec((block_t, 1), lambda ti, vi: (ti, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, D), lambda ti, vi: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
        scratch_shapes=scratch_shapes,
        compiler_params=_TPU_PARAMS,
        interpret=interpret,
    )(
        hidden.astype(compute_dtype),
        unembed.astype(compute_dtype),
        labels.astype(jnp.int32).reshape(T, 1),
    )
