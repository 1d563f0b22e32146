"""Operations and bytes of the benchmark's programs, counted from shapes.

Every count here is what the algorithm needs, not what one implementation
happens to execute: recomputation (rematerialized layers), re-reads caused
by tiling, and masked-out work do not count.  The per-layer readers divide
these counts by device time (a roofline share) or by the window and the
chip's peak (an MFU).

Conventions: one multiply-add is 2 operations; causal attention counts the
visible half of the score matrix; a tied embedding counts once, as the
output projection (the input lookup is a gather, not a matmul).
"""
from __future__ import annotations

import dataclasses

__all__ = [
    "LMShape",
    "lm_matmul_params",
    "lm_train_flops_per_token",
    "lm_extract_flops_per_token",
    "ce_proxy_cost",
    "fl_gains_argmax_cost",
    "fl_replay_cost",
    "least_time_s",
]


@dataclasses.dataclass(frozen=True)
class LMShape:
    """The widths a dense GQA decoder's operation count depends on."""

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    seq_len: int

    @classmethod
    def from_hf(cls, hf: dict, seq_len: int) -> "LMShape":
        """From a Hugging Face ``config.json``-style dict."""
        return cls(
            n_layers=int(hf["num_hidden_layers"]),
            d_model=int(hf["hidden_size"]),
            n_heads=int(hf["num_attention_heads"]),
            n_kv_heads=int(hf["num_key_value_heads"]),
            head_dim=int(hf["head_dim"]),
            d_ff=int(hf["intermediate_size"]),
            vocab=int(hf["vocab_size"]),
            seq_len=int(seq_len),
        )


def lm_layer_matmul_params(s: LMShape) -> int:
    """Matmul weights of one layer: q, k, v, o projections and a SwiGLU FFN."""
    attn = s.d_model * s.head_dim * (2 * s.n_heads + 2 * s.n_kv_heads)
    ffn = 3 * s.d_model * s.d_ff
    return attn + ffn


def lm_matmul_params(s: LMShape) -> int:
    """Matmul weights touched per token: the layers plus the output head."""
    return s.n_layers * lm_layer_matmul_params(s) + s.d_model * s.vocab


def _attn_fwd_flops_per_token(s: LMShape) -> float:
    """Causal scores and weighted sum: a query sees (T + 1) / 2 keys on
    average; 2 matmuls of 2 ops per key and head dimension."""
    keys = (s.seq_len + 1) / 2.0
    return s.n_layers * 2 * 2 * keys * s.n_heads * s.head_dim


def lm_train_flops_per_token(s: LMShape) -> float:
    """Forward and backward of one token: 6 per matmul weight plus attention."""
    return 6.0 * lm_matmul_params(s) + 3.0 * _attn_fwd_flops_per_token(s)


def lm_extract_flops_per_token(s: LMShape) -> float:
    """CRAIG proxy of one token: the forward trunk, then ``ce_proxy``'s
    logits and its (softmax - onehot) @ W^T, 4·D·V."""
    trunk = 2.0 * s.n_layers * lm_layer_matmul_params(s)
    return trunk + _attn_fwd_flops_per_token(s) + 4.0 * s.d_model * s.vocab


def ce_proxy_cost(tokens: int, d: int, vocab: int, w_bytes: int = 4):
    """(operations, bytes) of one ``ce_proxy`` call over ``tokens`` rows.

    Bytes: the unembedding read once, hidden states in, proxies out (fp32).
    """
    flops = 4.0 * tokens * d * vocab
    nbytes = w_bytes * d * vocab + 4.0 * tokens * d * 2 + 4.0 * tokens
    return flops, nbytes


def fl_gains_argmax_cost(n: int, m: int, d: int, tile_bytes: int = 4):
    """(operations, bytes) of one greedy sweep of ``m`` candidates over
    ``n`` pool rows at width ``d``: the (n, m) distance matmul."""
    flops = 2.0 * n * m * d
    nbytes = tile_bytes * (n + m) * d + 4.0 * (3 * n + 3 * m)
    return flops, nbytes


def fl_replay_cost(n: int, m: int, d: int):
    """(operations, bytes) of one streaming finalize replay of ``m``
    candidates over ``n`` live rows."""
    flops = 2.0 * n * m * d
    nbytes = 4.0 * (n + m) * d + 4.0 * (4 * n + m)
    return flops, nbytes


def least_time_s(flops: float, nbytes: float, peak_flops: float,
                 peak_bytes_s: float) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    tc, tm = flops / peak_flops, nbytes / peak_bytes_s
    return (tc, "compute") if tc >= tm else (tm, "memory")
