"""Plain float32 reference of a Qwen3 dense decoder, and the benchmark's weights.

Written from the published description of Qwen3 (Hugging Face
``modeling_qwen3``): pre-norm RMSNorm blocks; grouped-query attention with a
per-head RMSNorm on queries and keys before rotary embeddings (rotate-half
form, base ``rope_theta``); causal softmax at scale ``1/sqrt(head_dim)``;
SwiGLU feed-forward ``silu(x W_gate) * (x W_up) W_down``; a final RMSNorm;
output logits through the tied embedding.  Every matmul is float32 at
``Precision.HIGHEST``.  It imports nothing of the program under test.

``dot_mode="int8"`` is the control: every matmul operand is rounded to int8
with one scale per row of the contraction (symmetric, absmax / 127) before
the float32 product, the precision step below the bfloat16 the
configuration states.

The weights live in a layout of the benchmark's own (the same tree the
program's ``init_params`` builds, so it can train on them): a stacked layer
axis under ``stack.scanned[0]``, fused ``w_in = [W_gate | W_up]``.  They are
made from the seed in one jitted call on the device; the reference and the
program receive the same arrays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

__all__ = [
    "Qwen3Dims",
    "init_weights",
    "layer_leaves",
    "make_dot",
    "doc_losses",
    "weighted_loss_grad",
    "proxy_features",
    "clip_by_global_norm",
    "adamw_step",
]


class Qwen3Dims:
    """Sizes read from a Hugging Face style configuration dict."""

    def __init__(self, hf: dict):
        self.L = int(hf["num_hidden_layers"])
        self.D = int(hf["hidden_size"])
        self.H = int(hf["num_attention_heads"])
        self.KV = int(hf["num_key_value_heads"])
        self.hd = int(hf["head_dim"])
        self.F = int(hf["intermediate_size"])
        self.V = int(hf["vocab_size"])
        self.theta = float(hf["rope_theta"])
        self.eps = float(hf["rms_norm_eps"])
        if not hf.get("tie_word_embeddings", False):
            raise ValueError("this reference covers tied embeddings only")

    def key(self):
        return (self.L, self.D, self.H, self.KV, self.hd, self.F, self.V,
                self.theta, self.eps)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (more than 32 bits too)."""
    s = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(np.uint32(s & 0xFFFFFFFF)), np.uint32((s >> 32) & 0xFFFFFFFF)
    )


@functools.lru_cache(maxsize=4)
def _init_fn(dims_key):
    L, D, H, KV, hd, F, V, _, _ = dims_key

    def tn(k, shape, fan_in):
        return jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) / np.sqrt(fan_in)

    def init(key):
        ks = jax.random.split(key, 7)
        layer = {
            "norm1": {"scale": jnp.ones((L, D), jnp.float32)},
            "mixer": {
                "wq": tn(ks[0], (L, D, H, hd), D),
                "wk": tn(ks[1], (L, D, KV, hd), D),
                "wv": tn(ks[2], (L, D, KV, hd), D),
                "wo": tn(ks[3], (L, H, hd, D), H * hd),
                "q_norm": {"scale": jnp.ones((L, hd), jnp.float32)},
                "k_norm": {"scale": jnp.ones((L, hd), jnp.float32)},
            },
            "norm2": {"scale": jnp.ones((L, D), jnp.float32)},
            "ffn": {
                "w_in": tn(ks[4], (L, D, 2 * F), D),
                "w_out": tn(ks[5], (L, F, D), F),
            },
        }
        return {
            "embed": tn(ks[6], (V, D), D),
            "final_norm": {"scale": jnp.ones((D,), jnp.float32)},
            "stack": {"scanned": (layer,), "remainder": []},
        }

    return jax.jit(init)


def init_weights(seed: int, dims: Qwen3Dims):
    """Float32 weights from ``seed``, made on the device in one jitted call."""
    return _init_fn(dims.key())(seed_key(seed))


def layer_leaves(tree, n_layers: int) -> dict[str, jax.Array]:
    """Flatten to named leaves, one per layer for the stacked weights."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        if "scanned" in name:
            for i in range(n_layers):
                out[f"{name}[layer {i}]"] = leaf[i]
        else:
            out[name] = leaf
    return out


def _q8(x, axis):
    """Symmetric int8 rounding with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def make_dot(mode: str):
    """``dot(spec, a, b)``: an einsum whose single contracted axis letter is
    the one both operands share and the output lacks."""
    if mode not in ("f32", "int8"):
        raise ValueError(f"unknown dot mode {mode!r}")

    def dot(spec, a, b):
        if mode == "int8":
            ins, out = spec.split("->")
            sa, sb = ins.split(",")
            contracted = [c for c in sa if c in sb and c not in out]
            a = _q8(a, tuple(sa.index(c) for c in contracted))
            b = _q8(b, tuple(sb.index(c) for c in contracted))
        return jnp.einsum(spec, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    return dot


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.reshape((1,) * (x.ndim - 1) + (-1,))


def _rope(x, theta):
    """x (B, T, H, hd); rotate-half rotary embedding at positions 0..T-1."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(p, x, dims: Qwen3Dims, dot):
    eps = dims.eps
    h = _rms(x, p["norm1"]["scale"], eps)
    m = p["mixer"]
    q = dot("btd,dhk->bthk", h, m["wq"])
    k = dot("btd,dhk->bthk", h, m["wk"])
    v = dot("btd,dhk->bthk", h, m["wv"])
    q = _rope(_rms(q, m["q_norm"]["scale"], eps), dims.theta)
    k = _rope(_rms(k, m["k_norm"]["scale"], eps), dims.theta)
    rep = dims.H // dims.KV  # query head i reads key/value head i // rep
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    T = x.shape[1]
    s = dot("bqhk,bshk->bhqs", q, k) / np.sqrt(dims.hd)
    causal = np.tril(np.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = dot("bhqs,bshk->bqhk", a, v)
    x = x + dot("bqhk,hkd->bqd", o, m["wo"])
    h = _rms(x, p["norm2"]["scale"], eps)
    gu = dot("btd,df->btf", h, p["ffn"]["w_in"])
    g, u = gu[..., : dims.F], gu[..., dims.F:]
    return x + dot("btf,fd->btd", jax.nn.silu(g) * u, p["ffn"]["w_out"])


def _hidden(params, tokens, dims: Qwen3Dims, dot):
    """Final-normed hidden states (B, T, D)."""
    x = params["embed"][tokens]
    lay = params["stack"]["scanned"][0]
    for i in range(dims.L):
        x = _layer(jax.tree.map(lambda a: a[i], lay), x, dims, dot)
    return _rms(x, params["final_norm"]["scale"], dims.eps)


def doc_losses(params, tokens, labels, dims: Qwen3Dims, dot):
    """Mean next-token cross-entropy of each document (B,)."""
    h = _hidden(params, tokens, dims, dot)
    logits = dot("btd,vd->btv", h, params["embed"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold, axis=-1)


@functools.lru_cache(maxsize=8)
def _grad_fn(dims_key, mode):
    dims = _DIMS[dims_key]
    dot = make_dot(mode)

    def part(params, tokens, labels, w, denom):
        ls = doc_losses(params, tokens, labels, dims, dot)
        return jnp.sum(ls * w) / denom

    return jax.jit(jax.value_and_grad(part))


_DIMS: dict = {}


def weighted_loss_grad(params, tokens, labels, weights, dims: Qwen3Dims,
                       mode: str = "f32", docs_per_call: int = 1):
    """Loss ``Σ w_i ℓ_i / Σ w_i`` and its gradient, accumulated over blocks
    of ``docs_per_call`` documents so the (T, V) logits of one block fit."""
    _DIMS[dims.key()] = dims
    fn = _grad_fn(dims.key(), mode)
    w = np.asarray(weights, np.float32)
    denom = jnp.float32(max(float(w.sum()), 1e-6))
    loss, grad = 0.0, None
    for lo in range(0, len(w), docs_per_call):
        sl = slice(lo, lo + docs_per_call)
        l, g = fn(params, jnp.asarray(tokens[sl]), jnp.asarray(labels[sl]),
                  jnp.asarray(w[sl]), denom)
        loss = loss + l
        grad = g if grad is None else jax.tree.map(jnp.add, grad, g)
        del g
    return float(loss), grad


@functools.lru_cache(maxsize=8)
def _proxy_fn(dims_key, mode):
    dims = _DIMS[dims_key]
    dot = make_dot(mode)

    def proxy(params, tokens, labels):
        h = _hidden(params, tokens, dims, dot)
        logits = dot("btd,vd->btv", h, params["embed"])
        delta = jax.nn.softmax(logits, axis=-1) - jax.nn.one_hot(
            labels, dims.V, dtype=jnp.float32)
        g = dot("btv,vd->btd", delta, params["embed"])
        return jnp.mean(g, axis=1)

    return jax.jit(proxy)


def proxy_features(params, tokens, labels, dims: Qwen3Dims, mode: str = "f32",
                   docs_per_call: int = 2) -> np.ndarray:
    """CRAIG proxy per document: the mean over its tokens of the gradient of
    the token's cross-entropy with respect to the output layer's input,
    ``(softmax(h W^T) - onehot(y)) W``.  (B, D) float32 on the host."""
    _DIMS[dims.key()] = dims
    fn = _proxy_fn(dims.key(), mode)
    out = []
    for lo in range(0, len(tokens), docs_per_call):
        sl = slice(lo, lo + docs_per_call)
        out.append(np.asarray(fn(params, jnp.asarray(tokens[sl]),
                                 jnp.asarray(labels[sl]))))
    return np.concatenate(out, axis=0)


def clip_by_global_norm(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam_apply(params, m, v, grads, lr, b1, b2, eps, bc1, bc2):
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps),
        params, m, v)
    return params, m, v


def adamw_step(params, m, v, grads, step: int, opt: dict):
    """One Adam step (no weight decay) with global-norm clipping, step ≥ 1."""
    b1, b2 = float(opt["b1"]), float(opt["b2"])
    return _adam_apply(
        params, m, v, grads, jnp.float32(opt["lr"]), jnp.float32(b1),
        jnp.float32(b2), jnp.float32(opt["eps"]),
        jnp.float32(1 - b1 ** step), jnp.float32(1 - b2 ** step))
