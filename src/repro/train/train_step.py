"""pjit train/select step factories.

``make_train_step``  — γ-weighted loss → grad → optimizer update, with
optional microbatched gradient accumulation (overlaps the per-microbatch
DCN all-reduce with compute under the XLA scheduler) and optional int8
gradient compression on the pure-DP ``pod`` axis.

``make_select_step`` — CRAIG selection forward: proxy features for a
candidate pool batch (the technique's own SPMD program; lowered in the
dry-run alongside train/serve).

Both return pure functions ready for ``jax.jit(..., in_shardings=...)``.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.kernels.ops import resolve_impl
from repro.models import loss_fn as model_loss_fn
from repro.models import proxy_features
from repro.models.config import ModelConfig
from repro.optim.optimizers import Optimizer, OptState

__all__ = ["make_train_step", "make_select_step", "TrainState"]

TrainState = tuple  # (params, OptState)


def make_train_step(
    cfg: ModelConfig,
    optimizer: Optimizer,
    microbatches: int = 1,
    grad_transform: Callable[[Any], Any] | None = None,
) -> Callable:
    """Returns train_step(params, opt_state, batch) → (params', opt', metrics).

    microbatches > 1 splits the global batch along dim 0 and accumulates
    gradients with a ``lax.scan`` (sequential microbatches — the standard
    accumulation trick that also caps activation memory).
    ``grad_transform`` hooks gradient compression (distributed/compression).
    """

    def loss_wrapper(params, batch):
        total, metrics = model_loss_fn(params, cfg, batch)
        return total, metrics

    grad_fn = jax.value_and_grad(loss_wrapper, has_aux=True)

    def single(params, batch):
        (loss, metrics), grads = grad_fn(params, batch)
        return loss, metrics, grads

    def accumulated(params, batch):
        def micro(carry, mb):
            acc, loss_acc = carry
            (loss, metrics), grads = grad_fn(params, mb)
            acc = jax.tree.map(jnp.add, acc, grads)
            return (acc, loss_acc + loss), metrics

        split = jax.tree.map(
            lambda x: x.reshape((microbatches, x.shape[0] // microbatches) + x.shape[1:]),
            batch,
        )
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss_sum), metrics = jax.lax.scan(
            micro, (zeros, jnp.zeros((), jnp.float32)), split
        )
        grads = jax.tree.map(lambda g: g / microbatches, grads)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return loss_sum / microbatches, metrics, grads

    def train_step(params, opt_state: OptState, batch):
        if microbatches > 1:
            loss, metrics, grads = accumulated(params, batch)
        else:
            loss, metrics, grads = single(params, batch)
        if grad_transform is not None:
            grads = grad_transform(grads)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        out_metrics = {
            "loss": loss,
            "aux_loss": metrics.get("aux_loss", jnp.zeros(())),
            "step": new_opt.step,
        }
        return new_params, new_opt, out_metrics

    return train_step


def make_select_step(
    cfg: ModelConfig,
    proxy_impl: str = "auto",
    compute_dtype=None,
) -> Callable:
    """select_step(params, batch) → (B, D) proxy features (fp32).

    The trainer's ``ProxyExtractor`` (core/extract.py) scans this over the
    candidate pool, then hands features to CraigSelector /
    core.distributed.distributed_select.

    Args:
      proxy_impl: which CE-backward head computes the unembed-input proxy —
        * ``'auto'`` (default): ``'pallas'`` on TPU, ``'einsum'`` elsewhere;
        * ``'einsum'``: chunked ``lax.scan`` path
          (``core.proxy.lm_unembed_input_proxy``) — the shard_map-safe body;
        * ``'pallas'``: fused flash-style ``ce_proxy`` kernel
          (kernels/ce_proxy.py; interpret mode off-TPU, so CI exercises it).
      compute_dtype: matmul dtype override for the pallas path (fp32
        accumulation either way); None keeps the model's COMPUTE_DTYPE
        (bf16) — mirroring ``lm_unembed_input_proxy``.
    """
    proxy_impl = resolve_impl(proxy_impl, "einsum")
    if proxy_impl == "pallas":
        from repro.models import proxy_features_fused

        kw = {} if compute_dtype is None else {"compute_dtype": compute_dtype}

        def select_step(params, batch):
            return proxy_features_fused(params, cfg, batch, **kw)

        return select_step
    if proxy_impl != "einsum":
        raise ValueError(
            f"unknown proxy_impl {proxy_impl!r} (want 'auto'|'einsum'|'pallas')"
        )

    def select_step(params, batch):
        return proxy_features(params, cfg, batch)

    return select_step
