"""Model-level details: padded-vocab exactness, remat invariance, weights."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig, init_params, loss_fn
from repro.models.model import _chunked_ce


def test_padded_vocab_ce_is_exact():
    """CE with padded logit columns masked == CE over the true vocab."""
    B, T, D, V = 2, 12, 16, 100  # padded to 128
    key = jax.random.PRNGKey(0)
    hidden = jax.random.normal(key, (B, T, D))
    unembed = jax.random.normal(jax.random.PRNGKey(1), (D, 128)) * 0.3
    labels = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, V)
    padded = _chunked_ce(hidden, unembed, labels, chunk=4, valid_v=V)
    exact = _chunked_ce(hidden, unembed[:, :V], labels, chunk=4)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(exact), rtol=2e-3, atol=1e-3)


def test_chunk_size_invariance():
    B, T, D, V = 2, 24, 8, 64
    hidden = jax.random.normal(jax.random.PRNGKey(0), (B, T, D))
    unembed = jax.random.normal(jax.random.PRNGKey(1), (D, V)) * 0.3
    labels = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, V)
    a = _chunked_ce(hidden, unembed, labels, chunk=4)
    b = _chunked_ce(hidden, unembed, labels, chunk=24)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=1e-3)


@pytest.mark.tier2
@pytest.mark.parametrize("policy", ["nothing", "dots", "full"])
def test_remat_policy_value_invariance(policy):
    """Remat changes memory/recompute, never the loss value or gradients."""
    cfg = ModelConfig(
        name="t", family="dense", n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=2, d_ff=64, vocab_size=128, logit_chunk=8,
        remat_policy=policy,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 128),
    }
    loss, _ = loss_fn(params, cfg, batch)
    g = jax.grad(lambda p: loss_fn(p, cfg, batch)[0])(params)

    cfg0 = dataclasses.replace(cfg, remat_policy="nothing")
    loss0, _ = loss_fn(params, cfg0, batch)
    g0 = jax.grad(lambda p: loss_fn(p, cfg0, batch)[0])(params)
    # bf16 compute: different fusion/recompute orders reassociate sums
    assert float(loss) == pytest.approx(float(loss0), rel=2e-3)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-2, atol=2e-3)


@pytest.mark.tier2
def test_craig_weights_scale_gradients():
    """γ-weighted loss == reweighting per-example gradient contributions
    (the paper's per-element stepsize semantics under linear scaling)."""
    cfg = ModelConfig(
        name="t", family="dense", n_layers=1, d_model=16, n_heads=2,
        n_kv_heads=2, d_ff=32, vocab_size=64, logit_chunk=8,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 64)

    def grad_for(w):
        batch = {"tokens": toks, "labels": labels, "weights": jnp.asarray(w)}
        return jax.grad(lambda p: loss_fn(p, cfg, batch)[0])(params)

    # weights (2, 0): loss == example-0-only loss
    g_w = grad_for([2.0, 0.0])
    batch0 = {"tokens": toks[:1], "labels": labels[:1]}
    g_0 = jax.grad(lambda p: loss_fn(p, cfg, batch0)[0])(params)
    for a, b in zip(jax.tree.leaves(g_w), jax.tree.leaves(g_0)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-2, atol=2e-4)


@pytest.mark.tier2
def test_scan_vs_unrolled_stack_equivalence():
    """scan_layers=False (roofline probes) computes the identical function."""
    base = dict(
        name="t", family="dense", n_layers=4, d_model=32, n_heads=2,
        n_kv_heads=2, d_ff=64, vocab_size=64, logit_chunk=8,
    )
    cfg_s = ModelConfig(**base, scan_layers=True)
    cfg_u = ModelConfig(**base, scan_layers=False)
    params_s = init_params(jax.random.PRNGKey(0), cfg_s)
    # map scanned params → unrolled params (period = 1 layer)
    scanned = params_s["stack"]["scanned"]
    remainder = [
        jax.tree.map(lambda l: l[i], scanned[0]) for i in range(4)
    ]
    params_u = dict(params_s)
    params_u["stack"] = {"scanned": None, "remainder": remainder}
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 64),
    }
    l_s, _ = loss_fn(params_s, cfg_s, batch)
    l_u, _ = loss_fn(params_u, cfg_u, batch)
    # identical math; bf16 fusion order differs between scan and unrolled
    assert float(l_s) == pytest.approx(float(l_u), rel=2e-3)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_param_count_matches_init(tied):
    """``param_count`` counts what ``init_params`` allocates: a tied config
    has no separate unembed head.  The published Qwen3-1.7B (tied) has
    1.72 B parameters."""
    from repro.configs.registry import get_config

    cfg = dataclasses.replace(get_config("qwen3-1.7b"), tie_embeddings=tied)
    tree = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    n = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))
    tables = 1 if tied else 2  # embed (+ unembed), padded to padded_vocab
    pad = (cfg.padded_vocab - cfg.vocab_size) * cfg.d_model * tables
    assert n == cfg.param_count() + pad
    if tied:
        assert abs(cfg.param_count() - 1.72e9) / 1.72e9 < 0.02
