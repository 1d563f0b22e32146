"""Compile the main path's kernels for a TPU v5e chip that is described,
not attached (no chip is needed, and nothing runs).

Interpret-mode tests never reach the Mosaic compiler, which refuses what
the interpreter accepts: blocks whose last two dimensions are neither
(8, 128)-aligned nor whole, and tiles past the kernel's VMEM.  These cases
compile each kernel at the real widths of the qwen3-1.7b path
(d = 2048 proxy features, vocab 151936) and the smoke-depth train step,
whose memory must fit one chip.

The topology is described inside a fixture — never at import — because
only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N = 20480  # pool rows = candidates of one leaf
D = 2048  # qwen3-1.7b d_model = proxy feature width
V = 151_936  # qwen3-1.7b vocab
HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("tile_dtype", ["float32", "bfloat16"])
def test_fl_gains_argmax_compiles(one_chip, tile_dtype):
    """The device engine's sweep, with its 2048-wide candidate blocks."""
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731

    def sweep(x, cur, sq, d_max, chosen):
        return ops.fl_gains_argmax(
            x, x, cur, sq, sq, d_max, chosen, block_m=2048,
            tile_dtype=tile_dtype, interpret=False,
        )

    _compile(sweep, s((N, D)), s((N,)), s((N,)), s(()), s((N,), jnp.bool_))


def test_fl_replay_compiles(one_chip):
    """The streaming finalize: 40 row blocks × 4 candidate blocks."""
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa: E731
    m = 512

    def replay(x, e, valid, cur0, d_max):
        return ops.fl_replay(x, e, valid, cur0, d_max, interpret=False)

    _compile(replay, s((N, D)), s((m, D)), s((m,), jnp.bool_), s((N,)), s(()))


def test_topk_sim_compiles(one_chip):
    _compile(
        lambda x: ops.topk_sim(x, 64, interpret=False),
        _spec(one_chip, (N, D)),
    )


@pytest.mark.parametrize("d", [D, 4096])
def test_ce_proxy_compiles(one_chip, d):
    """The fused CE-backward proxy in bf16 at the published vocab, on the
    token tile its VMEM rule picks for d (4096: granite-3-8b's width)."""
    t = 2048
    _compile(
        lambda h, w, y: ops.ce_proxy(
            h, w, y, compute_dtype=jnp.bfloat16, interpret=False
        ),
        _spec(one_chip, (t, d), jnp.bfloat16),
        _spec(one_chip, (d, V), jnp.bfloat16),
        _spec(one_chip, (t,), jnp.int32),
    )


def test_pairwise_l2_compiles(one_chip):
    _compile(
        lambda x, y: ops.pairwise_l2(x, y, interpret=False),
        _spec(one_chip, (4096, D)),
        _spec(one_chip, (4096, D)),
    )


def test_smoke_train_step_fits_one_chip(one_chip, monkeypatch):
    """chip_smoke.py's layer cut: the train step, plus the params snapshot an
    async refresh holds and the extraction program's temporaries, fit HBM."""
    import dataclasses
    import importlib.util
    import pathlib

    from repro.configs.registry import get_config
    from repro.core.extract import make_scan_extract
    from repro.models import init_params
    from repro.optim import adamw, warmup_cosine
    from repro.train.train_step import make_select_step, make_train_step

    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    cfg = dataclasses.replace(
        get_config("qwen3-1.7b"), n_layers=smoke.N_LAYERS
    )
    b, t, m = smoke.BATCH, smoke.SEQ, smoke.POOL_DOCS // smoke.BATCH
    opt = adamw(warmup_cosine(3e-4, 10, 100))
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: _spec(one_chip, a.shape, a.dtype), tree
    )
    params = placed(
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    )
    opt_state = placed(jax.eval_shape(opt.init, params))
    batch = {
        "tokens": _spec(one_chip, (b, t), jnp.int32),
        "labels": _spec(one_chip, (b, t), jnp.int32),
        "weights": _spec(one_chip, (b,)),
    }
    # the trainer's jit: optimizer state donated, params not
    train = jax.jit(make_train_step(cfg, opt), donate_argnums=(1,))
    mt = train.lower(params, opt_state, batch).compile().memory_analysis()
    train_peak = (
        mt.argument_size_in_bytes + mt.output_size_in_bytes
        - mt.alias_size_in_bytes + mt.temp_size_in_bytes
    )
    # the extraction scan as the chip runs it: Pallas ce_proxy, not
    # interpreted (the CPU backend would otherwise pick interpret mode)
    monkeypatch.setattr(ops, "interpret_default", lambda: False)
    megabatch = {
        k: _spec(one_chip, (m, b, t), jnp.int32) for k in ("tokens", "labels")
    }
    extract = _compile(
        make_scan_extract(make_select_step(cfg, "pallas")), params, megabatch
    )
    me = extract.memory_analysis()
    snapshot = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    peak = train_peak + snapshot + me.temp_size_in_bytes
    assert train_peak < HBM_BYTES
    assert peak < HBM_BYTES, (train_peak, snapshot, me.temp_size_in_bytes)
