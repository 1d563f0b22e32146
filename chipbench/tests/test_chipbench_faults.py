"""A run with the timed path broken underneath must come out not correct.

Each test skips the harness's look for a chip and drives the rest of a run
(set-up, window, reference comparison against the cell's own limits) on
the CPU at a small size, with one fault planted in the program: a train
step that returns its state unchanged, half of the batch left out of the
mean, the γ weights of a coreset batch ignored, proxy features altered
where they are produced, a greedy pick or a γ altered where the selection
produces them.  A run without a fault must stay correct at the same size.
"""
from __future__ import annotations

import time

import jax
import pytest

from chipbench import harness

TINY_HF = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
           "vocab_size": 512}
TINY = {"hf_config": TINY_HF, "batch": 4, "seq_len": 32, "pool_docs": 32,
        "check_docs": 4, "topics": 4}


def run(cell_name: str, seed: int = 2**31 + 7, control: bool = False) -> dict:
    cell = harness.load_cell(cell_name, overrides=TINY)
    return harness.run_cell(cell, seed, 1.0, False, jax.devices()[:1],
                            time.perf_counter(), log=lambda m: None,
                            control=control)


def failing(out: dict) -> set:
    return {k for k, v in out["checks"].items() if not v["value"] <= v["limit"]}


@pytest.fixture
def broken_step(monkeypatch):
    import repro.train.trainer as trainer_mod

    real = trainer_mod.make_train_step

    def plant(kind):
        def make(cfg, optimizer, **kw):
            step = real(cfg, optimizer, **kw)

            def broken(params, opt_state, batch):
                if kind == "half_batch":
                    half = batch["tokens"].shape[0] // 2
                    return step(params, opt_state,
                                {k: v[:half] for k, v in batch.items()})
                if kind == "weights_ignored":
                    return step(params, opt_state,
                                {**batch, "weights": jax.numpy.ones_like(
                                    batch["weights"])})
                _, _, metrics = step(params, opt_state, batch)
                return params, opt_state, metrics

            return broken

        monkeypatch.setattr(trainer_mod, "make_train_step", make)

    return plant


@pytest.mark.parametrize("cell", ["qwen3-1.7b-L4.train-async",
                                  "qwen3-1.7b-L4.refresh"])
def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]


def test_state_left_unchanged_is_caught(broken_step):
    broken_step("unchanged")
    out = run("qwen3-1.7b-L4.train-async")
    assert not out["correct"]
    assert {"grad_gap", "update_gap"} <= failing(out)


def test_half_batch_is_caught(broken_step):
    broken_step("half_batch")
    out = run("qwen3-1.7b-L4.train-async")
    assert not out["correct"]
    assert "loss_gap" in failing(out)


def test_ignored_weights_are_caught(broken_step):
    broken_step("weights_ignored")
    out = run("qwen3-1.7b-L4.train-async")
    assert not out["correct"]
    assert failing(out) & {"loss_gap", "grad_gap", "update_gap"}


@pytest.mark.parametrize("fault,number", [("pick", "greedy_gap"),
                                          ("gamma", "gamma_gap")])
def test_altered_selection_is_caught(monkeypatch, fault, number):
    from repro.core.engines import device

    real = device.greedy_fl_device

    def altered(feats, budget, **kw):
        res = real(feats, budget, **kw)
        if fault == "pick":  # the first pick after the prefix becomes a row
            # not picked
            r0 = len(kw["init_selected"]) if kw.get("init_selected") is not None else 0
            left = sorted(set(range(feats.shape[0])) - set(res.indices.tolist()))
            return res._replace(indices=res.indices.at[r0].set(left[0]))
        # one row's weight moves from the last pick to the first
        return res._replace(weights=res.weights.at[0].add(1.0).at[-1].add(-1.0))

    monkeypatch.setattr(device, "greedy_fl_device", altered)
    out = run("qwen3-1.7b-L4.train-async")
    assert not out["correct"]
    assert number in failing(out)


def test_altered_features_are_caught(monkeypatch):
    from repro.core import extract

    real = extract.make_scan_extract

    def altered(select_fn):
        scan = real(select_fn)
        return lambda params, batches: scan(params, batches) * 1.05

    monkeypatch.setattr(extract, "make_scan_extract", altered)
    out = run("qwen3-1.7b-L4.refresh")
    assert not out["correct"]
    assert failing(out) == {"feat_gap"}
