"""CRAIG refreshes of one pool back to back, with no train steps.

Set-up builds the program's ``Trainer`` on weights made from the seed, with
the refresh run inline (``refresh_mode="sync"``), and runs one cold refresh
and one warm-started refresh, which compile every program the window uses.
The window submits refreshes one after another until ``--seconds`` have
passed; each runs the trainer's own refresh work, ``ProxyExtractor.extract``
then ``CraigSelector.select`` on the ``device`` engine, warm-started from
the previous selection, and stages its coreset in the sampler.

Correctness compares the last refresh of the window: ``feat_gap``, the
proxy features of a sample of pool documents drawn from the seed against
the float32 reference (``chipbench/reference/<reference>.py``), the worst
row's ``‖f - f_ref‖ / ‖f_ref‖``; ``cov_gap``, the coverage the selection
reports against the float64 reference's for the same picks
(``selection_check``); and ``mass_gap``, exact: the coreset covers the
pool.  The picks and γ are compared in the train-async cell, where the
same selection runs; here the control reads them no worse than the
program does, so no limit could separate the two (PERF.md).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import harness
from chipbench.drivers import lm_common, selection_check

NUMBERS = ("feat_gap", "cov_gap", "mass_gap")


def setup(cell: harness.Cell, seed: int, devices, log) -> dict:
    ref = lm_common.reference_module(cell)
    cfg, hf = lm_common.program_config(cell)
    dims = ref.Qwen3Dims(hf)
    docs = lm_common.SeededDocs(seed, int(cell.param("pool_docs")),
                                int(cell.param("seq_len")), dims.V,
                                int(cell.param("topics")),
                                float(cell.param("zipf_a")))
    weights = ref.init_weights(seed, dims)
    tap = lm_common.SelectionTap(cell)
    trainer = lm_common.build_trainer(cell, seed, cfg, lambda: weights, docs,
                                      "sync")
    del weights
    state = {"cell": cell, "seed": seed, "trainer": trainer, "docs": docs,
             "dims": dims, "ref": ref, "tap": tap}
    for _ in range(2):  # cold, then warm-started: every program compiles
        t = time.perf_counter()
        trainer.refresher.submit(trainer.params)
        log(f"set-up refresh {time.perf_counter() - t:.3f} s")
    return state


def window(state: dict, seconds: float, spans: harness.Spans) -> dict:
    trainer, n_pool = state["trainer"], state["docs"].n_docs
    done = 0
    t0_ns = time.perf_counter_ns()
    t_end = t0_ns + int(seconds * 1e9)
    while time.perf_counter_ns() < t_end:
        with spans.span("refresh"):
            trainer.refresher.submit(trainer.params)
        done += 1
    t1_ns = time.perf_counter_ns()
    window_s = (t1_ns - t0_ns) / 1e9
    return {
        "t0_ns": t0_ns, "t1_ns": t1_ns, "window_s": window_s,
        "attempted": done, "failed": 0, "refreshes": done,
        "docs": done * n_pool,
        "e2e": {"refresh_docs_per_s": done * n_pool / window_s},
        "note": f"{done} refreshes of {n_pool} docs",
    }


def sample_rows(seed: int, n_pool: int, n: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 0x5A3])
    return np.sort(rng.choice(n_pool, size=min(n, n_pool), replace=False))


def feat_gap(feats: np.ndarray, ref_feats: np.ndarray) -> float:
    """The worst row's ``‖f - f_ref‖ / ‖f_ref‖``."""
    f, r = np.asarray(feats, np.float64), np.asarray(ref_feats, np.float64)
    return float(np.max(np.linalg.norm(f - r, axis=1) / np.linalg.norm(r, axis=1)))


def budget(cell) -> int:
    """The coreset size k the trainer asks of every refresh."""
    return int(round(float(cell.param("craig_fraction")) * int(cell.param("pool_docs"))))


def check(state: dict, record: dict, log) -> list:
    cell, docs, ref, dims = state["cell"], state["docs"], state["ref"], state["dims"]
    state["tap"].close()
    feats, init, sel = state["tap"].calls[-1]
    state["last_call"] = (np.asarray(feats, np.float32), init, sel)
    rows = sample_rows(state["seed"], docs.n_docs, int(cell.param("check_docs")))
    state["rows"] = rows
    state["trainer"] = state["tap"] = None
    del feats
    gc.collect()
    params = ref.init_weights(state["seed"], dims)
    ref_feats = ref.proxy_features(params, docs.tokens[rows], docs.labels[rows],
                                   dims)
    del params
    state["ref_feats"] = ref_feats
    k = budget(cell)
    nums = {"feat_gap": feat_gap(state["last_call"][0][rows], ref_feats),
            "mass_gap": selection_check.mass_gap(sel.indices, sel.weights,
                                                 docs.n_docs, k)}
    nums["cov_gap"] = selection_check.selection_numbers([state["last_call"]])["cov_gap"]
    return [harness.Check(n, float(nums[n]), harness.limit_of(cell, n))
            for n in NUMBERS]


def control(state: dict, log) -> dict:
    """The control's numbers, after ``check``: the reference's proxies of
    the same documents with every matmul operand rounded to int8, and the
    float64 greedy at ``Precision.HIGH`` on the program's features and
    prefix, in the program's place."""
    docs, rows = state["docs"], state["rows"]
    params = state["ref"].init_weights(state["seed"], state["dims"])
    low = state["ref"].proxy_features(params, docs.tokens[rows],
                                      docs.labels[rows], state["dims"], "int8")
    del params
    return {"feat_gap": feat_gap(low, state["ref_feats"]),
            "cov_gap": selection_check.control_numbers(
                [state["last_call"]], "high")["cov_gap"]}
