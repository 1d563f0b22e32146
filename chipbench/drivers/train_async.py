"""Coreset training with the asynchronous CRAIG refresh on the same chip.

Set-up builds one ``Trainer`` (the program's normal path: ``Trainer`` →
``ProxyExtractor`` → ``CraigSelector`` on the ``device`` engine →
``CoresetSampler``) on weights made from the seed.  It submits the first
refresh on those weights, as the trainer does at its first epoch boundary,
waits for it and skips the sampler past the full-data epoch.  It then
drives the trainer through its first three steps with ``Trainer.run``, the
call the window makes: the first installs the coreset and submits the
warm-started second refresh, and all three train on coreset rows (rows that
all differ) weighted by the installed γ.  Set-up waits for the second
refresh and skips to the next epoch boundary, so the window starts as
training does in steady state: a coreset installed and a warm-started
refresh in flight, every program compiled.

The window calls ``Trainer.run(1)`` until ``--seconds`` have passed; every
epoch boundary installs the refresh that finished and submits the next.

Correctness follows the first three steps with the plain float32
reference (``chipbench/reference/<reference>.py``) on the same weights,
rows and γ, the weights taken from the first refresh's selection (not
from the sampler): the loss of each step, the norm of each weight's first
gradient as AdamW received it (its first moment after one step over
``1 - b1``), and the norm of each weight's change over the three steps;
each stacked weight counts once per layer.  The first refresh's selection
(cold) and the window's last (warm-started) are read against the float64
greedy (``selection_check``).  ``mass_gap`` is exact: the last refresh's
coreset and the coreset installed last both cover the pool.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import harness
from chipbench.drivers import lm_common, refresh, selection_check

NUMBERS = ("loss_gap", "grad_gap", "update_gap",
           *selection_check.NUMBERS, "mass_gap")


def _leaf_norms(ref, tree, n_layers, scale=1.0) -> dict:
    import jax.numpy as jnp

    return {k: float(jnp.linalg.norm(v.astype(jnp.float32))) * scale
            for k, v in ref.layer_leaves(tree, n_layers).items()}


def setup(cell: harness.Cell, seed: int, devices, log) -> dict:
    import jax

    ref = lm_common.reference_module(cell)
    cfg, hf = lm_common.program_config(cell)
    dims = ref.Qwen3Dims(hf)
    seq, batch = int(cell.param("seq_len")), int(cell.param("batch"))
    docs = lm_common.SeededDocs(seed, int(cell.param("pool_docs")), seq,
                                dims.V, int(cell.param("topics")),
                                float(cell.param("zipf_a")))
    from repro.models import init_params

    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    weights = ref.init_weights(seed, dims)
    got = jax.eval_shape(lambda: weights)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            a.shape != b.shape or a.dtype != b.dtype for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("the benchmark's weight layout differs from the "
                           "program's init_params")
    tap = lm_common.SelectionTap(cell)
    trainer = lm_common.build_trainer(cell, seed, cfg, lambda: weights, docs,
                                      "async")
    del weights
    b1 = float(cell.param("optimizer")["b1"])
    trainer.refresher.submit(trainer.params)
    trainer.refresher.wait()
    feats, _, first = tap.calls[-1]
    first_call = (np.asarray(feats, np.float32), None, first)
    trainer.sampler.skip_to(trainer.sampler.epoch + 1, 0)
    p0 = trainer.params
    n_calls = len(docs.calls)
    trainer.run(1)
    m = trainer.opt_state.inner["m"]
    grad_norms = _leaf_norms(ref, m, dims.L, 1.0 / (1.0 - b1))
    trainer.run(2)
    delta = jax.tree.map(lambda a, b: a - b, trainer.params, p0)
    change_norms = _leaf_norms(ref, delta, dims.L)
    del delta, p0, m
    losses = [e["loss"] for e in trainer.metrics_log if e["event"] == "step"]
    first_rows = [idx for idx in docs.calls[n_calls:] if len(idx) == batch][:3]
    # steady state: let the warm-started second refresh finish and skip to
    # the boundary that installs it, so the window's first step submits the
    # third
    trainer.refresher.wait()
    trainer.sampler.skip_to(trainer.sampler.epoch + 1, 0)
    n_log = len(trainer.metrics_log)
    log(f"set-up: losses {losses}, refreshes "
        f"{[e['select_time_s'] for e in trainer.metrics_log if e['event'] == 'craig_refresh']}")
    return {
        "cell": cell, "seed": seed, "trainer": trainer, "docs": docs,
        "dims": dims, "hf": hf, "ref": ref, "n_log": n_log, "tap": tap,
        "program": {"losses": losses[:3], "grad": grad_norms,
                    "change": change_norms},
        "first_rows": first_rows, "first_call": first_call,
        "first_weights": [batch_weights(first, rows) for rows in first_rows],
    }


def batch_weights(sel, rows) -> np.ndarray:
    """The weight of each row of a coreset batch: its γ in the selection,
    scaled so that γ averages 1 over the coreset (0 for a row outside it).
    The pool is the whole corpus, so pool and corpus indices agree."""
    gamma = dict(zip(np.asarray(sel.indices).tolist(),
                     np.asarray(sel.weights, np.float64).tolist()))
    scale = len(gamma) / sum(gamma.values())
    return np.array([gamma.get(int(r), 0.0) * scale for r in rows], np.float32)


def window(state: dict, seconds: float, spans: harness.Spans) -> dict:
    cell, trainer = state["cell"], state["trainer"]
    tokens_per_step = int(cell.param("batch")) * int(cell.param("seq_len"))
    steps = 0
    t0_ns = time.perf_counter_ns()
    t_end = t0_ns + int(seconds * 1e9)
    while time.perf_counter_ns() < t_end:
        with spans.span("train_step"):
            trainer.run(1)
        steps += 1
    t1_ns = time.perf_counter_ns()
    window_s = (t1_ns - t0_ns) / 1e9
    events = trainer.metrics_log[state["n_log"]:]
    refreshes = [e for e in events if e["event"] == "craig_refresh"]
    return {
        "t0_ns": t0_ns, "t1_ns": t1_ns, "window_s": window_s,
        "attempted": steps, "failed": 0,
        "steps": steps, "tokens": steps * tokens_per_step,
        "refreshes": refreshes,
        "e2e": {"train_tokens_per_s": steps * tokens_per_step / window_s},
        "note": f"{steps} steps, {len(refreshes)} refreshes installed",
    }


def reference_readings(cell, seed, dims, ref, docs, rows, weights, mode: str):
    """The reference's three steps on the rows and weights the program
    trained on."""
    import jax
    import jax.numpy as jnp

    opt = cell.param("optimizer")
    params = ref.init_weights(seed, dims)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad = [], None
    for step, (r, w) in enumerate(zip(rows, weights), start=1):
        loss, g = ref.weighted_loss_grad(params, docs.tokens[r], docs.labels[r],
                                         w, dims, mode)
        g = ref.clip_by_global_norm(g, float(opt["clip"]))
        if step == 1:
            grad = _leaf_norms(ref, g, dims.L)
        losses.append(loss)
        params, m, v = ref.adamw_step(params, m, v, g, step, opt)
        del g
    del m, v
    p0 = ref.init_weights(seed, dims)
    change = {k: float(jnp.linalg.norm(a - b)) for (k, a), b in zip(
        ref.layer_leaves(params, dims.L).items(),
        ref.layer_leaves(p0, dims.L).values())}
    return {"losses": losses, "grad": grad, "change": change}


def _worst_leaf_gap(prog: dict, refv: dict, keep=None) -> float:
    med = float(np.median(list(refv.values())))
    gaps = [abs(prog[k] - r) / max(r, med) for k, r in refv.items()
            if keep is None or k in keep]
    return max(gaps)


def compare(prog: dict, refr: dict) -> dict:
    """The compared numbers of the three steps, from the two sides' readings."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in
                   zip(prog["losses"], refr["losses"]))
    med_g = float(np.median(list(refr["grad"].values())))
    moved = {k for k, g in refr["grad"].items() if g >= 1e-3 * med_g}
    return {
        "loss_gap": loss_gap,
        "grad_gap": _worst_leaf_gap(prog["grad"], refr["grad"]),
        "update_gap": _worst_leaf_gap(prog["change"], refr["change"], moved),
    }


def _selection_calls(state: dict) -> list:
    return [state["first_call"], state["last_call"]]


def check(state: dict, record: dict, log) -> list:
    cell, trainer, docs = state["cell"], state["trainer"], state["docs"]
    trainer.refresher.wait()
    state["tap"].close()
    k = refresh.budget(cell)
    feats, init, sel = state["tap"].calls[-1]
    state["last_call"] = (np.asarray(feats, np.float32), init, sel)
    installed = trainer.sampler.state_dict()
    mass = (selection_check.mass_gap(sel.indices, sel.weights, docs.n_docs, k)
            + selection_check.mass_gap(installed["indices"], installed["weights"],
                                       docs.n_docs, k))
    if not record["refreshes"]:
        mass = float("inf")  # no refresh reached the sampler in the window
    state["trainer"] = state["tap"] = None
    del trainer, feats
    gc.collect()
    refr = reference_readings(cell, state["seed"], state["dims"], state["ref"],
                              docs, state["first_rows"], state["first_weights"],
                              "f32")
    state["reference"] = refr
    nums = compare(state["program"], refr)
    nums.update(selection_check.selection_numbers(_selection_calls(state)))
    nums["mass_gap"] = mass
    log(f"reference losses {refr['losses']}, program {state['program']['losses']}")
    return [harness.Check(n, float(nums[n]), harness.limit_of(cell, n))
            for n in NUMBERS]


def control(state: dict, log) -> dict:
    """The control's numbers, after ``check``: the reference with its
    matmul operands rounded to int8 trains the same rows and weights, and
    the float64 greedy at ``Precision.HIGH`` selects from the same features
    and prefixes, in the program's place."""
    cell, docs = state["cell"], state["docs"]
    ctrl = reference_readings(cell, state["seed"], state["dims"], state["ref"],
                              docs, state["first_rows"], state["first_weights"],
                              "int8")
    nums = compare(ctrl, state["reference"])
    nums.update(selection_check.control_numbers(_selection_calls(state), "high"))
    return nums
