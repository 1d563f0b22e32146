#!/usr/bin/env python3
"""On-chip smoke run: the CRAIG trainer and the coreset service on a TPU.

    python chip_smoke.py              # phases 1 and 2 on one chip
    python chip_smoke.py --chips 4    # tree selection on a 4-chip mesh only

Phase 1 trains qwen3-1.7b at its published widths through ``Trainer``
(``launch/train.py``'s construction) with an async CRAIG refresh on the
``device`` engine: one refresh selects from a 512-document pool, installs at
the epoch boundary, and a few steps train on the γ-weighted coreset.  The
depth is cut to ``N_LAYERS``: params, the donated AdamW state, one refresh's
params snapshot and the extraction program's temporaries then fit the 16 GiB
of one v5e chip (``compiled.memory_analysis()`` of the train step and of the
extraction scan).  Weights are random, made from ``--seed``.

Phase 2 feeds a ``CoresetService`` 8192 clustered rows at the qwen3-1.7b
proxy width (2048) and finalizes through the blocked ``fl_replay`` kernel,
then runs a short exchange through the ``launch/serve.py --coreset``
JSON-lines protocol.

``--chips 4`` runs only ``tree_select_mesh`` over a (2, 2) tree on four
chips and compares it with ``tree_select_host`` on the same pool.

Each phase checks its results and that the kernels it resolves are Pallas
kernels compiled for the chip; any failure exits non-zero.  Without a TPU
the script exits non-zero before any phase.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import io
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_LAYERS = 4
BATCH, SEQ, POOL_DOCS = 8, 1024, 512
CRAIG_FRACTION = 0.5
CORESET_STEPS = 4
SERVICE_BUDGET, SERVICE_DIM, SERVICE_DELTAS, DELTA_ROWS = 512, 2048, 4, 2048
TREE_FANOUTS, TREE_LEAF_ROWS, TREE_DIM = (2, 2), 20480, 2048
TREE_R_LOCAL, TREE_R_FINAL = 64, 128


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def _lowers_to_kernel(jitted, *args, **kwargs) -> bool:
    """True when the lowered program calls a Mosaic (Pallas TPU) kernel."""
    return "tpu_custom_call" in jitted.lower(*args, **kwargs).as_text()


def _peak_gib(device) -> float:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", float("nan")) / 2**30


def clustered_rows(rng, n: int, d: int, centers):
    """``n`` rows around seeded cluster ``centers`` (numpy, float32)."""
    import numpy as np

    pick = rng.integers(0, len(centers), size=n)
    noise = rng.standard_normal((n, d), dtype=np.float32)
    return centers[pick] + noise


# ---------------------------------------------------------------------------
# Phase 1: Trainer with an async device-engine refresh
# ---------------------------------------------------------------------------


def phase_train(cfg, *, seed: int, batch: int, seq: int, pool_docs: int,
                coreset_steps: int) -> dict:
    """Train through one installed refresh; returns the phase record."""
    import jax
    import numpy as np

    from repro.core.craig import CraigConfig
    from repro.core.engines import DeviceConfig
    from repro.core.engines.device import greedy_fl_device
    from repro.data.synthetic import TokenStream
    from repro.kernels.ops import resolve_impl
    from repro.models import init_params
    from repro.optim import adamw, warmup_cosine
    from repro.train import Trainer, TrainerConfig, make_select_step

    ds = TokenStream(n_docs=pool_docs, seq_len=seq,
                     vocab_size=cfg.vocab_size, n_topics=16, seed=seed)
    epoch0 = pool_docs // batch  # full-data steps before the install
    steps = epoch0 + coreset_steps
    tcfg = TrainerConfig(
        batch_size=batch,
        select_every_epochs=2,  # one refresh: triggered at epoch 0 only
        craig=CraigConfig(fraction=CRAIG_FRACTION, per_class=False,
                          engine=DeviceConfig()),
        proxy_pool_batches=pool_docs // batch,
        refresh_mode="async",
        seed=seed,
    )
    trainer = Trainer(
        cfg, tcfg, ds, adamw(warmup_cosine(3e-4, 10, steps)),
        lambda: init_params(jax.random.PRNGKey(seed), cfg),
    )
    n_params = sum(x.size for x in jax.tree.leaves(trainer.params))

    b0 = ds.batch(np.arange(batch))
    b0["weights"] = np.ones((batch,), np.float32)
    compile_s = []
    for _ in range(2):  # cold (empty cache) then warm (persistent cache)
        jax.clear_caches()
        t = time.perf_counter()
        trainer.train_step.lower(trainer.params, trainer.opt_state, b0).compile()
        compile_s.append(time.perf_counter() - t)

    gains_impl = resolve_impl(DeviceConfig().gains_impl, "jax")
    kernels = {
        "ce_proxy": _lowers_to_kernel(
            jax.jit(make_select_step(cfg, trainer.proxy_impl)),
            trainer.params, {k: b0[k] for k in ("tokens", "labels")},
        ),
        "fl_gains_argmax": _lowers_to_kernel(
            greedy_fl_device,
            jax.ShapeDtypeStruct((pool_docs, cfg.d_model), np.float32),
            int(CRAIG_FRACTION * pool_docs), gains_impl=gains_impl,
        ),
    }

    t = time.perf_counter()
    trainer.run(steps)
    trainer.refresher.wait()
    run_s = time.perf_counter() - t

    log_ = trainer.metrics_log
    step_ev = [m for m in log_ if m["event"] == "step"]
    refresh_ev = [m for m in log_ if m["event"] == "craig_refresh"]
    coreset_ev = [m for m in step_ev if m["epoch"] >= 1]
    losses = np.asarray([m["loss"] for m in step_ev])
    sampler = trainer.sampler.state_dict()
    gamma = np.asarray(sampler["weights"] or [], np.float64)

    check(len(step_ev) == steps, f"ran {len(step_ev)} of {steps} steps")
    check(bool(np.all(np.isfinite(losses))), f"non-finite losses {losses}")
    check(len(refresh_ev) == 1, f"{len(refresh_ev)} refreshes installed")
    ev = refresh_ev[0]
    check(ev["engine"]["name"] == "device", f"engine {ev['engine']}")
    check(ev["coreset_size"] == int(CRAIG_FRACTION * pool_docs),
          f"coreset size {ev['coreset_size']}")
    check(sampler["version"] == 1, f"sampler version {sampler['version']}")
    check(len(coreset_ev) == coreset_steps,
          f"{len(coreset_ev)} steps on the coreset")
    check(gamma.size == ev["coreset_size"] and gamma.sum() == pool_docs,
          f"Σγ = {gamma.sum()} over {gamma.size} docs, want {pool_docs}")
    return {
        "n_layers": cfg.n_layers,
        "params": int(n_params),
        "param_count": cfg.param_count(),
        "compile_s": {"cold": compile_s[0], "warm": compile_s[1]},
        "run_s": run_s,
        "engine": ev["engine"],
        "gains_impl": gains_impl,
        "proxy_impl": trainer.proxy_impl,
        "kernels": kernels,
        "select_time_s": ev["select_time_s"],
        "install_stall_s": ev["install_stall_s"],
        "loss_first": float(losses[0]),
        "loss_last_full_data": float(losses[len(losses) - coreset_steps - 1]),
        "loss_coreset": [float(m["loss"]) for m in coreset_ev],
        "coreset_size": ev["coreset_size"],
        "gamma_sum": float(gamma.sum()),
        "pool_docs": pool_docs,
    }


# ---------------------------------------------------------------------------
# Phase 2: CoresetService ingest + blocked fl_replay finalize
# ---------------------------------------------------------------------------


def phase_service(*, seed: int, budget: int, dim: int, n_deltas: int,
                  delta_rows: int, proto_rows: int = 128) -> dict:
    """Stream clustered deltas into a CoresetService; returns the record."""
    import numpy as np

    from repro.kernels import ops
    from repro.launch import serve
    from repro.serve import CoresetService

    rng = np.random.default_rng(seed)
    centers = 5.0 * rng.standard_normal((64, dim), dtype=np.float32)
    svc = CoresetService(budget, dim)  # sync: each delta drains + finalizes
    delta_s = []
    for _ in range(n_deltas):
        x = clustered_rows(rng, delta_rows, dim, centers)
        t = time.perf_counter()
        svc.submit_delta(x)
        delta_s.append(time.perf_counter() - t)
    u = svc.coreset()

    n = n_deltas * delta_rows
    idx = np.asarray(u.indices)
    finalize_impl = ops.resolve_impl(svc.selector.config.finalize_impl, "jax")
    replay_kernel = _lowers_to_kernel(
        ops.fl_replay,
        np.zeros((n, dim), np.float32), np.zeros((budget, dim), np.float32),
        np.ones((budget,), bool), np.zeros((n,), np.float32),
        np.float32(1.0),
    )
    check(u.n_seen == n, f"n_seen {u.n_seen}, want {n}")
    check(float(u.weights.sum()) == n, f"Σγ = {u.weights.sum()}, want {n}")
    check(idx.size == budget and np.unique(idx).size == idx.size,
          f"{idx.size} indices, {np.unique(idx).size} unique, want {budget}")
    check(bool(((idx >= 0) & (idx < n)).all()), "index outside the pool")
    check(bool(np.isfinite(u.coverage)), f"coverage {u.coverage}")

    # the JSON-lines protocol: every reply must say ok
    reqs = [{"op": "delta", "feats": clustered_rows(
        rng, proto_rows, dim, centers).tolist()} for _ in range(2)]
    reqs += [{"op": "coreset"}, {"op": "quit"}]
    out = io.StringIO()
    serve.main(
        ["--coreset", "--budget", str(budget // 8), "--dim", str(dim)],
        stdin=io.StringIO("\n".join(json.dumps(r) for r in reqs) + "\n"),
        stdout=out,
    )
    replies = [json.loads(line) for line in out.getvalue().splitlines()]
    check(len(replies) == len(reqs), f"{len(replies)} replies to {len(reqs)}")
    for r in replies:
        check(r.get("ok") is True, f"protocol reply not ok: {r}")
    check(sum(replies[2]["gamma"]) == 2 * proto_rows,
          f"protocol Σγ = {sum(replies[2]['gamma'])}")
    return {
        "n_seen": u.n_seen,
        "budget": budget,
        "dim": dim,
        "row_blocks": -(-n // 512),
        "finalize_impl": finalize_impl,
        "kernels": {"fl_replay": replay_kernel},
        "gamma_sum": float(u.weights.sum()),
        "coverage": float(u.coverage),
        "delta_s": delta_s,  # ingest + finalize; the first one compiles
        "protocol_replies": len(replies),
    }


# ---------------------------------------------------------------------------
# --chips 4: tree_select_mesh on a (2, 2) mesh vs tree_select_host
# ---------------------------------------------------------------------------


def phase_tree(devices, *, seed: int, fanouts, leaf_rows: int, dim: int,
               r_local: int, r_final: int) -> dict:
    """Mesh and host tree drivers on one seeded pool; returns the record."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.distributed import resolve_round1_config
    from repro.distributed.tree_select import (
        TreeTopology,
        tree_mesh,
        tree_select_host,
        tree_select_mesh,
    )

    topo = TreeTopology(tuple(fanouts))
    n = topo.n_leaves * leaf_rows
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 3)
    centers = 5.0 * jax.random.normal(k0, (64, dim), jnp.float32)
    assign = jax.random.randint(k1, (n,), 0, 64)
    feats = centers[assign] + jax.random.normal(k2, (n, dim), jnp.float32)
    leaf = resolve_round1_config("auto", {}, leaf_rows)
    mesh = tree_mesh(topo, devices[: topo.n_leaves])

    def mesh_fn(x):
        return tree_select_mesh(x, mesh, topo, r_local, r_final,
                                local_engine=leaf)[:2]

    mesh_kernel = _lowers_to_kernel(jax.jit(mesh_fn), feats)
    t = time.perf_counter()
    sm = tree_select_mesh(feats, mesh, topo, r_local, r_final,
                          local_engine=leaf)
    jax.block_until_ready(sm.indices)
    mesh_s = time.perf_counter() - t
    t = time.perf_counter()
    sh = tree_select_host(feats, topo, r_local, r_final, local_engine=leaf)
    jax.block_until_ready(sh.indices)
    host_s = time.perf_counter() - t

    im, ih = np.asarray(sm.indices), np.asarray(sh.indices)
    wm, wh = np.asarray(sm.weights), np.asarray(sh.weights)
    check(im.size == r_final and np.unique(im).size == r_final,
          f"mesh selected {np.unique(im).size} unique of {r_final}")
    check(np.array_equal(im, ih),
          f"mesh and host indices differ at {np.flatnonzero(im != ih)}")
    check(float(wm.sum()) == n and float(wh.sum()) == n,
          f"Σγ mesh {wm.sum()} host {wh.sum()}, want {n}")
    return {
        "fanouts": list(fanouts),
        "n": n,
        "dim": dim,
        "leaf_engine": leaf.to_dict(),
        "kernels": {"fl_gains_argmax in shard_map": mesh_kernel},
        "r_local": r_local,
        "r_final": r_final,
        "gamma_sum": {"mesh": float(wm.sum()), "host": float(wh.sum())},
        "coverage": {"mesh": float(sm.coverage), "host": float(sh.coverage)},
        "first_call_s": {"mesh": mesh_s, "host": host_s},  # compiles
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-chip tree-selection phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.cache import init_compile_cache

    cache_dir = init_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    devices = jax.devices()
    from repro.kernels import ops

    check(ops.interpret_default() is False, "Pallas would run interpreted")
    where = f"{dev.device_kind} ×{args.chips}"
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; compile cache {cache_dir}")

    if args.chips == 4:
        check(len(devices) >= 4, f"--chips 4 needs 4 devices, have "
              f"{len(devices)}")
        rec = phase_tree(devices, seed=args.seed, fanouts=TREE_FANOUTS,
                         leaf_rows=TREE_LEAF_ROWS, dim=TREE_DIM,
                         r_local=TREE_R_LOCAL, r_final=TREE_R_FINAL)
        check(rec["leaf_engine"].get("gains_impl") == "pallas",
              f"leaf engine {rec['leaf_engine']}")
        check(all(rec["kernels"].values()), f"kernels {rec['kernels']}")
        log(f"[tree on {where}] " + json.dumps(rec))
    else:
        from repro.configs.registry import get_config

        cfg = dataclasses.replace(get_config("qwen3-1.7b"), n_layers=N_LAYERS)
        rec = phase_train(cfg, seed=args.seed, batch=BATCH, seq=SEQ,
                          pool_docs=POOL_DOCS, coreset_steps=CORESET_STEPS)
        for key in ("gains_impl", "proxy_impl"):
            check(rec[key] == "pallas", f"{key} resolved to {rec[key]!r}")
        check(all(rec["kernels"].values()), f"kernels {rec['kernels']}")
        rec["peak_gib"] = _peak_gib(dev)
        log(f"[train on {where}] " + json.dumps(rec))

        rec = phase_service(seed=args.seed, budget=SERVICE_BUDGET,
                            dim=SERVICE_DIM, n_deltas=SERVICE_DELTAS,
                            delta_rows=DELTA_ROWS)
        check(rec["finalize_impl"] == "pallas",
              f"finalize_impl resolved to {rec['finalize_impl']!r}")
        check(all(rec["kernels"].values()), f"kernels {rec['kernels']}")
        rec["peak_gib"] = _peak_gib(dev)
        log(f"[service on {where}] " + json.dumps(rec))

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
