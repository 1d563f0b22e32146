"""Serving driver: batched greedy decoding against any registry arch, or
the coreset service behind a JSON-lines protocol.

Decode mode:

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --batch 4 --prompt-len 16 --new 32

Coreset-as-a-service mode (DESIGN.md §10) — one JSON request per stdin
line, one JSON response per stdout line:

    PYTHONPATH=src python -m repro.launch.serve --coreset --budget 32 --dim 8

    {"op": "delta", "feats": [[...], ...], "labels": [...]?}
        -> {"ok": true, "version": v, "n_seen": n}
    {"op": "coreset"}
        -> {"ok": true, "version": v, "indices": [...], "gamma": [...],
            "n_seen": n, "n_live": l, "coverage": c}
    {"op": "quit"}   -> {"ok": true, "bye": true}
    anything invalid -> {"ok": false, "error": "..."}   (service keeps running)

Pod-scale decode lowering (KV cache sharded per distributed/sharding.py)
is exercised by `launch/dryrun.py --shape decode_32k / long_500k`.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import numpy as np

from repro.configs.registry import ARCHS, get_config, smoke_config
from repro.launch.cache import init_compile_cache
from repro.models import init_params
from repro.serve import greedy_generate


def _serve_coreset(args, stdin=None, stdout=None) -> None:
    """JSON-lines loop over a CoresetService (sync mode: the response to a
    delta is only written once its drain has published)."""
    from repro.core.engines import StreamingConfig
    from repro.faults import FailurePolicy, install_from_env
    from repro.serve import CoresetService

    install_from_env()  # chaos tests arm the service via $REPRO_FAULT_PLAN
    stdin = sys.stdin if stdin is None else stdin
    stdout = sys.stdout if stdout is None else stdout
    svc = CoresetService(
        args.budget,
        args.dim,
        config=StreamingConfig(eps=args.eps, levels=args.levels),
        metric=args.metric,
        per_class=args.per_class,
        mode="sync",
        evict=args.evict,
        failure_policy=FailurePolicy(
            max_retries=args.ingest_retries,
            backoff_base_s=args.ingest_backoff_s,
            on_exhaustion=args.on_exhaustion,
        ),
    )

    def reply(obj: dict) -> None:
        stdout.write(json.dumps(obj) + "\n")
        stdout.flush()

    for line in stdin:
        line = line.strip()
        if not line:
            continue
        try:
            req = json.loads(line)
            op = req.get("op")
            if op == "delta":
                version = svc.submit_delta(req["feats"], req.get("labels"))
                failure = svc.pop_failure()
                if failure is not None:
                    # keep_stale abandonment: the drain was dropped, the
                    # installed selection is unchanged — tell the client
                    # explicitly instead of letting the version stall
                    reply({"ok": False, "n_seen": svc.n_seen, **failure})
                else:
                    reply(
                        {"ok": True, "version": version, "n_seen": svc.n_seen}
                    )
            elif op == "coreset":
                u = svc.coreset(block=True)
                if u is None:
                    reply({"ok": False, "error": "no deltas ingested yet"})
                else:
                    reply(
                        {
                            "ok": True,
                            "version": u.version,
                            "indices": u.indices.tolist(),
                            "gamma": u.weights.tolist(),
                            "n_seen": u.n_seen,
                            "n_live": u.n_live,
                            "coverage": u.coverage,
                        }
                    )
            elif op == "quit":
                reply({"ok": True, "bye": True})
                return
            else:
                reply({"ok": False, "error": f"unknown op {op!r}"})
        except Exception as e:  # noqa: BLE001 — protocol errors go to the client
            reply({"ok": False, "error": f"{type(e).__name__}: {e}"})


def main(argv=None, stdin=None, stdout=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new", type=int, default=32)
    # coreset service mode
    ap.add_argument("--coreset", action="store_true",
                    help="run the JSON-lines coreset service instead of decode")
    ap.add_argument("--budget", type=int, default=32)
    ap.add_argument("--dim", type=int, default=8)
    ap.add_argument("--metric", default="l2", choices=("l2", "cosine"))
    ap.add_argument("--per-class", action="store_true")
    ap.add_argument("--eps", type=float, default=0.15)
    ap.add_argument("--levels", type=int, default=0)
    ap.add_argument("--evict", action="store_true",
                    help="bounded-memory mode: drop pool rows no sieve "
                         "references after every drain (O(L·k·d) state)")
    ap.add_argument("--ingest-retries", type=int, default=0,
                    help="retries per ingest drain before the exhaustion "
                         "policy applies (DESIGN.md §12)")
    ap.add_argument("--ingest-backoff-s", type=float, default=0.05,
                    help="base of the exponential retry backoff")
    ap.add_argument("--on-exhaustion", default="raise",
                    choices=("raise", "keep_stale"),
                    help="'raise' fails the request; 'keep_stale' keeps "
                         "serving the installed selection and replies with "
                         "a craig_refresh_failed event")
    args = ap.parse_args(argv)
    init_compile_cache()

    if args.coreset:
        _serve_coreset(args, stdin=stdin, stdout=stdout)
        return
    if args.arch is None:
        ap.error("--arch is required unless --coreset is given")

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.frontend != "tokens":
        cfg = dataclasses.replace(cfg, frontend="tokens")
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    t0 = time.time()
    out = greedy_generate(params, cfg, prompts, max_new=args.new)
    dt = time.time() - t0
    n_tok = args.batch * (args.prompt_len + args.new)
    print(f"{cfg.name}: {out.shape} in {dt:.2f}s ({n_tok/dt:.0f} tok/s)")
    print("sample:", np.asarray(out[0, args.prompt_len:args.prompt_len + 12]))


if __name__ == "__main__":
    main()
