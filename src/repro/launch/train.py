"""Production training driver.

Single-host usage (CPU smoke / debugging):

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --steps 20 --batch 8 --seq 64

On a pod, the same driver runs under the production mesh: every jitted step
is sharded via the rules in distributed/sharding.py; `--dry-run` lowers and
compiles the full-scale program instead of executing (see launch/dryrun.py
for the batched sweep).

Features wired in: CRAIG per-epoch coreset refresh (--craig-fraction),
microbatched grad accumulation, checkpoint/restart (--ckpt), preemption
(SIGTERM → emergency save), deterministic restart stream.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs.registry import ARCHS, get_config, smoke_config
from repro.core.craig import CraigConfig
from repro.data.synthetic import TokenStream
from repro.launch.cache import init_compile_cache
from repro.models import init_params
from repro.optim import adamw, warmup_cosine
from repro.train import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--docs", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--craig-fraction", type=float, default=0.5)
    ap.add_argument("--no-craig", action="store_true")
    ap.add_argument("--select-every", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    init_compile_cache()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.frontend != "tokens":
        # stub-frontend archs train over precomputed embeddings; the
        # synthetic stream provides tokens — swap to token frontend for the
        # driver (backbone identical), as the modality stub is data-side.
        cfg = dataclasses.replace(cfg, frontend="tokens")
    print(f"arch={cfg.name} ({'smoke' if args.smoke else 'full'}) "
          f"params≈{cfg.param_count()/1e6:.1f}M layers={cfg.n_layers}")

    ds = TokenStream(n_docs=args.docs, seq_len=args.seq,
                     vocab_size=cfg.vocab_size, n_topics=16)
    tcfg = TrainerConfig(
        batch_size=args.batch,
        select_every_epochs=0 if args.no_craig else args.select_every,
        use_craig=not args.no_craig,
        craig=CraigConfig(fraction=args.craig_fraction, per_class=False),
        proxy_pool_batches=max(1, args.docs // args.batch),
        checkpoint_dir=args.ckpt,
        microbatches=args.microbatches,
    )
    trainer = Trainer(
        cfg, tcfg, ds, adamw(warmup_cosine(args.lr, 10, args.steps)),
        lambda: init_params(jax.random.PRNGKey(0), cfg),
    )
    trainer.install_signal_handler()
    if trainer.restore_or_init():
        print(f"restored at step {trainer.step}")
    t0 = time.time()
    log = trainer.run(args.steps)
    steps = [m for m in log if m["event"] == "step"]
    print(f"{len(steps)} steps in {time.time()-t0:.1f}s; "
          f"loss {steps[0]['loss']:.3f} → {np.mean([s['loss'] for s in steps[-5:]]):.3f}")


if __name__ == "__main__":
    main()
