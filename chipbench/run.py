#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are looked up by
name from ``BENCHMARK.json`` (``chipbench/harness.py``).  The run sets up
and warms the cell's own shapes (``setup_s``), measures for ``--seconds``
seconds, compares what the timed path produced with the plain reference,
and prints as the last line of stdout one JSON object::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {"platform", "kind", "count", "memory_peak_bytes", ...},
     "checks": {<number>: {"value", "limit"}}}

With ``--trace 1`` the window is traced with ``jax.profiler`` and the
metrics are the cell's per-layer metrics; ``device`` adds ``busy_s`` and
``window_s`` and the line carries a ``breakdown``.

It exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  JAX's persistent compilation cache is kept at the
fixed ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def main(argv=None, control: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    cache = ROOT / ".jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    from repro.launch.cache import init_compile_cache

    init_compile_cache()
    import jax

    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)  # no eviction

    from chipbench import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chipbench: needs a TPU, but JAX found platform {platform!r} "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    harness.peaks_for(devices[0].device_kind)  # an unknown chip is an error
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices[: cell.chips], T_START, control=control)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
