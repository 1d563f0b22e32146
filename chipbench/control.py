#!/usr/bin/env python3
"""One run of a cell with its control read beside the program.

    python chipbench/control.py --workload <cell> --seed <n> --seconds <s>

A control is the plain reference put in the program's place and computed
one precision step below what the configuration states; each cell's
comparison must find it not correct.  This runs the cell as
``chipbench/run.py`` does (set-up, the measured window, the program's
comparison), then reads the control on the same inputs, and prints the
result line with a ``control`` key: each number the control read, beside
the cell's limit.

* ``*.train-async``: the reference trains the program's first three
  batches and weights with every matmul operand rounded to int8 (the
  program computes in bfloat16), and the float64 greedy with its dot
  products at ``Precision.HIGH`` (three bfloat16 passes) selects from the
  program's features (the configuration states float32 at ``HIGHEST``);
* ``*.refresh``: the reference's proxy features of the sampled documents
  with every matmul operand rounded to int8, and the same greedy at
  ``HIGH``.

The benchmark's own runs never read the control.  It needs the chip at the
cells' sizes; ``tests/test_chipbench_control.py`` runs it small on the CPU.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", required=True)
    args = ap.parse_args(argv)
    from chipbench import run

    return run.main(["--workload", args.workload, "--seed", args.seed,
                     "--seconds", args.seconds, "--trace", "0"], control=True)


if __name__ == "__main__":
    sys.exit(main())
