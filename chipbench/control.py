"""Readings of the control: the configuration's reference put in the
program's place with one stated guarantee broken, compared as a run
compares the program.

    python3 chipbench/control.py --workload <cell> --steps <n> \\
        --seeds <s1> <s2> ...

For a fixpoint cell the control is the reference's fixpoint stopped one
round before it is complete; for an update cell it is the view one
batch behind the updates applied. ``--steps`` is the number of outputs
a run of the cell compares. Each seed prints one JSON line with the
numbers ``correct`` compares; the control must come out not correct.
The program and the chip are not used: the control's outputs are the
reference's own.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import bench as B  # noqa: E402
from chipbench import check as C  # noqa: E402


def readings(cell: B.Cell, seed: int, steps: int) -> dict:
    driver = cell.driver.Driver(cell, seed)
    driver.stand_in(steps)
    result = C.compare(driver.control(), driver.expected())
    return {"seed": seed, "correct": C.correct(result), **result}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = B.resolve(args.workload)
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed, args.steps)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
