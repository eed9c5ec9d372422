"""The readings the limits of ``correct`` were set from, taken on the chip.

    chiprun -- python3 benchmarks/tests/control_on_chip.py \\
        --workload <cell> --seeds 11,12,13 --seconds 8

For each seed, in one process: a short window of the cell at its own load
through ``run.measure`` (the same entry, programs and sizes as a timed run),
the numbers the comparison gives for the program, and the numbers it gives for
the control -- the plain reference put in the program's place with the
accumulator one step narrower (``precision.control`` of the configuration),
over the same chunk log.  The program has to read 0 everywhere; the control
has to fail at least one number.  Every comparison is exact, so every limit
is 0 and these readings only show that 0 separates the two.
"""

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import run  # noqa: E402
from harness import check  # noqa: E402

NARROWER = {"int16": np.int16, "int8": np.int8, "float32": np.float32}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    a = ap.parse_args()
    rows = []
    for seed in (int(x) for x in a.seeds.split(",")):
        args = run.parse_args(["--workload", a.workload, "--seed", str(seed),
                               "--seconds", str(a.seconds), "--trace", "0"])
        rc, result, d = run.measure(args, lambda text: None)
        if rc:
            print(f"seed {seed}: exit {rc}")
            return rc
        narrow = NARROWER[d["cfg"]["precision"]["control"].split()[0]]
        control = d["oracle"].expected(d["cfg"], seed, d["log"],
                                       acc_dtype=narrow)
        control = {k: v for k, v in control.items() if not k.startswith("_")}
        c_numbers, _ = check.compare(control, d["want"])
        row = {"seed": seed, "correct": result["correct"],
               "results": int(len(d["want"]["key"])),
               "program": d["numbers"], "control": c_numbers,
               "control_correct": check.verdict(c_numbers)[0]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    ok = all(r["correct"] and not r["control_correct"] for r in rows)
    print(f"{a.workload}: {len(rows)} seeds, program correct on all: "
          f"{all(r['correct'] for r in rows)}, control refused on all: "
          f"{all(not r['control_correct'] for r in rows)}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
