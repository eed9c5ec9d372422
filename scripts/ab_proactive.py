"""Interleaved A/B for proactive dispatch sizing.

Alternates the headline bench workload with proactive flush sizing ON and
OFF in ONE process, so drift over the session averages across arms.
Proactive sizing is opt-in: arm "on" sets WF_PROACTIVE=1, arm "off" unsets
it (native_core.py treats unset/"0"/"" as off).  Prints per-run tps +
launch diagnostics and per-arm best/median.

Usage: python scripts/ab_proactive.py [n_million] [rounds]
"""

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
import numpy as np


def main():
    n_m = float(sys.argv[1]) if len(sys.argv) > 1 else 16
    rounds = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    bench.N_TUPLES = int(n_m * 1e6)
    from windflow_tpu.core.tuples import Schema
    schema = Schema(value=np.int64)
    batches = bench.make_stream(schema)
    want = bench.expected_total(batches)

    bench.run_once(batches, schema)          # compile warmup
    from windflow_tpu.ops.resident import prewarm_regular_ladder
    prewarm_regular_ladder()

    arms = {"on": [], "off": []}
    for r in range(rounds):
        for arm in ("on", "off"):
            # proactive sizing is opt-in since the 2026-07-31 A/B showed
            # it losing on this wire (native_core.py): arm "on" opts in
            if arm == "on":
                os.environ["WF_PROACTIVE"] = "1"
            else:
                os.environ.pop("WF_PROACTIVE", None)
            dt, _n, total, diag = bench.run_once(batches, schema)
            assert total == want, (arm, total, want)
            row = {"tps": round(bench.N_TUPLES / dt, 1), **diag}
            arms[arm].append(row)
            print(f"round {r} {arm:3s}: {json.dumps(row)}", flush=True)
    os.environ.pop("WF_PROACTIVE", None)
    for arm, rows in arms.items():
        tps = [x["tps"] for x in rows]
        print(f"{arm:3s}: best {max(tps):,.0f}  median "
              f"{statistics.median(tps):,.0f}  "
              f"dispatches {[x['dispatches'] for x in rows]}")


if __name__ == "__main__":
    main()
