"""Interleaved sweep of the in-flight dispatch window (the hold threshold
that gates reactive coalescing) and queue depth, judged on the same
per-run launch diagnostics as the bench.

Usage: python scripts/sweep_window.py [n_million] [rounds]
"""

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
import numpy as np

CONFIGS = [
    {"dw": 8, "depth": 48},                        # bench.py default (anchor)
    {"dw": 8, "depth": 48, "flush": 1 << 18},
    {"dw": 16, "depth": 96},
    {"dw": 8, "depth": 48, "no_overlap": True},
]


def main():
    n_m = float(sys.argv[1]) if len(sys.argv) > 1 else 16
    rounds = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    bench.N_TUPLES = int(n_m * 1e6)
    from windflow_tpu.core.tuples import Schema
    schema = Schema(value=np.int64)
    batches = bench.make_stream(schema)
    want = bench.expected_total(batches)

    bench.run_once(batches, schema)
    from windflow_tpu.ops.resident import prewarm_regular_ladder
    prewarm_regular_ladder()

    results = {i: [] for i in range(len(CONFIGS))}
    for r in range(rounds):
        for i, cfg in enumerate(CONFIGS):
            os.environ["WF_DISPATCH_WINDOW"] = str(cfg["dw"])
            if cfg.get("no_overlap"):
                os.environ["WF_NO_OVERLAP"] = "1"
            else:
                os.environ.pop("WF_NO_OVERLAP", None)
            dt, _n, total, diag = _run(batches, schema, cfg["depth"],
                                       cfg.get("flush", bench.FLUSH_ROWS))
            assert total == want, (cfg, total, want)
            row = {"tps": round(bench.N_TUPLES / dt, 1), **diag}
            results[i].append(row)
            print(f"round {r} {cfg}: {json.dumps(row)}", flush=True)
    os.environ.pop("WF_DISPATCH_WINDOW", None)
    os.environ.pop("WF_NO_OVERLAP", None)
    for i, cfg in enumerate(CONFIGS):
        tps = [x["tps"] for x in results[i]]
        print(f"{cfg}: best {max(tps):,.0f} "
              f"median {statistics.median(tps):,.0f} "
              f"dispatches {[x['dispatches'] for x in results[i]]}")


def _run(batches, schema, depth, flush_rows=None):
    import time

    from windflow_tpu.core.windows import WinType
    from windflow_tpu.ops import resident
    from windflow_tpu.ops.functions import Reducer
    from windflow_tpu.patterns.basic import Sink, Source
    from windflow_tpu.patterns.win_seq_tpu import WinSeqTPU
    from windflow_tpu.runtime.engine import Dataflow
    from windflow_tpu.runtime.farm import build_pipeline

    n_out = [0]
    total = [0]

    def consume(rows):
        if rows is not None and len(rows):
            n_out[0] += len(rows)
            total[0] += int(rows["value"].sum())

    stage = WinSeqTPU(Reducer("sum", value_range=(0, 100)), bench.WIN,
                      bench.SLIDE, WinType.CB, batch_len=bench.BATCH_LEN,
                      flush_rows=flush_rows or bench.FLUSH_ROWS,
                      depth=depth, shards=1)
    df = Dataflow()
    build_pipeline(df, [Source(batches=batches, schema=schema),
                        stage, Sink(consume, vectorized=True)])
    resident.stats_snapshot(reset=True)
    t0 = time.perf_counter()
    df.run_and_wait_end()
    dt = time.perf_counter() - t0
    diag = resident.stats_snapshot(reset=True)
    return dt, n_out[0], total[0], diag


if __name__ == "__main__":
    main()
