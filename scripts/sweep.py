"""Sweep bench configs on the real chip (shards / flush_rows / depth),
interleaved round-robin so drift over the session averages out across
configs.

Usage: python scripts/sweep.py [n_million] [rounds]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
import numpy as np


def main():
    n_m = float(sys.argv[1]) if len(sys.argv) > 1 else 8
    rounds = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    bench.N_TUPLES = int(n_m * 1e6)
    from windflow_tpu.core.tuples import Schema
    schema = Schema(value=np.int64)
    batches = bench.make_stream(schema)

    configs = []
    for shards in (1, 2, 4):
        for flush in (1 << 19, 1 << 20):
            configs.append(dict(shards=shards, flush=flush, depth=24))

    best = {i: None for i in range(len(configs))}
    for r in range(rounds):
        for i, cfg in enumerate(configs):
            bench.FLUSH_ROWS = cfg["flush"]
            orig = bench.run_once

            def run_with(cfg=cfg):
                from windflow_tpu.core.windows import WinType
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

                df = Dataflow()
                build_pipeline(df, [
                    Source(batches=batches, schema=schema),
                    WinSeqTPU(Reducer("sum"), bench.WIN, bench.SLIDE,
                              batch_len=bench.BATCH_LEN,
                              flush_rows=cfg["flush"], depth=cfg["depth"],
                              shards=cfg["shards"]),
                    Sink(consume, vectorized=True)])
                t0 = time.perf_counter()
                df.run_and_wait_end()
                return time.perf_counter() - t0

            dt = run_with()
            tps = bench.N_TUPLES / dt
            if best[i] is None or tps > best[i]:
                best[i] = tps
            print(f"round {r} cfg{i} shards={cfg['shards']} "
                  f"flush=2^{cfg['flush'].bit_length()-1} "
                  f"depth={cfg['depth']}: {tps/1e6:.2f}M tps", flush=True)
    print("\nbest-of per config:")
    for i, cfg in enumerate(configs):
        print(f"  shards={cfg['shards']} flush=2^{cfg['flush'].bit_length()-1}"
              f" depth={cfg['depth']}: {best[i]/1e6:.2f}M tps")


if __name__ == "__main__":
    main()
