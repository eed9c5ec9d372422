"""Interleaved YSB A/B: host kf vs device kf-tpu (and optionally wmr vs
wmr-tpu) alternating in ONE process so drift over the session averages
across arms — judged on MEDIAN as well as best.

Usage: python scripts/ab_ysb.py [rounds] [duration_sec] [pardegree2]
       [variant_pair: kf|wmr]
"""

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from windflow_tpu.apps.ysb import run, warmup


def main():
    rounds = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    dur = float(sys.argv[2]) if len(sys.argv) > 2 else 4.0
    par = int(sys.argv[3]) if len(sys.argv) > 3 else 4
    pair = sys.argv[4] if len(sys.argv) > 4 else "kf"
    host_v, dev_v = (pair, pair + "-tpu")

    warmup(dev_v, 1, par, 10.0, 262144)
    arms = {host_v: [], dev_v: []}
    for r in range(rounds):
        for v in (dev_v, host_v):
            out = run(v, duration_sec=dur, pardegree1=1, pardegree2=par,
                      warm=False)
            arms[v].append(out)
            print(f"round {r} {v}: {json.dumps(out)}", flush=True)
    for v, rows in arms.items():
        eps = [x["events_per_sec"] for x in rows]
        gen = [x.get("gen_events_per_sec", 0) for x in rows]
        lat = [x["avg_latency_us"] / 1e3 for x in rows]
        print(f"{v:8s}: best {max(eps):,.0f}  median "
              f"{statistics.median(eps):,.0f} ev/s   "
              f"median ingest {statistics.median(gen):,.0f} ev/s   "
              f"median avg-latency {statistics.median(lat):,.0f} ms")


if __name__ == "__main__":
    main()
