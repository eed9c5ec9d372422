"""The delivery control of an exactly-once cell, read on the chip.

    chiprun -- python3 benchmarks/tests/delivery_control_on_chip.py \\
        --workload <cell> --seeds 21,22 --seconds 45

The cell's configuration kills a window worker and has it restored; what makes
the restored worker's output exactly-once is that its consumer drops the
prefix of it that it has already seen.  This script shows that the comparison
that decides ``correct`` sees the difference.  For each seed, a process a run,
through ``run.measure`` (the same entry, programs and sizes as a timed run),
with the configuration's kill moved right behind a window's close
(``kill.after_emit``: the worker dies in the call after the one in which the
closed windows' results left, so its replay emits them again):

1. as the library runs it: every number of the comparison has to read 0, and
   the consumers have to have dropped at least one batch;
2. with the drop turned off by this script (a patch of
   ``NodeRecovery.is_replayed``; the library has no such option): a window
   reaches the sink twice, ``duplicates`` reads over 0 and the run is not
   correct.

Every comparison is exact and every limit is 0; the readings only show that 0
separates exactly-once from at-least-once.
"""

import argparse
import importlib
import json
import os
import resource
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def one_pass(a, seed, drop):
    """One run in this process; its row as the last line of its output."""
    import run
    from windflow_tpu.recovery.epoch import NodeRecovery

    resolve = run.resolve

    def behind_a_close(cell_name, rehearsal):
        cell, cfg, *rest = resolve(cell_name, rehearsal)
        cfg["kill"] = dict(cfg["kill"], window_index=a.window_index,
                           offset_us=0, after_emit=True)
        return (cell, cfg, *rest)

    run.resolve = behind_a_close
    if not drop:
        NodeRecovery.is_replayed = lambda self, src, seq: False
    args = run.parse_args(
        ["--workload", a.workload, "--seed", str(seed), "--seconds",
         str(a.seconds), "--trace", "0"])
    rc, result, d = run.measure(args, lambda text: None)
    if rc:
        return rc
    config = importlib.import_module(f"configs.{d['cfg']['name']}")
    fired_at, killed, report = config.fault_report()
    print(json.dumps({
        "seed": seed, "drop": drop, "correct": result["correct"],
        "killed_at_us": fired_at,
        "replayed_batches": killed["replayed_batches"],
        "dedup_dropped_batches": sum(
            c["dedup_dropped_batches"] for c in report.values()),
        "faults": d["oracle"].delivery_faults(d["numbers"]),
        "numbers": d["numbers"],
        "peak_host_rss_mb": resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss // 1024}), flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--window-index", type=int, default=1,
                    help="the first window whose close may arm the kill")
    ap.add_argument("--drop", type=int, choices=(0, 1),
                    help="one pass in this process: with the drop, without")
    a = ap.parse_args()
    seeds = [int(x) for x in a.seeds.split(",")]
    if a.drop is not None:
        return one_pass(a, seeds[0], bool(a.drop))
    # a process a pass: this one never touches the device, and a pass starts
    # with the host's memory to itself (two in one process ran a 40 GiB host
    # out of memory: a pass holds a window of archives, export buffers and
    # journals, and the allocator does not hand all of it back)
    rows = []
    for seed in seeds:
        for drop in (1, 0):
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--workload",
                 a.workload, "--seeds", str(seed), "--seconds",
                 str(a.seconds), "--window-index", str(a.window_index),
                 "--drop", str(drop)], stdout=subprocess.PIPE, text=True)
            if out.returncode:
                print(f"seed {seed} drop {drop}: exit {out.returncode}")
                return out.returncode
            line = out.stdout.strip().splitlines()[-1]
            rows.append(json.loads(line))
            print(line, flush=True)
    ok = all(r["correct"] and r["dedup_dropped_batches"] > 0
             if r["drop"] else
             not r["correct"] and set(r["faults"]) == {"duplicates"}
             for r in rows)
    print(f"{a.workload}: {len(rows)} runs; exactly-once with the drop, "
          f"delivered twice and refused without it: {ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
