"""The run's launch records: ``launches.jsonl``, which the program writes
beside its per-node logs when profiling is on (one line per ship-phase
span: ``phase, t0_ns, t1_ns, launch, shard, cause`` and what the phase
noted).  Not a metric's reader: the readers that need the file share it."""

import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def out_dir(obs):
    return os.path.join(BENCH, "out", obs["cell"]["name"])


def spans(obs):
    """The file's lines as dicts, oldest first; None when there is none."""
    path = os.path.join(out_dir(obs), "nodes", "launches.jsonl")
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def in_window(obs, records):
    """Records that began while the generator's window ran (the ring is
    cleared as the measured graph starts, so its first record marks it)."""
    if not records:
        return []
    t0 = min(r["t0_ns"] for r in records)
    t1 = t0 + obs["gen"]["ran_s"] * 1e9
    return [r for r in records if r["t0_ns"] <= t1]
