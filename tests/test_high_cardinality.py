"""High-key-cardinality hardening: the emitter /
accumulator / keyed-state hot paths must scale to 1e5 distinct keys —
vectorised group-by instead of a full-batch mask per key.  Budgeted: each
scenario must finish in seconds, and results stay differentially correct
against low-cardinality semantics."""

import time

import numpy as np
import pytest

from windflow_tpu.core.tuples import Schema, batch_from_columns
from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.patterns.basic import Accumulator, Sink, Source
from windflow_tpu.patterns.key_farm import KeyFarm
from windflow_tpu.patterns.win_mapreduce import WinMapReduce
from windflow_tpu.runtime.emitters import KeyedStreamState
from windflow_tpu.runtime.engine import Dataflow
from windflow_tpu.runtime.farm import build_pipeline

SCHEMA = Schema(value=np.int64)
N_KEYS = 100_000
ROWS_PER_KEY = 6


def wide_stream(chunk_rows=200_000):
    """ROWS_PER_KEY in-order rows for each of N_KEYS keys, interleaved."""
    out = []
    for i in range(ROWS_PER_KEY):
        ids = np.full(N_KEYS, i)
        keys = np.arange(N_KEYS)
        for lo in range(0, N_KEYS, chunk_rows):
            sl = slice(lo, lo + chunk_rows)
            out.append(batch_from_columns(
                SCHEMA, key=keys[sl], id=ids[sl], ts=ids[sl],
                value=ids[sl] + keys[sl] % 7))
    return out


def run_counted(patterns):
    got = {"rows": 0, "total": 0}

    def snk(rows):
        if rows is not None and len(rows):
            got["rows"] += len(rows)
            got["total"] += int(rows["value"].sum())

    df = Dataflow()
    build_pipeline(df, [Source(batches=iter(wide_stream()), schema=SCHEMA),
                        *patterns, Sink(snk, vectorized=True)])
    t0 = time.perf_counter()
    df.run_and_wait_end()
    return got, time.perf_counter() - t0


def test_keyed_stream_state_slow_path_scales():
    """Force the out-of-order slow path with 1e5 keys; must be O(n + K)."""
    st = KeyedStreamState("id")
    keys = np.tile(np.arange(N_KEYS // 10), 4)
    # per key, arrival order of ids is 1,0,2,3 -> the 0 must drop
    ids = np.repeat(np.array([1, 0, 2, 3]), len(keys) // 4)
    b = batch_from_columns(SCHEMA, key=keys, id=ids, ts=ids, value=ids)
    t0 = time.perf_counter()
    out = st.filter(b)
    dt = time.perf_counter() - t0
    assert dt < 5.0, f"slow path took {dt:.1f}s"
    # per key the id-0 row follows id-1 and must drop
    assert len(out) == len(b) - N_KEYS // 10


def test_wmr_high_cardinality_differential():
    """Win_MapReduce at 1e5 keys: totals equal KeyFarm's on the same
    stream, in seconds (the WinMap emitter's round-robin is the per-key
    loop that used to collapse)."""
    win = ROWS_PER_KEY
    kf, dt_kf = run_counted([KeyFarm(Reducer("sum"), win, win, WinType.CB,
                                     pardegree=2)])
    wmr, dt_wmr = run_counted([WinMapReduce(Reducer("sum"), Reducer("sum"),
                                            win, win, WinType.CB,
                                            map_degree=2)])
    assert wmr["total"] == kf["total"]
    # vectorised cores + collector run this in ~2.5s; 20s leaves headroom
    # for slow CI hosts while still catching a per-key-loop regression
    assert dt_wmr < 20, f"wmr took {dt_wmr:.1f}s at {N_KEYS} keys"
    assert dt_kf < 20, f"kf took {dt_kf:.1f}s at {N_KEYS} keys"


def test_accumulator_high_cardinality():
    """Vectorised accumulator fold at 1e5 keys in seconds, equal to the
    per-row flavour's totals."""
    out_schema = Schema(total=np.int64)

    def fold_row(row, acc):
        acc["total"] += row["value"]

    def fold_vec(rows, acc):
        # per-row running snapshots of this key's fold
        run = int(acc["total"]) + np.cumsum(rows["value"])
        acc["total"] = run[-1]
        out = np.zeros(len(rows), dtype=out_schema.dtype())
        out["total"] = run
        return out

    small = wide_stream()[:2]   # row flavour is O(rows) python calls

    def run_acc(fn, vectorized, batches):
        got = []
        df = Dataflow()
        build_pipeline(df, [
            Source(batches=iter(batches), schema=SCHEMA),
            Accumulator(fn, out_schema, vectorized=vectorized),
            Sink(lambda r: got.append(int(r["total"].sum()))
                 if r is not None and len(r) else None, vectorized=True)])
        t0 = time.perf_counter()
        df.run_and_wait_end()
        return sum(got), time.perf_counter() - t0

    a, _ = run_acc(fold_row, False, small)
    b, _ = run_acc(fold_vec, True, small)
    assert a == b
    full, dt = run_acc(fold_vec, True, wide_stream())
    assert full > 0
    assert dt < 30, f"vectorised accumulator took {dt:.1f}s"
