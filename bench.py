"""Headline benchmark — windowed-sum throughput on the device path.

The TPU equivalent of the reference's ``src/sum_test_gpu`` workload
(win_seq_gpu.hpp:309-530: count-based sliding-window sum, micro-batched onto
the device): a deterministic multi-key integer stream is pushed through
``WinSeqTPU`` (archive staging -> batched XLA window evaluation -> async
launches), and we report end-to-end *input tuples per second* including all
host bookkeeping, exactly the metric the reference's self-timing tests print
(`sum_cb.hpp` totalsum runs / `test_ysb_kf.cpp:113`).

One warmup run, then five timed runs.  Prints ONE JSON line: {"metric",
"value", "unit", "vs_baseline", "device", ...}.  It runs on a TPU or not at
all: no chip, a native runtime that does not build, a wrong total or a
failed control each end the run with a non-zero exit and no figure.

The reference publishes no numbers; ``BASELINE_TUPLES_PER_SEC`` is the
V100-class bar from BASELINE.json's north star.  It is an engineering
estimate, load-bearing only as a fixed yardstick: the reference's GPU path
is *host-throughput-bound*, not kernel-bound — every tuple is processed one
at a time by Win_Seq_GPU::svc on the CPU (win_seq_gpu.hpp:309-530: per-tuple
extract, key map lookup, triggerer arithmetic), and the CUDA work is a
trivial sum kernel behind a per-batch BLOCKING cudaStreamSynchronize (:481).
A per-tuple C++ hot loop of that shape sustains tens of ns/tuple on one
core, i.e. ~15-30M tuples/s per worker; 20M is the midpoint.  The number is
a STABLE denominator, not a measured V100 datum (the benchmark-definition
PR replaces it, ROADMAP Queue 3 item 7).
"""

import json
import os
import statistics
import sys
import time

import numpy as np

BASELINE_TUPLES_PER_SEC = 20e6

# workload shape: CB sliding windows, the sum_test_gpu default regime
N_KEYS = 64
N_TUPLES = 16_000_000         # total stream length across keys
WIN, SLIDE = 256, 64
BATCH_LEN = 1 << 15           # fired-window flush trigger (row trigger first)
FLUSH_ROWS = 1 << 19          # rows per fused device dispatch
CHUNK = 1 << 20               # stream batch (rows per engine message)
N_RUNS = 5                    # timed runs after the warmup


def make_stream(schema, n_tuples=None, chunk=None, seed=7):
    """Deterministic per-key-ordered integer stream (sum_cb.hpp:89-117);
    sized by the module's N_TUPLES / CHUNK unless told otherwise."""
    from windflow_tpu.core.tuples import batch_from_columns
    per_key = (n_tuples or N_TUPLES) // N_KEYS
    rows = max((chunk or CHUNK) // N_KEYS, 1)
    batches = []
    rng = np.random.default_rng(seed)
    for lo in range(0, per_key, rows):
        m = min(rows, per_key - lo)
        ids = np.repeat(np.arange(lo, lo + m), N_KEYS)
        keys = np.tile(np.arange(N_KEYS), m)
        vals = rng.integers(0, 100, size=m * N_KEYS).astype(np.int64)
        batches.append(batch_from_columns(
            schema, key=keys, id=ids, ts=ids, value=vals))
    return batches


def run_once(batches, schema, host_core=False):
    from windflow_tpu.core.windows import WinType
    from windflow_tpu.ops import resident
    from windflow_tpu.ops.functions import Reducer
    from windflow_tpu.patterns.basic import Sink, Source
    from windflow_tpu.patterns.win_seq import WinSeq
    from windflow_tpu.patterns.win_seq_tpu import WinSeqTPU
    from windflow_tpu.runtime.engine import Dataflow
    from windflow_tpu.runtime.farm import build_pipeline

    n_out = [0]
    total = [0]

    def consume(rows):
        if rows is not None and len(rows):
            n_out[0] += len(rows)
            total[0] += int(rows["value"].sum())

    if host_core:
        # control: the identical workload on the host window core — the
        # framework's floor with no device launch in the path
        stage = WinSeq(Reducer("sum"), WIN, SLIDE, WinType.CB)
    else:
        # shards=1 and depth=48 were chosen on a one-core host; whether
        # they suit this machine is for the benchmark PR to measure
        # (ROADMAP Queue 1 item 3), scripts/sweep*.py are the sweeps
        stage = WinSeqTPU(Reducer("sum", value_range=(0, 100)), WIN, SLIDE,
                          WinType.CB, batch_len=BATCH_LEN,
                          flush_rows=FLUSH_ROWS, depth=48, shards=1)
    df = Dataflow()
    build_pipeline(df, [
        Source(batches=batches, schema=schema),
        stage,
        Sink(consume, vectorized=True)])
    resident.stats_snapshot(reset=True)
    t0 = time.perf_counter()
    df.run_and_wait_end()
    dt = time.perf_counter() - t0
    # per-run launch diagnostics: dispatches, merges, mean launch service
    diag = resident.stats_snapshot(reset=True)
    return dt, n_out[0], total[0], diag


def expected_total(batches) -> int:
    """Host oracle: sum of all complete-window sums, via per-key cumsum."""
    vals = np.concatenate([b["value"] for b in batches])
    keys = np.concatenate([b["key"] for b in batches])
    total = 0
    for k in range(N_KEYS):
        v = vals[keys == k]
        if not len(v):
            continue
        c = np.concatenate([[0], np.cumsum(v)])
        # every *opened* window fires: complete ones on the fly, partial
        # trailing ones at EOS (win_seq.hpp:433-474 flush semantics)
        n_wins = (len(v) - 1) // SLIDE + 1
        starts = np.arange(n_wins) * SLIDE
        total += int(np.sum(c[np.minimum(starts + WIN, len(v))] - c[starts]))
    return total


def host_loop_tps(lib, batches) -> float:
    """The C++ bookkeeping + launch staging ALONE (queue never shipped) on
    the same stream: the device path's host-side ceiling on this machine.
    A device figure that approaches it is host-bound."""
    import ctypes
    b0 = batches[0]
    f = b0.dtype.fields
    offs = (b0.dtype.itemsize, f["key"][1], f["id"][1], f["ts"][1],
            f["marker"][1], f["value"][1])
    h = lib.wf_core_new(WIN, SLIDE, 0, 0, 0, 1, SLIDE, 0, 1, SLIDE, 0, 1,
                        SLIDE, BATCH_LEN, FLUSH_ROWS, 3)
    p32 = ctypes.POINTER(ctypes.c_int32)
    p64 = ctypes.POINTER(ctypes.c_longlong)

    def drain():
        # pop + discard staged launches each chunk: the take/fill cost is
        # part of the device path's host side, and the queue never
        # accumulates the whole stream's staged blocks
        K = ctypes.c_longlong()
        R = ctypes.c_longlong()
        B = ctypes.c_longlong()
        KP = ctypes.c_longlong()
        cap = ctypes.c_longlong()
        wire = ctypes.c_int()
        rebase = ctypes.c_int()
        while lib.wf_launch_peek(
                h, ctypes.byref(K), ctypes.byref(R), ctypes.byref(B),
                ctypes.byref(wire), ctypes.byref(rebase), ctypes.byref(KP),
                ctypes.byref(cap)):
            Bn = max(B.value, 1)
            blk = np.empty(
                (KP.value, max(R.value, 1)),
                dtype=(np.int8, np.int16, np.int32, np.int64)[wire.value])
            o8 = np.empty(K.value, dtype=np.int64)
            w32 = np.empty(Bn, dtype=np.int32)
            s32 = np.empty(Bn, dtype=np.int32)
            l32 = np.empty(Bn, dtype=np.int32)
            h64 = np.empty(Bn, dtype=np.int64)
            lib.wf_launch_take_padded(
                h, blk.ctypes.data_as(ctypes.c_void_p), KP.value,
                blk.shape[1], o8.ctypes.data_as(p64),
                w32.ctypes.data_as(p32), s32.ctypes.data_as(p32),
                l32.ctypes.data_as(p32), h64.ctypes.data_as(p64),
                h64.ctypes.data_as(p64), h64.ctypes.data_as(p64),
                h64.ctypes.data_as(p64), None, None)

    try:
        t0 = time.perf_counter()
        for b in batches:
            lib.wf_core_process(h, b.ctypes.data, len(b), *offs)
            drain()
        return N_TUPLES / (time.perf_counter() - t0)
    finally:
        lib.wf_core_free(h)


def main():
    import jax
    from windflow_tpu import native
    from windflow_tpu.core.tuples import Schema
    from windflow_tpu.ops.backend import (device_info, enable_compile_cache,
                                          require_tpu)

    cache_dir = enable_compile_cache()
    require_tpu()
    lib = native.load()      # a build or bind failure raises here
    if lib is None:
        raise RuntimeError("native/wf_native.cpp is missing: bench.py "
                           "measures the native resident core")
    schema = Schema(value=np.int64)
    batches = make_stream(schema)
    want = expected_total(batches)

    def checked(**kw):
        dt, n_windows, total, diag = run_once(batches, schema, **kw)
        if total != want:
            raise AssertionError(
                f"windowed-sum total {total} != oracle {want} ({kw})")
        return dt, n_windows, diag

    # full warmup run: compiles every (pad, N) bucket the timed runs hit
    # (executables are cached process-wide across pattern instances) ...
    t0 = time.perf_counter()
    checked()
    # ... then the deep-coalescing shape ladder: merged {2x..16x} dispatch
    # buckets only occur when launches queue up, exactly when a cold
    # mid-run compile would wreck the run that needs the merge
    from windflow_tpu.ops.resident import prewarm_regular_ladder
    ladder = prewarm_regular_ladder()
    warmup_s = time.perf_counter() - t0

    runs = []
    n_windows = 0
    for _ in range(N_RUNS):
        dt, n_windows, diag = checked()
        runs.append({"tps": round(N_TUPLES / dt, 1), **diag})
    best = max(r["tps"] for r in runs)
    # controls, same stream, same oracle: the host window core, and the
    # C++ bookkeeping + staging loop alone
    hdt, _n, _d = checked(host_core=True)
    print(json.dumps({
        "metric": "sum_test_tpu CB windowed-sum input tuples/sec "
                  f"(win={WIN} slide={SLIDE} keys={N_KEYS} "
                  f"flush_rows={FLUSH_ROWS}, {n_windows} windows)",
        "value": best,
        "unit": "tuples/sec",
        "vs_baseline": round(best / BASELINE_TUPLES_PER_SEC, 3),
        "device": device_info(),
        "median_tps": round(statistics.median(r["tps"] for r in runs), 1),
        "host_core_tps": round(N_TUPLES / hdt, 1),
        "host_loop_tps": round(host_loop_tps(lib, batches), 1),
        "n_runs": len(runs),
        "sampling": f"1 warmup + {N_RUNS} timed runs; value is the best, "
                    "median_tps the median",
        "host_cpus": os.cpu_count(),
        "jax": jax.__version__,
        "compile_cache": cache_dir,
        "warmup_s": round(warmup_s, 2),
        "ladder_steps_compiled": ladder,
        "runs": runs,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
