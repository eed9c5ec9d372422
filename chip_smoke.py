"""chip_smoke.py — does windflow-tpu start, and answer right, on this TPU?

One process, one chip (or the four chips of one host), plain
``python chip_smoke.py``.  It builds the native runtime from the committed
sources, drives the system's main paths through the entry points a user
would call, checks every result against the host oracle, and asserts per leg
that a TPU executor — not a CPU backend, a host core or an interpreter — did
the work.  Exit code 0 and a last stdout line
``{"ok": true, "device": {...}}`` only if every leg passed on a TPU; without
an accelerator it exits non-zero and prints no result.

Legs (each run twice: cold wall with compiles, then warm):

  A  pipe_test_tpu: Source -> Map -> Filter -> WinFarmTPU(2) -> Sink, 8M tuples
  B  sum_test_tpu at bench.py's constants: WinSeqTPU, 16M tuples
  C  the step families A and B never compile: TB windows, max, a two-field
     MultiReducer, a JaxWindowFunction on the restaging executor
  F  (x1, every host) leg B's stream on a ring placed on a mesh of ONE
     chip: the mesh placement (ops/resident._OnMesh) on the device it is
     written for
  E-G (>= 4 chips) KeyFarmTPU one ring per chip, a mesh-sharded ring,
     __graft_entry__'s mesh dry run

The legs are functions with size parameters; tests/test_chip_smoke.py calls
them tiny on the CPU backend.  ``main()`` itself never runs without a TPU.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import threading
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
WIN, SLIDE, N_KEYS = 256, 64, 64     # the window every leg uses
VAL_RANGE = (0, 100)                 # stream values, proves int32 sums fit


class CompileCounter:
    """Executables JAX compiled, as opposed to fetched from the persistent
    cache: compile requests minus cache hits (jax.monitoring events)."""

    def __init__(self):
        import jax
        self._mu = threading.Lock()   # ship threads compile too
        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            with self._mu:
                self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            with self._mu:
                self.hits += 1

    def compiled(self) -> int:
        with self._mu:
            return self.requests - self.hits


# ------------------------------------------------------------------ helpers

def _run_stage(batches, schema, stage):
    """Source -> stage -> Sink; returns (wall seconds, result rows sorted
    by (key, id), the Dataflow that ran)."""
    from windflow_tpu.patterns.basic import Sink, Source
    from windflow_tpu.runtime.engine import Dataflow
    from windflow_tpu.runtime.farm import build_pipeline

    got = []

    def consume(rows):
        if rows is not None and len(rows):
            got.append(rows.copy())

    df = Dataflow()
    build_pipeline(df, [Source(batches=batches, schema=schema), stage,
                        Sink(consume, vectorized=True)])
    t0 = time.perf_counter()
    df.run_and_wait_end()
    wall = time.perf_counter() - t0
    if not got:
        raise AssertionError("the stage emitted no window result")
    rows = np.concatenate(got)
    return wall, np.sort(rows, order=["key", "id"]), df


def _assert_rows_equal(got, want, what):
    if len(got) != len(want):
        raise AssertionError(
            f"{what}: {len(got)} window results, oracle has {len(want)}")
    for f in want.dtype.names:
        if not np.array_equal(got[f], want[f]):
            bad = int(np.flatnonzero(got[f] != want[f])[0])
            raise AssertionError(
                f"{what}: field {f!r} differs from the oracle at row {bad}: "
                f"{got[f][bad]!r} != {want[f][bad]!r}")


def assert_device_work(cores, core_cls, platform):
    """The assertion that keeps a fallback from hiding: every window core
    is `core_cls` (not a host core, not a Python stand-in), each of its
    executors sits on a `platform` device, dispatched at least once, and
    that device reports memory in use where it reports memory at all.
    Returns the set of devices that did the work."""
    if not cores:
        raise AssertionError("the dataflow holds no window core")
    devices = set()
    for core in cores:
        if type(core) is not core_cls:
            raise AssertionError(
                f"window core is {type(core).__name__}, this leg is for "
                f"{core_cls.__name__}")
        if getattr(core, "_delegate", None) is not None:
            raise AssertionError(
                f"{core_cls.__name__} handed the stream to "
                f"{type(core._delegate).__name__}")
        for ex in getattr(core, "executors", None) or [core.executor]:
            mesh = getattr(ex, "mesh", None)
            owned = list(mesh.devices.flat) if mesh is not None \
                else [ex.device]
            for dev in owned:
                if dev.platform != platform:
                    raise AssertionError(
                        f"executor on {dev} ({dev.platform}), "
                        f"expected {platform}")
                stats = dev.memory_stats()
                if stats and stats.get("peak_bytes_in_use", 1) <= 0:
                    raise AssertionError(f"{dev} reports no memory used")
            sent = getattr(ex, "dispatches", None)
            if sent is None:
                sent = ex.launches
            if sent <= 0:
                raise AssertionError(
                    f"{type(ex).__name__} on {owned[0]} never dispatched")
            devices.update(owned)
    return devices


def _twice(run, counter):
    """Run a leg's body cold then warm; returns its timing/compile facts
    (set-up facts for the benchmark PR, not claims).  The body's own facts
    are kept from the warm run."""
    facts = {}
    for phase in ("cold", "warm"):
        c0 = counter.compiled() if counter else 0
        wall, extra = run()
        facts[f"{phase}_wall_s"] = round(wall, 3)
        facts[f"{phase}_compiled"] = (counter.compiled() - c0
                                      if counter else None)
        facts.update(extra)
    return facts


def _sum_stream(n_tuples, chunk, seed):
    import bench
    from windflow_tpu.core.tuples import Schema
    schema = Schema(value=np.int64)
    return schema, bench.make_stream(schema, n_tuples, chunk, seed)


def _oracle(batches, schema, winfunc, win_type):
    from windflow_tpu.patterns.win_seq import WinSeq
    _wall, rows, _df = _run_stage(
        batches, schema, WinSeq(winfunc, WIN, SLIDE, win_type))
    return rows


def _stage_leg(name, batches, schema, make_stage, oracle_rows, core_cls,
               platform, counter, resident=True, extra_check=None):
    """Shared body of the single-stage legs: run `make_stage()` over the
    stream twice, compare with the oracle rows, assert the device did it."""
    from windflow_tpu.ops import resident as res
    from windflow_tpu.patterns.win_seq import window_cores

    def run():
        res.stats_snapshot(reset=True)
        wall, rows, df = _run_stage(batches, schema, make_stage())
        _assert_rows_equal(rows, oracle_rows, name)
        cores = window_cores(df)
        devices = assert_device_work(cores, core_cls, platform)
        extra = {"windows": len(rows), "core": core_cls.__name__,
                 "devices": sorted(str(d) for d in devices)}
        if resident:
            snap = res.stats_snapshot(reset=True)
            if snap["dispatches"] <= 0:
                raise AssertionError(
                    f"{name}: no resident dispatch recorded")
            extra.update(dispatches=snap["dispatches"],
                         mean_launch_ms=snap["mean_launch_ms"])
        else:
            extra["launches"] = sum(c.executor.launches for c in cores)
        if extra_check is not None:
            extra.update(extra_check(cores))
        return wall, extra

    return {"leg": name, **_twice(run, counter)}


# --------------------------------------------------------------------- legs

def leg_pipe(n_tuples=8_000_000, platform="tpu", counter=None, seed=7,
             chunk=1 << 20, flush_rows=1 << 19, pardegree=2, key_farm=False):
    """Leg A (and E with ``key_farm``): the MultiPipe apps/pipe.py
    assembles — Source -> Map(v*3+1) -> Filter(v%5!=0) -> WinFarmTPU CB
    256/64, 64 keys -> Sink — sum and window count against
    apps/pipe.py:expected.  State is small by nature here (64 keys x 256
    rows); the size that is real is the stream.  A Key_Farm downstream of
    a Filter counts RAW tuple ids where a Win_Farm counts surviving tuples
    (api/multipipe.py:_maybe_order, as in the reference), so leg E's
    oracle is the same MultiPipe on the host KeyFarm."""
    from windflow_tpu.apps import pipe as app
    from windflow_tpu.ops import resident as res
    from windflow_tpu.patterns.key_farm import KeyFarm
    from windflow_tpu.patterns.native_core import NativeResidentCore
    from windflow_tpu.patterns.win_seq import window_cores
    from windflow_tpu.patterns.win_seq_tpu import KeyFarmTPU, WinFarmTPU

    chunks = app.make_values(n_tuples, chunk, seed)
    if key_farm:
        host, state = app.build_pipe(
            chunks, 1, flush_rows, depth=48, capacity=4,
            farm=lambda red, win, slide, wt, pardegree, **_dev: KeyFarm(
                red, win, slide, wt, pardegree=pardegree))
        host.run_and_wait_end()
        want_total, want_windows = state["total"], state["rcv"]
    else:
        want_total, want_windows = app.expected(chunks)

    def run():
        pipe, state = app.build_pipe(
            chunks, pardegree, flush_rows, depth=48, capacity=4,
            farm=KeyFarmTPU if key_farm else WinFarmTPU)
        res.stats_snapshot(reset=True)
        t0 = time.perf_counter()
        pipe.run_and_wait_end()
        wall = time.perf_counter() - t0
        if (state["total"], state["rcv"]) != (want_total, want_windows):
            raise AssertionError(
                f"pipe: sum {state['total']} / {state['rcv']} windows, "
                f"oracle {want_total} / {want_windows}")
        cores = window_cores(pipe._df)
        if len(cores) != pardegree:
            raise AssertionError(
                f"pipe: {len(cores)} window cores for pardegree {pardegree}")
        devices = assert_device_work(cores, NativeResidentCore, platform)
        if key_farm and len(devices) != pardegree:
            raise AssertionError(
                f"KeyFarmTPU(pardegree={pardegree}) put its rings on "
                f"{len(devices)} device(s): {sorted(map(str, devices))}")
        snap = res.stats_snapshot(reset=True)
        if snap["dispatches"] <= 0:
            raise AssertionError("pipe: no resident dispatch recorded")
        return wall, {
            "tuples": n_tuples, "windows": want_windows,
            "core": "NativeResidentCore",
            "devices": sorted(str(d) for d in devices),
            "dispatches_per_ring": [c.executor.dispatches for c in cores],
            "mean_launch_ms": snap["mean_launch_ms"]}

    name = f"E key_farm x{pardegree}" if key_farm else "A pipe"
    return {"leg": name, **_twice(run, counter)}


def leg_sum(n_tuples=None, platform="tpu", counter=None, seed=7, chunk=None,
            batch_len=None, flush_rows=None, mesh=None):
    """Leg B (and F with ``mesh``): sum_test_tpu at bench.py's constants
    through WinSeqTPU, total against bench.py:expected_total and every
    window against the host WinSeq."""
    import bench
    from windflow_tpu.core.windows import WinType
    from windflow_tpu.ops.functions import Reducer
    from windflow_tpu.patterns.native_core import NativeResidentCore
    from windflow_tpu.patterns.win_seq_tpu import WinSeqTPU

    schema, batches = _sum_stream(n_tuples or bench.N_TUPLES,
                                  chunk or bench.CHUNK, seed)
    want_total = bench.expected_total(batches)
    oracle = _oracle(batches, schema, Reducer("sum"), WinType.CB)
    if int(oracle["value"].sum()) != want_total:
        raise AssertionError("host WinSeq disagrees with expected_total")

    def make_stage():
        return WinSeqTPU(Reducer("sum", value_range=VAL_RANGE), WIN, SLIDE,
                         WinType.CB, batch_len=batch_len or bench.BATCH_LEN,
                         flush_rows=flush_rows or bench.FLUSH_ROWS,
                         depth=48, shards=1, mesh=mesh)

    def ring_spread(cores):
        if mesh is None:
            return {}
        ring = cores[0].executor._ring
        spread = len(ring.sharding.device_set)
        if spread != mesh.devices.size:
            raise AssertionError(
                f"mesh ring spans {spread} device(s), mesh has "
                f"{mesh.devices.size}")
        return {"ring_devices": spread, "ring_spec": str(ring.sharding.spec)}

    name = "B sum" if mesh is None else f"F mesh ring x{mesh.devices.size}"
    return _stage_leg(name, batches, schema, make_stage, oracle,
                      NativeResidentCore, platform, counter,
                      extra_check=ring_spread)


def _two_field_stream(n_tuples, chunk, seed):
    from windflow_tpu.core.tuples import Schema, batch_from_columns
    schema = Schema(a=np.int64, b=np.int64)
    rng = np.random.default_rng(seed)
    per_key = n_tuples // N_KEYS
    rows = max(chunk // N_KEYS, 1)
    batches = []
    for lo in range(0, per_key, rows):
        m = min(rows, per_key - lo)
        ids = np.repeat(np.arange(lo, lo + m), N_KEYS)
        batches.append(batch_from_columns(
            schema, key=np.tile(np.arange(N_KEYS), m), id=ids, ts=ids,
            a=rng.integers(*VAL_RANGE, size=m * N_KEYS),
            b=rng.integers(-50, 50, size=m * N_KEYS)))
    return schema, batches


def legs_families(n_tuples=4_000_000, platform="tpu", counter=None, seed=11,
                  chunk=1 << 20, batch_len=1 << 13, flush_rows=1 << 18):
    """Leg C: the step families legs A and B do not compile, each against
    the host WinSeq: TB windows (irregular descriptors, `_make_step`), a
    max reducer (the (B, pad) gather of `_ring_eval`), a two-field
    MultiReducer (`_make_multi_step`), and a JaxWindowFunction on the
    restaging DeviceWindowExecutor.  Returns one callable per sub-leg, so
    a caller can report each on its own."""
    import jax.numpy as jnp

    from windflow_tpu.core.windows import WinType
    from windflow_tpu.ops.functions import (FnWindowFunction, MultiReducer,
                                            Reducer)
    from windflow_tpu.patterns.native_core import NativeResidentCore
    from windflow_tpu.patterns.win_seq_tpu import (DeviceWinSeqCore,
                                                   JaxWindowFunction,
                                                   WinSeqTPU)

    schema, batches = _sum_stream(n_tuples, chunk, seed)
    kw = dict(batch_len=batch_len, flush_rows=flush_rows)

    # ts == id in this stream, so TB 256/64 fires the same row sets as CB
    def tb_sum():
        return _stage_leg(
            "C1 TB sum", batches, schema,
            lambda: WinSeqTPU(Reducer("sum", value_range=VAL_RANGE), WIN,
                              SLIDE, WinType.TB, **kw),
            _oracle(batches, schema, Reducer("sum"), WinType.TB),
            NativeResidentCore, platform, counter)

    def cb_max():
        return _stage_leg(
            "C2 CB max", batches, schema,
            lambda: WinSeqTPU(Reducer("max", value_range=VAL_RANGE), WIN,
                              SLIDE, WinType.CB, **kw),
            _oracle(batches, schema, Reducer("max"), WinType.CB),
            NativeResidentCore, platform, counter)

    def multi(ranged):
        return MultiReducer(
            Reducer("sum", "a", "sa",
                    value_range=VAL_RANGE if ranged else None),
            Reducer("max", "b", "mb",
                    value_range=(-50, 50) if ranged else None))

    def two_rings(cores):
        n = len(cores[0].executor.fields)
        if n != 2:
            raise AssertionError(f"multi-field executor holds {n} ring(s)")
        return {"rings": n}

    def two_field():
        schema2, batches2 = _two_field_stream(n_tuples, chunk, seed)
        return _stage_leg(
            "C3 two-field multi", batches2, schema2,
            lambda: WinSeqTPU(multi(True), WIN, SLIDE, WinType.CB, **kw),
            _oracle(batches2, schema2, multi(False), WinType.CB),
            NativeResidentCore, platform, counter, extra_check=two_rings)

    # an arbitrary batched JAX function: sum of squares, exact in int32
    # (99^2 * 256 < 2^31)
    def sumsq(keys, gwids, cols, mask):
        v = cols["value"]
        return jnp.sum(jnp.where(mask, v * v, 0), axis=1)

    def host_sumsq(key, gwid, rows):
        return int(np.sum(rows["value"].astype(np.int64) ** 2))

    def jax_fn():
        return _stage_leg(
            "C4 jax fn restaging", batches, schema,
            lambda: WinSeqTPU(
                JaxWindowFunction(sumsq, fields=("value",),
                                  result_fields={"value": np.int64}),
                WIN, SLIDE, WinType.CB, batch_len=batch_len),
            _oracle(batches, schema,
                    FnWindowFunction(host_sumsq, {"value": np.int64}),
                    WinType.CB),
            DeviceWinSeqCore, platform, counter, resident=False)

    return [tb_sum, cb_max, two_field, jax_fn]


def legs_multichip(n_chips, platform="tpu", counter=None, pipe_tuples=None,
                   sum_tuples=None, **size):
    """Legs E-G, one process driving `n_chips` devices: leg A's pipeline on
    KeyFarmTPU with one ring per chip, leg B's stream on one ring sharded
    P('kf', None) over a mesh, and __graft_entry__'s (kf, wf, sp) dry run
    with its psum over sp.  Returns one callable per leg."""
    import jax

    import __graft_entry__ as graft
    from windflow_tpu.parallel.mesh import make_mesh

    def graft_dryrun():
        def run():
            t0 = time.perf_counter()
            fn, args = graft.entry()
            jax.block_until_ready(jax.jit(fn)(*args))
            graft.dryrun_multichip(n_chips)
            return time.perf_counter() - t0, {"devices": n_chips}

        return {"leg": f"G graft dryrun x{n_chips}", **_twice(run, counter)}

    return [
        functools.partial(
            leg_pipe, **({"n_tuples": pipe_tuples} if pipe_tuples else {}),
            platform=platform, counter=counter, pardegree=n_chips,
            key_farm=True, **size),
        lambda: leg_sum(n_tuples=sum_tuples, platform=platform,
                        counter=counter, mesh=make_mesh(n_chips, 1), **size),
        graft_dryrun]


# --------------------------------------------------------------------- main

def rebuild_native():
    """Build native/libwfnative.so from the committed sources only: a .so
    or host.tag that travelled with the tree (both git-ignored; built with
    -march=native elsewhere) is removed first.  A failure is fatal — the
    legs are never run on the Python cores to get a green smoke."""
    from windflow_tpu import native
    for stale in ("libwfnative.so", "host.tag"):
        path = os.path.join(HERE, "native", stale)
        if os.path.exists(path):
            os.remove(path)
    t0 = time.perf_counter()
    # runs make; no make, no g++ or a failed compile raise NativeBuildError
    # with the tool's own message
    if native.load() is None:
        raise RuntimeError("native/wf_native.cpp is missing from the tree")
    return round(time.perf_counter() - t0, 2)


def main():
    import jax
    devs = jax.devices()            # the backend starts here
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{devs[0].platform!r} ({devs[0].device_kind}, {len(devs)} "
              "device(s))", file=sys.stderr)
        return 2
    try:
        from windflow_tpu.ops.backend import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the windflow_tpu checkout is not beside this "
              f"file ({e})", file=sys.stderr)
        return 2
    import importlib.metadata as md

    import jaxlib
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    print(json.dumps({
        "device": device, "jax": jax.__version__,
        "jaxlib": jaxlib.__version__, "libtpu": md.version("libtpu"),
        "host_cpus": os.cpu_count(), "compile_cache": cache_dir,
        "cache_dir_from_env": bool(os.environ.get(
            "JAX_COMPILATION_CACHE_DIR"))}), flush=True)
    print(json.dumps({"native_build_s": rebuild_native()}), flush=True)

    common = dict(platform="tpu", counter=counter)
    from windflow_tpu.parallel.mesh import make_mesh
    plan = [lambda: leg_pipe(**common), lambda: leg_sum(**common),
            *legs_families(**common),
            lambda: leg_sum(mesh=make_mesh(1, 1), **common)]
    if len(devs) >= 4:
        plan += legs_multichip(4, **common)
    failed = []
    for leg in plan:
        try:
            print(json.dumps({"ok": True, **leg()}), flush=True)
        except Exception as e:   # boundary: report the leg, run the rest
            traceback.print_exc()
            failed.append(f"{type(e).__name__}: {e}"[:500])
            print(json.dumps({"ok": False, "error": failed[-1]}),
                  flush=True)
    if len(devs) < 4:
        print(json.dumps({"multichip": f"not run: {len(devs)} device(s), "
                          "legs E-G need 4"}), flush=True)
    print(json.dumps({"compiled_total": counter.compiled(),
                      "cache_hits": counter.hits}), flush=True)
    if failed:
        print(json.dumps({"ok": False, "device": device, "failed": failed}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
