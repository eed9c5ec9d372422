"""The ``spatial_wf`` deployment on the CPU at a small size
(benchmarks/configs/spatial_wf.*): the configuration's own ``build`` -- a
user's window function (the all-pairs skyline) staged to the device through
``WinFarmTPU`` on the Python resident core -- against the plain reference,
exact; the float16 control that has to read wrong; the step cache a
function's pipelines share (``ops/resident._FN_STEP_CACHE``); launches of
one, two and four windows; the counters the function-bound launch keeps; and
the ladder a function-bound launch pads its windows to
(``ops/device._bucket_fine``), beside the power of two a built-in stat keeps.
"""

import gc
import json
import os
import sys
import weakref

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from configs import spatial_wf, spatial_wf_oracle  # noqa: E402
from harness import check  # noqa: E402

from windflow_tpu.api import MultiPipe  # noqa: E402
from windflow_tpu.core.windows import WinType  # noqa: E402
from windflow_tpu.ops import resident  # noqa: E402
from windflow_tpu.ops.device import _bucket, _bucket_fine  # noqa: E402
from windflow_tpu.patterns.basic import Sink, Source  # noqa: E402
from windflow_tpu.patterns.win_seq import window_cores  # noqa: E402
from windflow_tpu.patterns.win_seq_tpu import (  # noqa: E402
    JaxWindowFunction, WinFarmTPU, plan_core)
from windflow_tpu.utils import profile  # noqa: E402

CHUNK, RATE, N_CHUNKS = 64, 100_000, 60    # 3,840 points, 200 a window


@pytest.fixture(autouse=True)
def _profile_state():
    profile.disable()
    profile.reset()
    yield
    profile.auto()
    profile.reset()


def _cfg(**shapes):
    with open(os.path.join(BENCH, "configs", "spatial_wf.json")) as f:
        cfg = json.load(f)
    cfg["shapes"].update(win_us=2_000, slide_us=500, flush_rows=4096)
    cfg["shapes"].update(shapes)
    cfg["stream"]["template_events"] = 4096
    return cfg


def _log(rate=RATE):
    """The open loop's schedule: event i is due at i / rate."""
    return {"chunk": CHUNK,
            "off_us": (np.arange(CHUNK, dtype=np.int64) * 1_000_000) // rate,
            "base_us": [(j * CHUNK * 1_000_000) // rate
                        for j in range(N_CHUNKS)]}


def _source(cfg, seed, log):
    period = spatial_wf_oracle.period_events(cfg)

    def generate(shipper):
        for j, base in enumerate(log["base_us"]):
            start = j * CHUNK
            batch = np.zeros(CHUNK, dtype=spatial_wf.record_dtype(cfg))
            for name, col in spatial_wf_oracle.columns(
                    cfg, seed, start % period, CHUNK).items():
                batch[name] = col
            batch["id"] += spatial_wf_oracle.id_shift(
                cfg, start - start % period)
            batch["ts"] = base + log["off_us"]
            shipper.push_batch(batch)
    return generate


def _run(cfg, seed, build=spatial_wf.build, rate=RATE):
    """One pass of the pipeline: the sink's table, the log, the pipe."""
    log, got = _log(rate), []
    pipe = build(cfg, _source(cfg, seed, log),
                 lambda r: got.append(r.copy())
                 if r is not None and len(r) else None)
    pipe.run_and_wait_end(timeout=300)
    table = {k: np.asarray(v, dtype=np.int64) for k, v in
             spatial_wf.result_table(np.concatenate(got)).items()}
    return table, log, pipe


def _skyline_fn(fn):
    return JaxWindowFunction(fn, fields=("x", "y"),
                             result_fields=dict(spatial_wf.RESULT_FIELDS),
                             field_dtypes={"x": np.float32, "y": np.float32})


def _build_with(winfunc):
    """The configuration's pipeline around another window function."""
    def build(cfg, source_fn, sink_fn):
        shp = cfg["shapes"]
        return (MultiPipe("sky_other")
                .add_source(Source(source_fn, spatial_wf.SCHEMA, fresh=True))
                .add(WinFarmTPU(winfunc, shp["win_us"], shp["slide_us"],
                                WinType.TB, pardegree=2, batch_len=1,
                                flush_rows=4096, use_resident=True))
                .chain_sink(Sink(sink_fn, vectorized=True)))
    return build


def _numbers(table, want):
    return check.compare(table, want)[0]


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 424242])
def test_pipeline_matches_the_reference_exactly(seed):
    cfg = _cfg()
    table, log, pipe = _run(cfg, seed)
    cores = window_cores(pipe._df)
    assert [type(c).__name__ for c in cores] == ["ResidentWinSeqCore"] * 2
    assert all(c.executor.dispatches > 0 for c in cores)
    want = spatial_wf_oracle.expected(cfg, seed, log)
    brute = spatial_wf_oracle.brute_force(cfg, seed, log)
    assert len(want["wid"]) > 70
    assert all(np.array_equal(want[k], brute[k]) for k in brute)
    assert set(_numbers(table, want).values()) == {0}
    assert np.array_equal(table["wid"], want["wid"])     # in order at the sink


def test_plan_core_chooses_the_python_resident_core():
    fn = spatial_wf.window_function()
    from windflow_tpu.core.windows import WindowSpec
    plan = plan_core(WindowSpec(2_000, 500, WinType.TB), fn,
                     use_resident=True, native=4)
    assert (plan.core, plan.family, plan.mesh) == ("resident_py", "multi",
                                                   False)


def test_float16_control_reads_wrong():
    cfg, seed = _cfg(), 5
    log = _log()
    want = spatial_wf_oracle.expected(cfg, seed, log)
    control = spatial_wf_oracle.expected(cfg, seed, log,
                                         acc_dtype=np.float16)
    numbers = _numbers({k: v for k, v in control.items()
                        if not k.startswith("_")}, want)
    assert numbers["wrong.checksum"] > 0
    assert not check.verdict(numbers)[0]
    # ... and so do float16 rings under the program itself
    with np.errstate(invalid="ignore"):
        table, _log_, _pipe = _run(
            cfg, seed, _build_with(spatial_wf.window_function(np.float16)))
    assert _numbers(table, want)["wrong.checksum"] > 0


def _builds():
    return resident.stats_snapshot()["udf_step_builds"]


def test_a_second_pipeline_with_the_same_function_builds_no_step():
    cfg = _cfg()
    first, _l, _p = _run(cfg, 9)
    built = _builds()
    assert built > 0
    again, _l, _p = _run(cfg, 9)                # same function object
    assert _builds() == built
    assert all(np.array_equal(first[k], again[k]) for k in first)

    def other(keys, gwids, cols, mask):         # a different function
        return spatial_wf.skyline(keys, gwids, cols, mask)

    third, _l, _p = _run(cfg, 9, _build_with(_skyline_fn(other)))
    assert _builds() > built
    assert all(np.array_equal(first[k], third[k]) for k in first)


def test_the_step_cache_does_not_keep_a_function_alive():
    def mine(keys, gwids, cols, mask):
        return spatial_wf.skyline(keys, gwids, cols, mask)

    fn = _skyline_fn(mine)
    ex = resident.MultiFieldResidentExecutor(
        ("x", "y"), jax_fn=fn, acc_dtypes={"x": np.float32,
                                           "y": np.float32})
    ex.reset(1, 64)
    pts = np.asarray([[3, 1, 2, 2]], dtype=np.float32)
    ex.launch("m", {"x": pts, "y": pts[:, ::-1].copy()},
              np.zeros(1, dtype=np.int64), np.zeros(1, dtype=np.int64),
              np.zeros(1, dtype=np.int64), np.asarray([4]),
              wkeys=np.zeros(1, dtype=np.int64),
              wgwids=np.zeros(1, dtype=np.int64))
    (_meta, (size, checksum)), = ex.drain()
    # (3,2) (1,2) (2,1) (2,3): (1,2) and (2,1) are the frontier
    assert (int(size[0]), float(checksum[0])) == (2, 6.0)
    assert mine in resident._FN_STEP_CACHE
    dead = weakref.ref(mine)
    del mine, fn, ex
    gc.collect()
    assert dead() is None
    assert not any(f.__name__ == "mine" for f in resident._FN_STEP_CACHE)


class _Unhashable:
    """A callable the function-keyed cache cannot key."""
    __hash__ = None

    def __call__(self, keys, gwids, cols, mask):
        return spatial_wf.skyline(keys, gwids, cols, mask)


def test_a_function_that_cannot_be_keyed_keeps_its_steps_in_its_executor():
    fn = _skyline_fn(_Unhashable())
    ex = resident.MultiFieldResidentExecutor(
        ("x", "y"), jax_fn=fn, acc_dtypes={"x": np.float32,
                                           "y": np.float32})
    ex.reset(1, 64)
    one = np.ones((1, 2), dtype=np.float32)
    z = np.zeros(1, dtype=np.int64)
    for _ in range(2):
        ex.launch("m", {"x": one, "y": one}, z, z, z, np.asarray([2]),
                  wkeys=z, wgwids=z)
    results = ex.drain()
    assert len(ex._step_cache) == 1 and len(results) == 2
    assert int(results[0][1][0][0]) == 2          # identical points both live


@pytest.mark.parametrize("batch_len", [2, 4])
def test_launches_of_several_windows_agree_with_one(batch_len):
    one, log, _p = _run(_cfg(), 13)
    many, _l, pipe = _run(_cfg(batch_len=batch_len), 13)
    assert all(np.array_equal(one[k], many[k]) for k in one)
    fewer = sum(c.executor.dispatches for c in window_cores(pipe._df))
    assert fewer < sum(c.executor.dispatches
                       for c in window_cores(_p._df))


def test_the_function_bound_launch_counts_its_windows_rows_and_cells():
    cfg, seed = _cfg(), 21
    profile.enable()
    table, log, pipe = _run(cfg, seed)
    counters, spans = profile.counters(), profile.report()
    want = spatial_wf_oracle.expected(cfg, seed, log)
    ts = spatial_wf_oracle._event_times(log)
    _i, lo, hi, _c = spatial_wf_oracle._windows(cfg, ts)
    assert counters["udf_windows"] == len(want["wid"]) == len(table["wid"])
    assert counters["udf_rows"] == int((hi - lo).sum())
    # every window of a launch runs at the bucketed longest length
    assert counters["udf_cells"] >= counters["udf_rows"]
    assert counters["udf_cells"] % 256 == 0       # 200 points run as 256
    dispatches = sum(c.executor.dispatches for c in window_cores(pipe._df))
    assert spans["launch_take"][1] == spans["dispatch"][1] == dispatches
    # a step of the built-in families counts none of it
    assert "udf_windows" not in _reducer_counters()


def _reducer_counters():
    from windflow_tpu.core.tuples import Schema, batch_from_columns
    from windflow_tpu.core.windows import WindowSpec
    from windflow_tpu.ops.functions import MultiReducer, Reducer
    from windflow_tpu.patterns.win_seq_tpu import ResidentWinSeqCore
    profile.reset()
    core = ResidentWinSeqCore(
        WindowSpec(4, 2, WinType.CB),
        MultiReducer(Reducer("sum", "a", "s", value_range=(0, 32)),
                     Reducer("max", "a", "m", value_range=(0, 32))),
        batch_len=4)
    ids = np.arange(32)
    core.process(batch_from_columns(Schema(a=np.int64), key=ids % 2,
                                    id=ids // 2, ts=ids, a=ids))
    core.flush()
    return profile.counters()


# -- the padded length of a window handed to a user's function ----------------

def _fine(n):
    return _bucket_fine(n), _bucket(n)


@pytest.mark.parametrize("octave", range(3, 19))
def test_the_ladder_of_a_function_bound_pad(octave):
    """Every length of (2^(octave-1), 2^octave] up to 4,096; above, the
    neighbours of each step and every 997th length between."""
    lo, hi = (1 << (octave - 1)) + 1, 1 << octave
    ns = range(lo, hi + 1)
    if hi > 4096:
        steps = range(lo - 1, hi + 1, hi // 16)
        ns = sorted(set(ns[::997]) | {n for s in steps
                                      for n in (s - 1, s, s + 1) if n in ns})
    last = 0
    for n in ns:
        fine, power = _fine(n)
        assert n <= fine <= power == hi
        assert fine >= last                                   # monotone
        last = fine
        if n < 1024:
            assert fine == power
        else:
            assert fine % 128 == 0 and 8 * fine <= 9 * n      # <= 12.5% over
        if n >= 8192:
            assert fine % 1024 == 0
    if hi > 1024:
        # eight steps a doubling: 2^k * (8 + j) / 8
        assert sorted({_fine(n)[0] for n in ns}) == [
            (hi // 2) * (8 + j) // 8 for j in range(1, 9)]


@pytest.mark.parametrize("n, want", [
    (0, 8), (1, 8), (200, 256), (1023, 1024), (1024, 1024), (1025, 1152),
    (98_304, 98_304), (98_305, 106_496), (102_400, 106_496),
    (102_401, 106_496), (106_497, 114_688), (131_073, 147_456)])
def test_the_ladder_at_the_lengths_the_records_name(n, want):
    assert _fine(n)[0] == want


def _fn_pads(fn):
    """The padded lengths of the steps cached under a user's function."""
    return {key.pad for key in resident._FN_STEP_CACHE.get(fn, {})}


@pytest.mark.parametrize("points, pad", [(1151, 1152), (1152, 1152),
                                         (1153, 1280)])
def test_windows_on_under_and_over_a_ladder_step_match_the_reference(
        points, pad):
    """One point a microsecond, so a window of `points` us holds `points`
    of them: just under, on and just over the step 1,152."""
    cfg, seed = _cfg(win_us=points, slide_us=384), 31 + points
    table, log, pipe = _run(cfg, seed, rate=1_000_000)
    want = spatial_wf_oracle.expected(cfg, seed, log)
    ts = spatial_wf_oracle._event_times(log)
    _i, lo, hi, _c = spatial_wf_oracle._windows(cfg, ts)
    assert int((hi - lo).max()) == points and len(want["wid"]) >= 8
    assert set(_numbers(table, want).values()) == {0}
    assert np.array_equal(table["wid"], want["wid"])
    pads = _fn_pads(spatial_wf.skyline)
    assert pad in pads and 2048 not in pads


def _multi_executors(fn=None, stats=()):
    """A multi-field executor on one device and one on a mesh of two."""
    from windflow_tpu.parallel.mesh import make_mesh
    return [resident.make_executor(
        "multi", ("x", "y"), stats, {"x": np.float32, "y": np.float32},
        jax_fn=fn, mesh=mesh) for mesh in (None, make_mesh(n_kf=2))]


def _launch_one_window(ex, n):
    """One key's `n` points appended and evaluated as one window."""
    rng = np.random.default_rng(n)
    x = rng.integers(0, 1 << 16, (1, n)).astype(np.float32)
    y = rng.integers(0, 1 << 16, (1, n)).astype(np.float32)
    z = np.zeros(1, dtype=np.int64)
    ex.reset(1, 4096)
    ex.launch("m", {"x": x, "y": y}, z, z, z, np.asarray([n]),
              wkeys=z, wgwids=z)
    (_meta, out), = ex.drain()
    return [np.asarray(o).ravel()[:1] for o in out]


def test_a_function_bound_launch_is_padded_alike_on_a_mesh_and_on_one_device():
    def mine(keys, gwids, cols, mask):
        return spatial_wf.skyline(keys, gwids, cols, mask)

    one, mesh = _multi_executors(_skyline_fn(mine))
    outs = [_launch_one_window(ex, 1100) for ex in (one, mesh)]
    keys = list(resident._FN_STEP_CACHE[mine])
    assert len(keys) == 2 and keys[0].place.mesh is None
    assert keys[1].place == (mesh.mesh, "kf")
    assert keys[0].pad == keys[1].pad == 1152
    assert all(np.array_equal(a, b) for a, b in zip(*outs))
    lens = np.asarray([1100, 7, 1153])
    assert one._pad_for(lens) == mesh._pad_for(lens) == 1280


def test_a_stat_only_launch_still_keys_its_step_on_the_power_of_two():
    one, mesh = _multi_executors(stats=(("max", "x"), ("sum", "y")))
    (mx, sm), (mx_m, sm_m) = (_launch_one_window(ex, 1100)
                              for ex in (one, mesh))
    assert mx == mx_m and sm == sm_m
    # PR 43's parent's key, field for field: pad 2,048
    key = resident.StepKey(
        "multi", resident._ANY_DEVICE, (("max", "x"), ("sum", "y")), 4096,
        2048, 8, 8, ("<f4", "<f4"), ("<f4", "<f4"), 2048, ("x", "y"))
    assert key in resident._STEP_CACHE
    assert key._replace(place=mesh.place, KP=16) \
        in resident._STEP_CACHE                         # 2 shards x 8 rows
    assert one._pad_for(np.asarray([1100])) == 2048
    one.stats = (("sum", "x"),)                  # prefix sums gather nothing
    assert one._pad_for(np.asarray([1100])) == 0
