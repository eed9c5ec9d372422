"""Pane_Farm with a user's DEVICE function whose result is a container
(``PaneFarmTPU(plq_on_device=True, wlq_on_device=False)`` around a
``JaxWindowFunction(count_field=)``: Pane_Farm_GPU's device-PLQ families,
pane_farm_gpu.hpp:176-201), on the CPU at small sizes: the pane skylines on
the device and their merge on the host against the host ``PaneFarm`` row for
row and against the whole-window brute force (``tests/oracle.py``); an empty
pane, a frontier that fills its ``cap`` and one that passes it; every
``opt_level``, a timer, a mesh, the restaging core; what is refused and how;
the cast of a sub-array result field on the cores alone; the counters and
the two stage-emit spans; and the benchmark's ``spatial_pf`` configuration
(benchmarks/configs/spatial_pf.*) against its plain reference.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH, os.path.dirname(os.path.abspath(__file__))):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import oracle  # noqa: E402
from configs import spatial_pf, spatial_pf_oracle  # noqa: E402
from harness import check  # noqa: E402

from windflow_tpu.api import MultiPipe  # noqa: E402
from windflow_tpu.apps import spatial  # noqa: E402
from windflow_tpu.core.tuples import Schema  # noqa: E402
from windflow_tpu.core.windows import WindowSpec, WinType  # noqa: E402
from windflow_tpu.ops import resident  # noqa: E402
from windflow_tpu.patterns.basic import Sink, Source  # noqa: E402
from windflow_tpu.patterns.pane_farm import PaneFarm  # noqa: E402
from windflow_tpu.patterns.win_seq import WinSeq, window_cores  # noqa: E402
from windflow_tpu.patterns.win_seq_tpu import (  # noqa: E402
    DeviceWinSeqCore, JaxWindowFunction, PaneFarmTPU, ResidentWinSeqCore,
    make_core_for, plan_core)
from windflow_tpu.utils import profile  # noqa: E402

SCHEMA = Schema(x=np.float32, y=np.float32)
CAP, N, CHUNK = 16, 3000, 150


@pytest.fixture(autouse=True)
def _profile_state():
    profile.disable()
    profile.reset()
    yield
    profile.auto()
    profile.reset()


def _points(seed=3, n=N, side=64):
    """Seeded points on an integer grid: ties and identical points occur,
    and float32 holds every coordinate as the host's float64 does."""
    return np.random.default_rng(seed).integers(
        0, side, size=(n, 2)).astype(np.float32)


def _stream(pts, ts=None):
    """The batches of one key-less stream of ``pts``; ``ts`` 10 a row unless
    given."""
    ids = np.arange(len(pts), dtype=np.int64)
    ts = ids * 10 if ts is None else np.asarray(ts, dtype=np.int64)
    out = []
    for lo in range(0, len(pts), CHUNK):
        b = np.zeros(min(CHUNK, len(pts) - lo), dtype=SCHEMA.dtype())
        b["id"], b["ts"] = ids[lo:lo + CHUNK], ts[lo:lo + CHUNK]
        b["x"], b["y"] = pts[lo:lo + CHUNK, 0], pts[lo:lo + CHUNK, 1]
        out.append(b)
    return out


def _run(agg, batches, trace_dir=None):
    got = []

    def src(shipper):
        for b in batches:
            shipper.push_batch(b.copy())

    pipe = (MultiPipe("pf_udf", trace_dir=trace_dir)
            .add_source(Source(src, SCHEMA, fresh=True)).add(agg)
            .chain_sink(Sink(lambda r: got.append(r.copy())
                             if r is not None and len(r) else None,
                             vectorized=True)))
    pipe.run_and_wait_end(timeout=300)
    return np.concatenate(got), pipe


def _device(win, slide, wt, deg=2, cap=CAP, **kw):
    kw.setdefault("use_resident", True)
    return PaneFarmTPU(spatial.device_skyline_plq(cap), spatial.SkylineWLQ(),
                       win, slide, wt, plq_degree=deg, wlq_degree=1,
                       plq_on_device=True, wlq_on_device=False, batch_len=1,
                       flush_rows=4096, **kw)


def _host(win, slide, wt, deg=2, cap=CAP, **kw):
    return PaneFarm(spatial.SkylinePLQ(cap), spatial.SkylineWLQ(), win, slide,
                    wt, plq_degree=deg, wlq_degree=1, **kw)


def _as_dict(rows):
    return {int(r["id"]): (int(r["size"]), float(r["checksum"]))
            for r in rows}


GEOMETRY = {"TB": (4000, 1000, WinType.TB, "ts"),
            "CB": (300, 100, WinType.CB, "id")}


def _complete(ids, win, slide, panes=30):
    """Of the windows ``ids`` those whose every pane exists, of ``panes``
    panes of ``gcd(win, slide)`` (N rows at 10 a row: 30 of 1000 time
    units, 30 of 100 ids): what the window stage fires with a window's
    last pane; the flush brings the rest."""
    pane = np.gcd(win, slide)
    return int(np.count_nonzero(
        (np.asarray(ids) * slide + win) // pane <= panes))


@pytest.mark.parametrize("deg", [1, 2])
@pytest.mark.parametrize("kind", sorted(GEOMETRY))
def test_device_plq_and_host_wlq_equal_the_host_pane_farm_and_brute_force(
        kind, deg):
    win, slide, wt, pos = GEOMETRY[kind]
    pts = _points()
    batches = _stream(pts)
    dev, pipe = _run(_device(win, slide, wt, deg), batches)
    host, _p = _run(_host(win, slide, wt, deg), batches)
    assert np.array_equal(dev, host)            # row for row, header too
    cores = window_cores(pipe._df)
    assert [type(c).__name__ for c in cores] \
        == ["ResidentWinSeqCore"] * deg + ["WinSeqCore"]
    assert all(c.executor.dispatches > 0 for c in cores[:deg])
    # the pane workers are over the user's stream and told nothing; the
    # window worker is told its input is dense and fires on the last pane
    assert [c.windows_fired_complete for c in cores] == [None] * deg \
        + [_complete(dev["id"], win, slide)]
    assert 0 < cores[-1].windows_fired_complete < len(dev)
    rows = np.concatenate(batches)
    whole = oracle.skyline_windows(rows, win, slide, pos)
    assert oracle.skyline_windows(rows, win, slide, pos, by_panes=True) \
        == whole
    assert _as_dict(dev) == whole and len(whole) > 25
    assert np.array_equal(dev["id"], np.arange(len(dev)))   # in order


@pytest.mark.parametrize("deg", [1, 2])
@pytest.mark.parametrize("opt_level", [1, 2])
def test_every_opt_level_runs_a_device_plq(opt_level, deg):
    batches = _stream(_points(5))
    plain, _p = _run(_device(4000, 1000, WinType.TB, deg), batches)
    fused, pipe = _run(_device(4000, 1000, WinType.TB, deg,
                               opt_level=opt_level), batches)
    assert np.array_equal(plain, fused)
    cores = window_cores(pipe._df)
    assert [type(c).__name__ for c in cores] \
        == ["ResidentWinSeqCore"] * deg + ["WinSeqCore"]
    assert cores[-1].windows_fired_complete \
        == _complete(fused["id"], 4000, 1000) == len(fused) - 3


@pytest.mark.parametrize("deg", [1, 2])
def test_a_pane_with_no_point_still_yields_its_empty_result(deg):
    pts = _points(7)
    ts = np.arange(N) * 10
    ts[ts >= 12_000] += 3_000          # three panes nobody sends a point in
    batches = _stream(pts, ts)
    dev, pipe = _run(_device(4000, 1000, WinType.TB, deg), batches)
    host, _p = _run(_host(4000, 1000, WinType.TB, deg), batches)
    assert np.array_equal(dev, host)
    assert _as_dict(dev) == oracle.skyline_windows(
        np.concatenate(batches), 4000, 1000)
    # the pane stage gave the three empty panes their result: the WLQ's
    # count-based windows did not slip
    panes = sum(c.pane_results for c in window_cores(pipe._df)[:deg])
    assert panes == int(ts[-1]) // 1000 + 1


def _staircase(n_front, n_dominated=40, seed=11):
    """One pane's points: a frontier of exactly ``n_front`` and points it
    dominates, shuffled."""
    rng = np.random.default_rng(seed)
    front = np.stack([np.arange(n_front), n_front - 1 - np.arange(n_front)],
                     axis=1)
    inner = front[rng.integers(0, n_front, n_dominated)] \
        + rng.integers(1, 5, size=(n_dominated, 2))
    pts = np.concatenate([front, inner]).astype(np.float32)
    return pts[rng.permutation(len(pts))]


def test_a_frontier_that_fills_cap_exactly_is_carried_whole():
    cap = 8
    pts = np.concatenate([_staircase(cap), _points(13, 400) + 100])
    batches = _stream(pts)
    dev, pipe = _run(_device(2000, 1000, WinType.TB, 2, cap=cap), batches)
    host, _p = _run(_host(2000, 1000, WinType.TB, 2, cap=cap), batches)
    assert np.array_equal(dev, host)
    assert int(dev["size"][0]) == cap          # window 0: the staircase
    cores = window_cores(pipe._df)[:2]
    assert sum(c.pane_overflow for c in cores) == 0
    assert sum(c.pane_results for c in cores) == 5
    assert sum(c.pane_points_kept for c in cores) >= cap


@pytest.mark.parametrize("use_resident", [True, None])
def test_a_frontier_over_cap_raises_and_names_cap(use_resident):
    pts = np.concatenate([_staircase(9), _points(13, 400) + 100])
    with pytest.raises(Exception) as err:
        _run(_device(2000, 1000, WinType.TB, 2, cap=8,
                     use_resident=use_resident), _stream(pts))
    text = str(err.value)
    assert "cap of 8 slots" in text and "holds 9 entries" in text
    assert "never handed on" in text


def test_the_restaging_core_carries_a_container_result_too():
    batches = _stream(_points(17))
    dev, pipe = _run(_device(4000, 1000, WinType.TB, 2, use_resident=None),
                     batches)
    host, _p = _run(_host(4000, 1000, WinType.TB, 2), batches)
    assert np.array_equal(dev, host)
    assert [type(c).__name__ for c in window_cores(pipe._df)] \
        == ["DeviceWinSeqCore"] * 2 + ["WinSeqCore"]


def test_an_incremental_host_wlq_folds_the_device_panes():
    """Pane_Farm_GPU's second device-PLQ family: the host WLQ folds a pane
    row at a time (a ``WindowUpdate``)."""
    from windflow_tpu.ops.functions import WindowUpdate

    class FrontierTotals(WindowUpdate):
        result_fields = {"points": np.int64, "x_sum": np.float64}

        def update(self, key, gwid, row, acc):
            n = int(row["sk_n"])
            acc["points"] += n
            acc["x_sum"] += float(row["sk_x"][:n].sum())

    batches = _stream(_points(47))
    kw = dict(plq_degree=2, wlq_degree=1, wlq_incremental=True)
    dev, _p = _run(PaneFarmTPU(
        spatial.device_skyline_plq(CAP), FrontierTotals(), 4000, 1000,
        WinType.TB, plq_on_device=True, wlq_on_device=False, batch_len=1,
        flush_rows=4096, use_resident=True, **kw), batches)
    host, _p = _run(PaneFarm(spatial.SkylinePLQ(CAP), FrontierTotals(), 4000,
                             1000, WinType.TB, **kw), batches)
    assert np.array_equal(dev, host) and int(dev["points"].min()) > 0


def test_a_timer_on_the_device_plq_changes_no_result():
    batches = _stream(_points(19))
    plain, _p = _run(_device(4000, 1000, WinType.TB, 2), batches)
    timed, pipe = _run(_device(4000, 1000, WinType.TB, 2, max_delay_ms=1.0),
                       batches)
    assert np.array_equal(plain, timed)
    assert window_cores(pipe._df)[0].max_delay_s == 1e-3


def _drive(core, batches):
    out = np.concatenate([core.process(b) for b in batches] + [core.flush()])
    return out[np.lexsort((out["id"], out["key"]))]


def _keyed_stream(n_keys=6):
    pts = _points(23, 2400)
    out = []
    for b in _stream(pts):
        seq = b["id"].copy()
        b["key"], b["id"] = seq % n_keys, seq // n_keys
        b["ts"] = b["id"] * 60
        out.append(b)
    return out


def test_a_container_result_holds_on_a_mesh():
    """Rank-2 outputs through ``_OnMesh``: the shard-major window layout,
    the harvest's (shard, slot) selection and the copy to the host."""
    from windflow_tpu.parallel.mesh import make_mesh
    batches = _keyed_stream()
    host = _drive(WinSeq(spatial.SkylinePLQ(CAP), 1000, 1000,
                         WinType.TB).make_core(), batches)
    core = make_core_for(WindowSpec(1000, 1000, WinType.TB),
                         spatial.device_skyline_plq(CAP), batch_len=4,
                         flush_rows=4096, mesh=make_mesh(4, 1))
    assert type(core.executor.place).__name__ == "_OnMesh"
    assert np.array_equal(_drive(core, batches), host)
    assert core.pane_results == len(host)


# ------------------------------------------------------------- the refusals

def test_a_device_wlq_over_container_panes_is_refused_by_name():
    def merge(keys, gwids, cols, mask):
        return cols["sk_n"].sum(axis=1)

    wlq = JaxWindowFunction(merge, fields=("sk_x", "sk_n"),
                            result_fields={"size": np.int64})
    with pytest.raises(ValueError, match=r"device WLQ cannot read the "
                       r"container-valued pane fields \['sk_x'\].*"
                       r"wlq_on_device=False"):
        PaneFarmTPU(spatial.device_skyline_plq(CAP), wlq, 4000, 1000,
                    WinType.TB, plq_on_device=True, wlq_on_device=True)
    with pytest.raises(ValueError, match="container-valued pane fields"):
        PaneFarmTPU(spatial.SkylinePLQ(CAP), wlq, 4000, 1000, WinType.TB,
                    plq_on_device=False, wlq_on_device=True)
    # a device WLQ over the panes' SCALAR field alone is no container's
    PaneFarmTPU(spatial.device_skyline_plq(CAP),
                JaxWindowFunction(merge, fields=("sk_n",),
                                  result_fields={"size": np.int64}),
                4000, 1000, WinType.TB, use_resident=True)


def test_a_host_function_on_a_device_stage_is_refused_at_construction():
    with pytest.raises(TypeError, match="host Python functions cannot be "
                       "staged to the TPU"):
        PaneFarmTPU(spatial.device_skyline_plq(CAP), spatial.SkylineWLQ(),
                    4000, 1000, WinType.TB, plq_degree=2,
                    plq_on_device=True, wlq_on_device=True)


@pytest.mark.parametrize("fields,count_field", [
    ({"sk_x": np.dtype((np.float32, (4,))), "sk_n": np.int64}, "n"),
    ({"sk_x": np.float32, "sk_n": np.int64}, "sk_n")])
def test_a_count_field_needs_its_field_and_a_container_beside_it(
        fields, count_field):
    with pytest.raises(ValueError, match="count_field=.*container-valued"):
        JaxWindowFunction(lambda k, g, c, m: (), fields=("x",),
                          result_fields=fields, count_field=count_field)


@pytest.mark.parametrize("use_resident", [True, None])
def test_an_output_that_does_not_fit_its_result_field_is_named(use_resident):
    import jax.numpy as jnp

    def two_wide(keys, gwids, cols, mask):
        return jnp.stack([cols["x"].sum(axis=1)] * 2, axis=1)

    fn = JaxWindowFunction(two_wide, fields=("x",),
                           result_fields={"v": np.dtype((np.float64, (3,)))},
                           field_dtypes={"x": np.float32})
    core = make_core_for(WindowSpec(1000, 1000, WinType.TB), fn, batch_len=1,
                         flush_rows=4096, use_resident=use_resident)
    with pytest.raises(ValueError, match=r"output of shape \(\d+, 2\) does "
                       r"not fit its result field"):
        _drive(core, _stream(_points(29, 600)))


# ----------------------------------------- the cast, on the cores alone

@pytest.mark.parametrize("use_resident,core_class", [
    (True, ResidentWinSeqCore), (None, DeviceWinSeqCore)])
def test_a_sub_array_result_field_takes_a_rank_two_output(use_resident,
                                                          core_class):
    """A function returning ``(B, 3)`` into ``("v", (np.float64, (3,)))``:
    ``astype`` with the sub-array dtype itself broadcast every element to
    three and gave ``(n, 3, 3)``."""
    import jax.numpy as jnp

    def moments(keys, gwids, cols, mask):
        x = cols["x"]
        return (jnp.stack([jnp.sum(mask, axis=1).astype(jnp.float32),
                           jnp.sum(x, axis=1), jnp.sum(x * x, axis=1)],
                          axis=1),
                jnp.max(jnp.where(mask, x, 0), axis=1))

    fn = JaxWindowFunction(
        moments, fields=("x",), field_dtypes={"x": np.float32},
        result_fields={"v": np.dtype((np.float64, (3,))), "top": np.int64})
    core = make_core_for(WindowSpec(1000, 500, WinType.TB), fn, batch_len=2,
                         flush_rows=4096, use_resident=use_resident)
    assert type(core) is core_class
    batches = _stream(_points(31, 900, side=16))
    out = _drive(core, batches)
    assert out["v"].shape == (len(out), 3) and out["v"].dtype == np.float64
    rows = np.concatenate(batches)
    for r in out:
        lo = int(r["id"]) * 500
        x = rows["x"][(rows["ts"] >= lo) & (rows["ts"] < lo + 1000)]
        assert r["v"].tolist() == [len(x), x.sum(), (x * x).sum()]
        assert r["top"] == x.max()


# ------------------------------------------------ counters, spans, plan

def test_plan_core_gives_the_pane_function_the_python_resident_core():
    plan = plan_core(WindowSpec(1000, 1000, WinType.TB),
                     spatial.device_skyline_plq(CAP), use_resident=True,
                     native=4)
    assert (plan.core, plan.family, plan.mesh) == ("resident_py", "multi",
                                                   False)


def test_the_pane_counters_and_the_two_stage_emit_spans(tmp_path):
    profile.enable()
    batches = _stream(_points(37))
    dev, pipe = _run(_device(4000, 1000, WinType.TB, 2), batches,
                     trace_dir=str(tmp_path))
    host_panes, _p = _run(
        WinSeq(spatial.SkylinePLQ(CAP), 1000, 1000, WinType.TB), batches)
    counters = profile.counters()
    assert counters["pane_results"] == len(host_panes) == 30
    assert counters["pane_points_kept"] == int(host_panes["sk_n"].sum())
    assert "pane_overflow" not in counters
    logs = {}
    for fn in os.listdir(tmp_path):
        if fn.endswith(".log"):
            with open(tmp_path / fn) as f:
                node = json.load(f)
            logs[node["node"]] = node
    plq = [n for name, n in logs.items() if "_plq" in name
           and "pane_results" in n]
    assert len(plq) == 2
    assert sum(n["pane_results"] for n in plq) == 30
    assert sum(n["pane_points_kept"] for n in plq) \
        == counters["pane_points_kept"]
    assert all(n["pane_overflow"] == 0 for n in plq)
    assert not any("pane_results" in n for name, n in logs.items()
                   if "_plq" not in name)
    # the window stage alone says how many windows it fired with their
    # last pane: all but the three the stream's end cut short
    wlq = {name: n for name, n in logs.items()
           if "windows_fired_complete" in n}
    assert len(wlq) == 1 and "_wlq" in next(iter(wlq))
    wlq = next(iter(wlq.values()))
    assert (wlq["windows_fired_complete"], wlq["windows_fired"]) \
        == (len(dev) - 3, len(dev))
    # one record a batch of results: every pane id once from the pane
    # stage, every window id once from the window stage, a window after the
    # pane that closed it
    with open(tmp_path / "launches.jsonl") as f:
        records = [json.loads(line) for line in f]
    emitted = {phase: sorted((i, r["t0_ns"], r["t1_ns"])
                             for r in records if r["phase"] == phase
                             for i in r["ids"])
               for phase in ("pane_emit", "window_emit")}
    assert [i for i, _a, _b in emitted["pane_emit"]] == list(range(30))
    assert [i for i, _a, _b in emitted["window_emit"]] == list(range(len(dev)))
    pane_out = {i: t0 for i, t0, _t1 in emitted["pane_emit"]}
    for w, _t0, t1 in emitted["window_emit"]:
        assert t1 > pane_out[min(w + 3, 29)]
    assert all(r["rows"] >= len(r["ids"]) >= 1 and r["key"] == 0
               for r in records if r["phase"] in emitted)


def test_a_built_in_stage_keeps_no_pane_counter_and_emits_no_stage_span():
    from windflow_tpu.core.tuples import batch_from_columns
    from windflow_tpu.ops.functions import Reducer
    profile.enable()
    schema, ids = Schema(value=np.int64), np.arange(900)
    got = []
    (MultiPipe("pf_sum")
     .add_source(Source(lambda sh: sh.push_batch(batch_from_columns(
         schema, key=np.zeros(900), id=ids, ts=ids, value=ids % 7)), schema,
         fresh=True))
     .add(PaneFarmTPU(Reducer("sum"), Reducer("sum"), 300, 100, WinType.CB,
                      plq_degree=2, use_resident=True, flush_rows=4096))
     .chain_sink(Sink(lambda r: got.append(r.copy())
                      if r is not None and len(r) else None,
                      vectorized=True))).run_and_wait_end(timeout=300)
    assert int(np.concatenate(got)["value"][0]) == int((ids[:300] % 7).sum())
    assert "pane_results" not in profile.counters()
    # (the spans are the pattern's, not the function's: they are there)
    assert profile.report()["pane_emit"][1] > 0


def test_the_spatial_app_runs_the_pane_form_on_the_device():
    pts = _points(43, 2000, side=256) / 256.0      # the app's 1/256 grid
    ids = np.arange(len(pts))
    batches = [spatial._pt_batch(ids[lo:lo + 200], np.zeros(200, np.int64),
                                 ids[lo:lo + 200] * 50, pts[lo:lo + 200, 0],
                                 pts[lo:lo + 200, 1])
               for lo in range(0, len(pts), 200)]
    sizes = {}
    for variant in ("pf", "pf-tpu"):
        pipe, sink, n_gen = spatial.build_spatial(
            variant, 0.0, 2, 20.0, 5.0, 200, batches=[b.copy()
                                                      for b in batches])
        pipe.run_and_wait_end(timeout=300)
        sizes[variant] = (sink.received, sink.skyline_points, n_gen[0])
    assert sizes["pf"] == sizes["pf-tpu"] and sizes["pf"][0] > 15
    with pytest.raises(ValueError, match="--max-delay-ms applies to the "
                       "device variants"):
        spatial.build_spatial("pf", 0.0, 2, 20.0, 5.0, 200, batches=[],
                              max_delay_ms=5.0)


# ------------------------------ the benchmark's configuration (spatial_pf)

B_CHUNK, B_RATE, B_CHUNKS = 64, 100_000, 60    # 3,840 points, 50 a pane


def _cfg(**shapes):
    with open(os.path.join(BENCH, "configs", "spatial_pf.json")) as f:
        cfg = json.load(f)
    cfg["shapes"].update(win_us=2_000, slide_us=500, flush_rows=4096, cap=16)
    cfg["shapes"].update(shapes)
    cfg["stream"]["template_events"] = 4096
    return cfg


def _log():
    return {"chunk": B_CHUNK,
            "off_us": (np.arange(B_CHUNK, dtype=np.int64) * 1_000_000)
            // B_RATE,
            "base_us": [(j * B_CHUNK * 1_000_000) // B_RATE
                        for j in range(B_CHUNKS)]}


def _source(cfg, seed, log):
    period = spatial_pf_oracle.period_events(cfg)

    def generate(shipper):
        for j, base in enumerate(log["base_us"]):
            start = j * B_CHUNK
            batch = np.zeros(B_CHUNK, dtype=spatial_pf.record_dtype(cfg))
            for name, col in spatial_pf_oracle.columns(
                    cfg, seed, start % period, B_CHUNK).items():
                batch[name] = col
            batch["id"] += spatial_pf_oracle.id_shift(
                cfg, start - start % period)
            batch["ts"] = base + log["off_us"]
            shipper.push_batch(batch)
    return generate


def _run_cfg(cfg, seed, build=spatial_pf.build):
    log, got = _log(), []
    pipe = build(cfg, _source(cfg, seed, log),
                 lambda r: got.append(r.copy())
                 if r is not None and len(r) else None)
    pipe.run_and_wait_end(timeout=300)
    table = {k: np.asarray(v, dtype=np.int64) for k, v in
             spatial_pf.result_table(np.concatenate(got)).items()}
    return table, log, pipe


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 424242])
def test_the_configuration_matches_its_plain_reference_exactly(seed):
    cfg = _cfg()
    table, log, pipe = _run_cfg(cfg, seed)
    want = spatial_pf_oracle.expected(cfg, seed, log)
    brute = spatial_pf_oracle.brute_force(cfg, seed, log)
    assert len(want["wid"]) > 70
    assert all(np.array_equal(want[k], brute[k]) for k in brute)
    assert set(check.compare(table, want)[0].values()) == {0}
    assert np.array_equal(table["wid"], want["wid"])     # in order
    # the device path the benchmark asserts on, stage by stage
    from harness import device_assert
    devices, dispatches = device_assert.assert_device_path(
        window_cores(pipe._df), cfg["expected_core"],
        spatial_pf.window_workers(cfg), "cpu")
    assert dispatches > 70 and len(devices) >= 1


def test_the_configuration_under_float16_rings_reads_wrong():
    cfg, seed = _cfg(), 5
    log = _log()
    want = spatial_pf_oracle.expected(cfg, seed, log)
    control = spatial_pf_oracle.expected(cfg, seed, log,
                                         acc_dtype=np.float16)
    numbers = check.compare({k: v for k, v in control.items()
                             if not k.startswith("_")}, want)[0]
    assert numbers["wrong.checksum"] > 0
    assert not check.verdict(numbers)[0]

    def build16(cfg, source_fn, sink_fn):
        shp = cfg["shapes"]
        return (MultiPipe("sky_pf_16")
                .add_source(Source(source_fn, spatial_pf.SCHEMA, fresh=True))
                .add(PaneFarmTPU(
                    spatial_pf.pane_function(shp["cap"], np.float16),
                    spatial_pf.WindowMerge(), shp["win_us"], shp["slide_us"],
                    WinType.TB, plq_degree=2, plq_on_device=True,
                    wlq_on_device=False, batch_len=1, flush_rows=4096,
                    use_resident=True))
                .chain_sink(Sink(sink_fn, vectorized=True)))

    with np.errstate(invalid="ignore"):
        table, _l, _p = _run_cfg(cfg, seed, build16)
    assert check.compare(table, want)[0]["wrong.checksum"] > 0


def test_a_second_pipeline_of_the_configuration_builds_no_step():
    cfg = _cfg()
    first, _l, _p = _run_cfg(cfg, 9)
    built = resident.stats_snapshot()["udf_step_builds"]
    assert built > 0
    again, _l, _p = _run_cfg(cfg, 9)       # pane_skyline(cap): one object
    assert resident.stats_snapshot()["udf_step_builds"] == built
    assert all(np.array_equal(first[k], again[k]) for k in first)
