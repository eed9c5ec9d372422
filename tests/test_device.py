"""Device-path differential tests — the equivalent of src/sum_test_gpu:
every TPU pattern must produce the same results as its host counterpart /
Win_Seq on the same stream.  Under pytest these run on the CPU XLA backend
(conftest pins JAX_PLATFORMS=cpu); bench.py runs the same code on the real
chip."""

import numpy as np
import pytest

import jax.numpy as jnp

from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.patterns.win_seq import WinSeq
from windflow_tpu.patterns.win_seq_tpu import (DeviceWinSeqCore,
                                               JaxWindowFunction, KeyFarmTPU,
                                               PaneFarmTPU, WinFarmTPU,
                                               WinMapReduceTPU, WinSeqTPU)

from test_farms import (assert_wlq_fired_complete, cb_stream_batches,
                        dense_fire_counts, run_windowed, tb_stream_batches)
from test_pane_wmr import iv


def ref(win, slide, wt, batches):
    return run_windowed(WinSeq(Reducer("sum"), win, slide, wt), batches)


@pytest.mark.parametrize("win,slide", [(8, 3), (8, 8), (3, 8), (16, 7)])
@pytest.mark.parametrize("batch_len", [1, 7, 64, 100000])
def test_win_seq_tpu_cb(win, slide, batch_len):
    keys, n = 3, 150
    got = run_windowed(
        WinSeqTPU(Reducer("sum"), win, slide, WinType.CB,
                  batch_len=batch_len),
        cb_stream_batches(keys, n))
    assert got == ref(win, slide, WinType.CB, cb_stream_batches(keys, n))


@pytest.mark.parametrize("win,slide", [(40, 15), (30, 30), (15, 40)])
def test_win_seq_tpu_tb_ragged(win, slide):
    """TB windows are ragged -> exercises bucket padding + masking."""
    keys, n = 2, 160
    got = run_windowed(
        WinSeqTPU(Reducer("sum"), win, slide, WinType.TB, batch_len=32),
        tb_stream_batches(keys, n))
    assert got == ref(win, slide, WinType.TB, tb_stream_batches(keys, n))


@pytest.mark.parametrize("op", ["sum", "count", "min", "max", "mean"])
def test_builtin_ops_device(op):
    if op == "mean":
        pytest.skip("host Reducer has no mean; covered by jax-fn test")
    got = run_windowed(
        WinSeqTPU(Reducer(op), 10, 4, WinType.CB, batch_len=16),
        cb_stream_batches(2, 100))
    want = run_windowed(WinSeq(Reducer(op), 10, 4, WinType.CB),
                        cb_stream_batches(2, 100))
    assert got == want


def test_count_without_value_field():
    """count stages no payload columns at all (required_fields=())."""
    from windflow_tpu.core.tuples import Schema, batch_from_columns
    from windflow_tpu.patterns.basic import Sink, Source
    from windflow_tpu.runtime.engine import Dataflow
    from windflow_tpu.runtime.farm import build_pipeline

    bare = Schema()  # no payload fields
    ids = np.arange(40)
    b = batch_from_columns(bare, key=np.zeros(40), id=ids, ts=ids)
    got = []
    df = Dataflow()
    build_pipeline(df, [
        Source(batches=[b], schema=bare),
        WinSeqTPU(Reducer("count"), 10, 10, WinType.CB, batch_len=4),
        Sink(lambda r: got.append(int(r["value"])) if r is not None else None)])
    df.run_and_wait_end()
    assert got == [10, 10, 10, 10]


def test_user_jax_window_function():
    """Arbitrary JAX function over the window batch — the CUDA-functor
    replacement: here, sum of squares."""
    def fn(keys, gwids, cols, mask):
        v = cols["value"]
        return jnp.sum(jnp.where(mask, v * v, 0), axis=1)

    jf = JaxWindowFunction(fn, fields=("value",),
                           result_fields={"value": np.int64})
    got = run_windowed(WinSeqTPU(jf, 6, 2, WinType.CB, batch_len=32),
                       cb_stream_batches(2, 80))

    def host(key, gwid, rows):
        return int(np.sum(rows["value"].astype(np.int64) ** 2))

    from windflow_tpu.ops.functions import FnWindowFunction
    want = run_windowed(
        WinSeq(FnWindowFunction(host, {"value": np.int64}), 6, 2, WinType.CB),
        cb_stream_batches(2, 80))
    assert got == want


def test_host_python_fn_rejected_on_device():
    with pytest.raises(TypeError, match="cannot be staged"):
        WinSeqTPU(lambda k, g, rows: 0, 4, 2, WinType.CB).make_core()


def test_incremental_rejected_on_device():
    core = WinSeqTPU(Reducer("sum"), 4, 2, WinType.CB).make_core()
    with pytest.raises(TypeError, match="non-incremental"):
        core.use_incremental()


@pytest.mark.parametrize("pardegree", [2, 3])
def test_win_farm_tpu(pardegree):
    keys, n = 3, 140
    got = run_windowed(
        WinFarmTPU(Reducer("sum"), 10, 4, WinType.CB, pardegree=pardegree,
                   batch_len=16),
        cb_stream_batches(keys, n))
    assert got == ref(10, 4, WinType.CB, cb_stream_batches(keys, n))


@pytest.mark.parametrize("pardegree", [2, 4])
def test_key_farm_tpu(pardegree):
    keys, n = 5, 120
    got = run_windowed(
        KeyFarmTPU(Reducer("sum"), 10, 4, WinType.CB, pardegree=pardegree,
                   batch_len=16),
        cb_stream_batches(keys, n))
    assert got == ref(10, 4, WinType.CB, cb_stream_batches(keys, n))


@pytest.mark.parametrize("plq_dev,wlq_dev", [(True, False), (False, True),
                                             (True, True)])
def test_pane_farm_tpu_stage_placement(plq_dev, wlq_dev):
    keys, n = 3, 120
    graph = []
    got = run_windowed(
        PaneFarmTPU(Reducer("sum"), Reducer("sum"), 12, 4, WinType.CB,
                    plq_degree=2, wlq_degree=2, plq_on_device=plq_dev,
                    wlq_on_device=wlq_dev, batch_len=16),
        cb_stream_batches(keys, n), graph)
    assert iv(got) == iv(ref(12, 4, WinType.CB, cb_stream_batches(keys, n)))
    if wlq_dev:
        # a built-in sum on the device is the native core's: it is handed
        # the property and keeps the reference's trigger (its docstring)
        assert dense_fire_counts(graph[0]) == []
    else:
        assert_wlq_fired_complete(graph[0], got, cb_stream_batches(keys, n),
                                  12, 4, WinType.CB, 2)


@pytest.mark.filterwarnings("ignore:resident device path accumulates")
@pytest.mark.parametrize("use_resident", [True, False],
                         ids=["resident_py", "restage"])
@pytest.mark.parametrize("wlq", [1, 2])
def test_a_device_wlq_on_the_python_cores_fires_with_its_last_pane(
        monkeypatch, wlq, use_resident):
    """``ResidentWinSeqCore`` and ``DeviceWinSeqCore`` inherit the host
    core's triggerer, the property with it: the window is enqueued for its
    launch by its last pane id."""
    from windflow_tpu.patterns.win_seq import window_cores
    monkeypatch.setenv("WF_NO_NATIVE_CORE", "1")
    keys, n = 3, 120
    graph = []
    got = run_windowed(
        PaneFarmTPU(Reducer("sum"), Reducer("sum"), 12, 4, WinType.CB,
                    plq_degree=1, wlq_degree=wlq, plq_on_device=False,
                    wlq_on_device=True, batch_len=4, flush_rows=64,
                    use_resident=use_resident),
        cb_stream_batches(keys, n), graph)
    assert iv(got) == iv(ref(12, 4, WinType.CB, cb_stream_batches(keys, n)))
    want = "ResidentWinSeqCore" if use_resident else "DeviceWinSeqCore"
    assert [type(c).__name__ for c in window_cores(graph[0])][1:] \
        == [want] * wlq
    assert_wlq_fired_complete(graph[0], got, cb_stream_batches(keys, n),
                              12, 4, WinType.CB, wlq)


@pytest.mark.parametrize("map_dev,red_dev", [(True, False), (False, True),
                                             (True, True)])
def test_win_mapreduce_tpu_stage_placement(map_dev, red_dev):
    keys, n = 3, 120
    got = iv(run_windowed(
        WinMapReduceTPU(Reducer("sum"), Reducer("sum"), 12, 4, WinType.CB,
                        map_degree=3, reduce_degree=2, map_on_device=map_dev,
                        reduce_on_device=red_dev, batch_len=16),
        cb_stream_batches(keys, n)))
    assert got == iv(ref(12, 4, WinType.CB, cb_stream_batches(keys, n)))


def test_nested_tpu_inner():
    """Nesting with device inner patterns: WF(PF_TPU)."""
    from windflow_tpu.patterns.nesting import WinFarmOf

    keys, n = 3, 140
    inner = PaneFarmTPU(Reducer("sum"), Reducer("sum"), 16, 4, WinType.CB,
                        plq_degree=2, wlq_degree=1, batch_len=16)
    got = iv(run_windowed(WinFarmOf(inner, pardegree=2),
                          cb_stream_batches(keys, n)))
    assert got == iv(ref(16, 4, WinType.CB, cb_stream_batches(keys, n)))


@pytest.mark.parametrize("op", ["min", "max"])
def test_empty_windows_match_host_identity(op):
    """A TB stream with a time gap produces empty windows; the device path
    must emit the host Reducer identity (int64 extremes), not the narrowed
    compute-dtype identity (regression: int32 iinfo leaked through)."""
    from windflow_tpu.core.tuples import Schema, batch_from_columns

    def gap_stream():
        ids = np.arange(8)
        ts = np.concatenate([ids[:4], ids[:4] + 100])
        yield batch_from_columns(Schema(value=np.int64), key=np.zeros(8),
                                 id=ids, ts=ts, value=ids + 1)

    got = run_windowed(WinSeqTPU(Reducer(op), 10, 10, WinType.TB,
                                 batch_len=4), list(gap_stream()))
    want = run_windowed(WinSeq(Reducer(op), 10, 10, WinType.TB),
                        list(gap_stream()))
    assert got == want


def test_default_device_needs_a_tpu_or_an_explicit_platform(monkeypatch):
    """A backend JAX fell back to on its own is refused; one named in
    JAX_PLATFORMS (as conftest does) is the caller's choice.  bench.py and
    chip_smoke.py need a TPU whatever the variable says."""
    from windflow_tpu.core.windows import WindowSpec
    from windflow_tpu.ops import backend

    assert backend.default_device().platform == "cpu"     # conftest named it
    assert backend.device_info() == {"platform": "cpu", "kind": "cpu",
                                     "count": 8}
    with pytest.raises(RuntimeError, match="needs a TPU, found platform"):
        backend.require_tpu()
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="no TPU.*JAX_PLATFORMS is not"):
        backend.default_device()
    with pytest.raises(RuntimeError, match="no TPU"):
        WinSeqTPU(Reducer("sum"), 4, 2, WinType.CB).make_core()
    with pytest.raises(RuntimeError, match="no TPU"):
        DeviceWinSeqCore(WindowSpec(4, 2, WinType.CB), Reducer("sum"))
