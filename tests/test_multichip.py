"""Multi-chip wiring of the streaming patterns, on the virtual 8-device CPU
mesh (conftest): farm workers own one device each (the reference gives each
GPU worker its own stream/device, win_farm_gpu.hpp:132-168), and the
mesh-resident executor serves every key group from ONE sharded dispatch
(ring P(kf, None), ops/resident.py:_OnMesh)."""

import numpy as np
import pytest

import jax

from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.parallel.mesh import make_mesh
from windflow_tpu.patterns.win_seq import WinSeq
from windflow_tpu.patterns.win_seq_tpu import (KeyFarmTPU, WinFarmTPU,
                                               WinSeqTPU)

from test_farms import cb_stream_batches, run_windowed, tb_stream_batches

KEYS, N = 8, 120
WIN, SLIDE = 12, 4


def stream(wt):
    return (cb_stream_batches(KEYS, N) if wt is WinType.CB
            else tb_stream_batches(KEYS, N))


def worker_devices(farm):
    """Every device owning a ring/executor across the farm's replicas."""
    devs = set()
    for r in farm.replicas():
        core = r.core
        ex = getattr(core, "executor", None)
        if ex is not None:
            devs.add(ex.device)
        for sub in getattr(core, "executors", []):
            devs.add(sub.device)
    return devs


@pytest.mark.parametrize("farm_cls", [KeyFarmTPU, WinFarmTPU],
                         ids=["kf", "wf"])
def test_farm_workers_spread_over_devices(farm_cls):
    n_dev = len(jax.devices())
    assert n_dev == 8, "conftest must provide the virtual 8-device mesh"
    farm = farm_cls(Reducer("sum"), WIN, SLIDE, WinType.CB, pardegree=8,
                    batch_len=16)
    devs = worker_devices(farm)
    assert len(devs) == 8, (
        f"pardegree=8 farm placed rings on {len(devs)} devices, want 8")


def test_farm_device_list_pins_workers():
    """An explicit device list spreads over exactly those devices."""
    pick = jax.devices()[:2]
    farm = KeyFarmTPU(Reducer("sum"), WIN, SLIDE, WinType.CB, pardegree=4,
                      batch_len=16, device=pick)
    assert worker_devices(farm) == set(pick)


def test_farm_single_device_pins_all_workers():
    d = jax.devices()[3]
    farm = KeyFarmTPU(Reducer("sum"), WIN, SLIDE, WinType.CB, pardegree=4,
                      batch_len=16, device=d)
    assert worker_devices(farm) == {d}


@pytest.mark.parametrize("wt", [WinType.CB, WinType.TB], ids=["cb", "tb"])
@pytest.mark.parametrize("farm_cls", [KeyFarmTPU, WinFarmTPU],
                         ids=["kf", "wf"])
def test_spread_farm_matches_seq(farm_cls, wt):
    """Differential: an 8-worker farm spread over 8 devices produces the
    host Win_Seq totals with per-key in-order delivery."""
    ref = run_windowed(WinSeq(Reducer("sum"), WIN, SLIDE, wt), stream(wt))
    got = run_windowed(
        farm_cls(Reducer("sum"), WIN, SLIDE, wt, pardegree=8, batch_len=16),
        stream(wt))
    assert got.keys() == ref.keys()
    for k in ref:
        assert got[k] == ref[k], f"key {k} mismatch"


# ---------------------------------------------------------- mesh-resident

@pytest.mark.parametrize("wt", [WinType.CB, WinType.TB], ids=["cb", "tb"])
@pytest.mark.parametrize("op", ["sum", "max"])
def test_mesh_resident_matches_seq(wt, op):
    """One WinSeqTPU, ring sharded P(kf, None) over a 4-device mesh: one
    dispatch serves all key groups; totals equal the host core's."""
    mesh = make_mesh(n_kf=4)
    ref = run_windowed(WinSeq(Reducer(op), WIN, SLIDE, wt), stream(wt))
    got = run_windowed(
        WinSeqTPU(Reducer(op), WIN, SLIDE, wt, batch_len=16, mesh=mesh),
        stream(wt))
    assert got == ref


def test_mesh_resident_uses_all_mesh_devices():
    """Every mesh device must hold live archive rows — the stride mapping
    (row r -> shard r % S) balances keys over the shards, not just the
    NamedSharding's formal block count."""
    mesh = make_mesh(n_kf=8)
    core = WinSeqTPU(Reducer("sum"), WIN, SLIDE, WinType.CB, batch_len=16,
                     mesh=mesh).make_core()
    outs = [core.process(b) for b in stream(WinType.CB)]
    outs.append(core.flush())
    assert sum(len(o) for o in outs) > 0
    ring = core.executor._ring
    assert ring is not None
    shards = list(ring.addressable_shards)
    devs = {s.device for s in shards}
    assert len(devs) == 8
    # 8 keys over 8 shards: each shard owns exactly one live key's rows
    occupancy = [bool(np.asarray(s.data).any()) for s in shards]
    assert all(occupancy), f"idle shards: {occupancy}"


def test_mesh_resident_rejects_non_monoid():
    mesh = make_mesh(n_kf=2)
    with pytest.raises(ValueError, match="resident-path Reducer"):
        WinSeqTPU(Reducer("count"), WIN, SLIDE, WinType.CB,
                  mesh=mesh).make_core()


def test_mesh_resident_many_keys_rebase():
    """Key cardinality beyond the initial ring forces rebases across the
    sharded ring; totals must survive them."""
    mesh = make_mesh(n_kf=4)
    keys, n = 37, 60   # not a multiple of the shard count
    ref = run_windowed(WinSeq(Reducer("sum"), 8, 8, WinType.CB),
                       cb_stream_batches(keys, n))
    got = run_windowed(
        WinSeqTPU(Reducer("sum"), 8, 8, WinType.CB, batch_len=8,
                  flush_rows=64, mesh=mesh),
        cb_stream_batches(keys, n))
    assert got == ref


def test_mesh_routes_through_native_core():
    """r2 weak #3: make_core_for(mesh=) must ride the C++ bookkeeping when
    the native lib is available — not re-pay the Python hot loop on the
    multi-chip path."""
    from windflow_tpu import native as native_mod
    if native_mod.enabled() is None:
        pytest.skip("native library unavailable")
    from windflow_tpu.ops.resident import ResidentWindowExecutor
    from windflow_tpu.patterns.native_core import NativeResidentCore
    mesh = make_mesh(n_kf=4)
    core = WinSeqTPU(Reducer("sum"), WIN, SLIDE, WinType.CB,
                     mesh=mesh).make_core()
    assert isinstance(core, NativeResidentCore)
    assert type(core.executors[0]) is ResidentWindowExecutor
    assert core.executors[0].mesh is mesh


def test_mesh_multistat_matches_host():
    """Multi-stat MultiReducer (sum + max over one field, plus count) on
    the sharded ring: every stat evaluates in ONE mesh dispatch (r2 weak
    #3 'single-stat only' resolved)."""
    from windflow_tpu.core.windows import WindowSpec
    from windflow_tpu.core.winseq import WinSeqCore
    from windflow_tpu.ops.functions import MultiReducer
    from windflow_tpu.patterns.win_seq_tpu import make_core_for
    mk = MultiReducer(("count", None, "cnt"), ("sum", "value", "sm"),
                      ("max", "value", "mx"))
    spec = WindowSpec(WIN, SLIDE, WinType.CB)
    mesh = make_mesh(n_kf=4)
    batches = cb_stream_batches(11, 90)

    def run_core(core):
        outs = [core.process(b) for b in batches]
        outs.append(core.flush())
        outs = [o for o in outs if len(o)]
        res = np.concatenate(outs)
        return np.sort(res, order=["key", "id"])

    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        got = run_core(make_core_for(spec, mk, mesh=mesh, batch_len=16))
    want = run_core(WinSeqCore(spec, mk))
    assert len(got) == len(want)
    for f in ("key", "id", "ts", "cnt", "sm", "mx"):
        np.testing.assert_array_equal(got[f], want[f], err_msg=f)


def test_mesh_regular_descriptors_engage_and_match():
    """The native-mesh core compresses steady CB windows into per-key
    arithmetic descriptors and dispatches them through a mesh-placed
    ResidentWindowExecutor.launch_regular (r2 weak #3 'no regular-descriptor
    compression' resolved) — asserted to actually engage, with totals
    equal to the host core."""
    from windflow_tpu import native as native_mod
    if native_mod.enabled() is None:
        pytest.skip("native library unavailable")
    from windflow_tpu.ops.resident import ResidentWindowExecutor
    mesh = make_mesh(n_kf=4)
    calls = []
    orig = ResidentWindowExecutor.launch_regular

    def counting(self, *a, **kw):
        calls.append(self.mesh)
        return orig(self, *a, **kw)

    ResidentWindowExecutor.launch_regular = counting
    try:
        ref = run_windowed(WinSeq(Reducer("sum"), WIN, SLIDE, WinType.CB),
                           stream(WinType.CB))
        got = run_windowed(
            WinSeqTPU(Reducer("sum"), WIN, SLIDE, WinType.CB, batch_len=16,
                      flush_rows=128, mesh=mesh),
            stream(WinType.CB))
    finally:
        ResidentWindowExecutor.launch_regular = orig
    assert got == ref
    assert calls and all(m is mesh for m in calls), \
        "regular-descriptor mesh dispatch never engaged"


def test_mesh_multifield_matches_host():
    """Multi-FIELD MultiReducer (stats over two different payload fields)
    on per-field mesh-sharded rings: the general whole-tuple functor
    contract (win_seq_gpu.hpp:54-67) distributed over the kf axis
    (a mesh-placed MultiFieldResidentExecutor)."""
    from windflow_tpu.core.tuples import Schema, batch_from_columns
    from windflow_tpu.core.windows import WindowSpec
    from windflow_tpu.core.winseq import WinSeqCore
    from windflow_tpu.ops.functions import MultiReducer
    from windflow_tpu.ops.resident import MultiFieldResidentExecutor
    from windflow_tpu.patterns.win_seq_tpu import make_core_for

    schema = Schema(a=np.int64, b=np.int64)
    rng = np.random.default_rng(17)
    batches = []
    for lo in range(0, 96, 23):
        m = min(23, 96 - lo)
        ids = np.repeat(np.arange(lo, lo + m), 11)
        ks = np.tile(np.arange(11), m)
        batches.append(batch_from_columns(
            schema, key=ks, id=ids, ts=ids,
            a=rng.integers(0, 100, m * 11), b=rng.integers(0, 60, m * 11)))

    mf = MultiReducer(("count", None, "cnt"), ("sum", "a", "sa"),
                      ("max", "b", "mb"), ("min", "a", "na"))
    spec = WindowSpec(WIN, SLIDE, WinType.CB)
    mesh = make_mesh(n_kf=4)

    def run_core(core):
        outs = [core.process(b) for b in batches]
        outs.append(core.flush())
        outs = [o for o in outs if len(o)]
        res = np.concatenate(outs)
        return np.sort(res, order=["key", "id"])

    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, mf, mesh=mesh, batch_len=16)
        assert type(core.executor) is MultiFieldResidentExecutor
        assert core.executor.mesh is mesh
        # r5: the pod shape keeps the C++ hot loop for rich aggregates
        # too — mesh multi-field rides NativeResidentCore when the
        # native library is available (Python core otherwise)
        from windflow_tpu.native import enabled
        if enabled() is not None:
            from windflow_tpu.patterns.native_core import \
                NativeResidentCore
            assert isinstance(core, NativeResidentCore) and core._multi
        got = run_core(core)
    want = run_core(WinSeqCore(spec, mf))
    assert len(got) == len(want)
    for f in ("key", "id", "ts", "cnt", "sa", "mb", "na"):
        np.testing.assert_array_equal(got[f], want[f], err_msg=f)


def test_mesh_jax_fn_matches_host():
    """An arbitrary batched JaxWindowFunction over two fields evaluates on
    the mesh-sharded per-field rings — one SPMD dispatch per flush."""
    from windflow_tpu.core.tuples import Schema, batch_from_columns
    from windflow_tpu.core.windows import WindowSpec
    from windflow_tpu.core.winseq import WinSeqCore
    from windflow_tpu.ops.functions import WindowFunction
    from windflow_tpu.patterns.win_seq_tpu import (JaxWindowFunction,
                                                   make_core_for)

    schema = Schema(a=np.int64, b=np.int64)
    batches = []
    for lo in range(0, 72, 24):
        ids = np.repeat(np.arange(lo, lo + 24), 5)
        ks = np.tile(np.arange(5), 24)
        batches.append(batch_from_columns(
            schema, key=ks, id=ids, ts=ids, a=ids % 13, b=(ids * 5) % 7))

    class HostDot(WindowFunction):
        result_fields = {"dot": np.int64}
        required_fields = ("a", "b")

        def apply(self, key, gwid, rows):
            return (int((rows["a"] * rows["b"]).sum()),)

    import jax.numpy as jnp

    def fn(keys, gwids, cols, mask):
        return (jnp.sum(jnp.where(mask, cols["a"] * cols["b"], 0), axis=1),)

    jf = JaxWindowFunction(fn, fields=("a", "b"),
                           result_fields={"dot": np.int64})
    spec = WindowSpec(WIN, SLIDE, WinType.CB)
    mesh = make_mesh(n_kf=4)

    def run_core(core):
        outs = [core.process(b) for b in batches]
        outs.append(core.flush())
        outs = [o for o in outs if len(o)]
        res = np.concatenate(outs)
        return np.sort(res, order=["key", "id"])

    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        got = run_core(make_core_for(spec, jf, mesh=mesh, batch_len=16))
    want = run_core(WinSeqCore(spec, HostDot()))
    assert len(got) == len(want)
    for f in ("key", "id", "ts", "dot"):
        np.testing.assert_array_equal(got[f], want[f], err_msg=f)


def test_mesh_with_host_shards_matches_host():
    """Host key-sharding composes with mesh execution (r3 weak #5): each
    shard's C++ bookkeeping feeds its OWN P(kf, None)-sharded ring, so a
    multicore host parallelises the hot loop while every dispatch still
    serves all key groups."""
    from windflow_tpu import native as native_mod
    if native_mod.enabled() is None:
        pytest.skip("native library unavailable")
    from windflow_tpu.core.windows import WindowSpec
    from windflow_tpu.core.winseq import WinSeqCore
    from windflow_tpu.patterns.win_seq_tpu import make_core_for

    spec = WindowSpec(WIN, SLIDE, WinType.CB)
    mesh = make_mesh(n_kf=4)
    batches = cb_stream_batches(13, 110)

    def run_core(core):
        outs = [core.process(b) for b in batches]
        outs.append(core.flush())
        outs = [o for o in outs if len(o)]
        res = np.concatenate(outs)
        return np.sort(res, order=["key", "id"])

    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, Reducer("sum"), mesh=mesh, shards=2,
                             batch_len=16)
        assert len(core.executors) == 2
        assert all(ex.mesh is mesh for ex in core.executors)
        got = run_core(core)
    want = run_core(WinSeqCore(WindowSpec(WIN, SLIDE, WinType.CB),
                               Reducer("sum")))
    assert len(got) == len(want)
    for f in ("key", "id", "ts", "value"):
        np.testing.assert_array_equal(got[f], want[f], err_msg=f)


def test_mesh_multifield_scatter_dispatch_economics():
    """Perf-shaped exercise of the mesh placement's S-way scatter (a
    MultiFieldResidentExecutor) at realistic cardinality: 256 keys
    sharded over a 4-device kf mesh, ~100k rows, two payload fields.
    Pins the dispatch-count behavior — ONE fused SPMD dispatch per
    flush, NOT one per shard or per field — alongside correctness at
    this scale (the small differential above cannot see the economics)."""
    from windflow_tpu.core.tuples import Schema, batch_from_columns
    from windflow_tpu.core.windows import WindowSpec
    from windflow_tpu.core.vecinc import VecIncSlidingCore
    from windflow_tpu.ops.functions import MultiReducer
    from windflow_tpu.ops import resident
    from windflow_tpu.ops.resident import MultiFieldResidentExecutor
    from windflow_tpu.patterns.win_seq_tpu import make_core_for

    NK, ROWS, CHUNK = 256, 98_304, 1 << 14
    schema = Schema(a=np.int64, b=np.int64)
    rng = np.random.default_rng(23)
    batches = []
    per = CHUNK // NK
    for lo in range(0, ROWS // NK, per):
        ids = np.repeat(np.arange(lo, lo + per), NK)
        ks = np.tile(np.arange(NK), per)
        batches.append(batch_from_columns(
            schema, key=ks, id=ids, ts=ids,
            a=rng.integers(0, 100, per * NK), b=rng.integers(0, 60, per * NK)))

    mf = MultiReducer(("sum", "a", "sa"), ("max", "b", "mb"))
    spec = WindowSpec(64, 16, WinType.CB)
    mesh = make_mesh(n_kf=4)

    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, mf, mesh=mesh, batch_len=1 << 12,
                             flush_rows=1 << 15)
        assert type(core.executor) is MultiFieldResidentExecutor
        assert core.executor.mesh is mesh
        resident.stats_snapshot(reset=True)
        outs = [core.process(b) for b in batches]
        outs.append(core.flush())
        diag = resident.stats_snapshot(reset=True)
    got = np.concatenate([o for o in outs if len(o)])
    got = np.sort(got, order=["key", "id"])

    # economics: ~ROWS/flush_rows natural flushes; the scatter path must
    # not multiply that by fields (2) or shards (4) — one fused SPMD
    # dispatch per flush, +2 slack for the EOS tail
    flushes = -(-ROWS // (1 << 15))           # ceil
    assert 1 <= diag["dispatches"] <= flushes + 2, diag

    # correctness at scale, against the vectorised host core
    host = VecIncSlidingCore(spec, mf)
    want = [host.process(b) for b in batches]
    want.append(host.flush())
    want = np.concatenate([w for w in want if len(w)])
    want = np.sort(want, order=["key", "id"])
    assert len(got) == len(want)
    for f in ("key", "id", "sa", "mb"):
        np.testing.assert_array_equal(got[f], want[f], err_msg=f)
