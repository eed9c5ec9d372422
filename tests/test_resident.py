"""Differential tests for the device-resident window path (ops/resident.py +
ResidentWinSeqCore): the resident core must produce byte-identical results to
the host WinSeqCore on the same stream — the same invariant the reference's
``src/sum_test_gpu/test_all_*.cpp`` asserts between CPU and GPU pattern
variants, here asserted per-row rather than on totals."""

import warnings

import numpy as np
import pytest

# pin this module to the pure-Python resident core; the native C++ core has
# its own differential suite (test_native.py)
pytestmark = pytest.mark.usefixtures("no_native")


@pytest.fixture(autouse=True)
def no_native(monkeypatch):
    monkeypatch.setenv("WF_NO_NATIVE", "1")

from windflow_tpu.core.tuples import Schema, batch_from_columns
from windflow_tpu.core.windows import PatternConfig, Role, WindowSpec, WinType
from windflow_tpu.core.winseq import WinSeqCore
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.patterns.win_seq_tpu import (DeviceWinSeqCore,
                                               ResidentWinSeqCore,
                                               make_core_for)

SCHEMA = Schema(value=np.int64)


def run_core(core, batches):
    outs = []
    for b in batches:
        r = core.process(b)
        if len(r):
            outs.append(r)
    r = core.flush()
    if len(r):
        outs.append(r)
    if not outs:
        return np.zeros(0, dtype=core._result_dtype)
    out = np.concatenate(outs)
    return np.sort(out, order=["key", "id"])


def cb_stream(n_keys, per_key, chunk=37, seed=0, lo_val=-50, hi_val=100):
    rng = np.random.default_rng(seed)
    batches = []
    for lo in range(0, per_key, chunk):
        m = min(chunk, per_key - lo)
        ids = np.repeat(np.arange(lo, lo + m), n_keys)
        keys = np.tile(np.arange(n_keys), m)
        vals = rng.integers(lo_val, hi_val, size=m * n_keys).astype(np.int64)
        batches.append(batch_from_columns(
            SCHEMA, key=keys, id=ids, ts=ids, value=vals))
    return batches


def tb_stream(n_keys, per_key, seed=0):
    rng = np.random.default_rng(seed)
    ts_all = np.sort(rng.integers(0, per_key * 2, size=per_key))
    batches = []
    for lo in range(0, per_key, 53):
        m = min(53, per_key - lo)
        tss = np.repeat(ts_all[lo:lo + m], n_keys)
        ids = np.repeat(np.arange(lo, lo + m), n_keys)
        keys = np.tile(np.arange(n_keys), m)
        vals = rng.integers(0, 100, size=m * n_keys).astype(np.int64)
        batches.append(batch_from_columns(
            SCHEMA, key=keys, id=ids, ts=tss, value=vals))
    return batches


def assert_equal_results(a, b):
    assert len(a) == len(b)
    for f in ("key", "id", "ts", "value"):
        np.testing.assert_array_equal(a[f], b[f])


@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("win,slide", [(16, 4), (8, 8), (4, 12)])
@pytest.mark.parametrize("n_keys", [1, 5])
def test_resident_cb_matches_host(op, win, slide, n_keys):
    batches = cb_stream(n_keys, 503, seed=win * 31 + slide)
    spec = WindowSpec(win, slide, WinType.CB)
    host = run_core(WinSeqCore(spec, Reducer(op)), batches)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        dev_core = make_core_for(spec, Reducer(op), batch_len=64,
                                 flush_rows=200)
    assert isinstance(dev_core, ResidentWinSeqCore)
    assert_equal_results(host, run_core(dev_core, batches))


@pytest.mark.parametrize("op", ["sum", "max"])
@pytest.mark.parametrize("win,slide", [(20, 5), (10, 10), (6, 16)])
def test_resident_tb_matches_host(op, win, slide):
    batches = tb_stream(3, 400, seed=win + slide)
    spec = WindowSpec(win, slide, WinType.TB)
    host = run_core(WinSeqCore(spec, Reducer(op)), batches)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        dev_core = make_core_for(spec, Reducer(op), batch_len=32,
                                 flush_rows=150)
    assert_equal_results(host, run_core(dev_core, batches))


def test_resident_tiny_flush_forces_rebase():
    """Aggressive flush thresholds force many ring rebases; results must
    still match (exercises the deferred-purge + rebase invariant)."""
    batches = cb_stream(4, 1000, chunk=29, seed=9)
    spec = WindowSpec(32, 8, WinType.CB)
    host = run_core(WinSeqCore(spec, Reducer("sum")), batches)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        dev_core = ResidentWinSeqCore(spec, Reducer("sum"), batch_len=16,
                                      flush_rows=64)
    assert_equal_results(host, run_core(dev_core, batches))


def test_resident_plq_renumbering():
    """PLQ role renumbers result ids (win_seq.hpp:396-405); the resident
    path must renumber identically to the host core."""
    batches = cb_stream(3, 300, seed=4)
    spec = WindowSpec(8, 8, WinType.CB)
    cfg = PatternConfig(0, 1, 8, 1, 2, 8)
    host = run_core(
        WinSeqCore(spec, Reducer("sum"), config=cfg, role=Role.PLQ), batches)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        dev_core = ResidentWinSeqCore(spec, Reducer("sum"), config=cfg,
                                      role=Role.PLQ, batch_len=32,
                                      flush_rows=100)
    assert_equal_results(host, run_core(dev_core, batches))


def test_resident_narrow_wire_dtypes():
    """Values outside int8/int16 ranges must widen the wire dtype."""
    batches = cb_stream(2, 256, seed=5, lo_val=-40000, hi_val=40000)
    spec = WindowSpec(16, 4, WinType.CB)
    host = run_core(WinSeqCore(spec, Reducer("sum")), batches)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        dev_core = ResidentWinSeqCore(spec, Reducer("sum"), batch_len=64,
                                      flush_rows=300)
    assert_equal_results(host, run_core(dev_core, batches))


def test_resident_prod_matches_host():
    """prod rides the masked gather-reduce branch; regression for pad=0
    (which made every prod window return the identity)."""
    batches = cb_stream(2, 120, chunk=17, seed=11, lo_val=1, hi_val=4)
    spec = WindowSpec(6, 3, WinType.CB)
    host = run_core(WinSeqCore(spec, Reducer("prod")), batches)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        dev_core = make_core_for(spec, Reducer("prod"), batch_len=16,
                                 flush_rows=60)
    assert isinstance(dev_core, ResidentWinSeqCore)
    assert_equal_results(host, run_core(dev_core, batches))


def test_resident_float_sum_keeps_restaging_path():
    """float32 cumsum accumulates rounding error over the ring, so float
    sums must not auto-select the resident path."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(WindowSpec(4, 2, WinType.CB),
                             Reducer("sum", dtype=np.float32))
    assert not isinstance(core, ResidentWinSeqCore)


def test_resident_64bit_compute_dtype_needs_x64():
    """compute_dtype=int64 without jax x64 would silently truncate device
    buffers to 32 bits; the core must refuse instead."""
    import jax
    if jax.config.jax_enable_x64:
        pytest.skip("x64 enabled in this environment")
    with pytest.raises(ValueError, match="x64"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            ResidentWinSeqCore(WindowSpec(4, 2, WinType.CB), Reducer("sum"),
                               compute_dtype=np.int64)


def test_resident_count_skips_device_entirely():
    """count needs no device work at all: it routes to the HOST core
    (window lengths answer it), not to a restaging device core."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(WindowSpec(4, 2, WinType.CB), Reducer("count"))
    assert not isinstance(core, (DeviceWinSeqCore, ResidentWinSeqCore))
    # max over the position field is host-free too (the archive is
    # position-ordered), for both window kinds
    from windflow_tpu.core.winseq import WinSeqCore as _Host
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        mx_tb = make_core_for(WindowSpec(10, 5, WinType.TB),
                              Reducer("max", "ts", "hi"))
        mx_cb = make_core_for(WindowSpec(10, 5, WinType.CB),
                              Reducer("max", "id", "hi"))
        mx_val = make_core_for(WindowSpec(10, 5, WinType.CB),
                               Reducer("max", "value"))
    assert not isinstance(mx_tb, (DeviceWinSeqCore, ResidentWinSeqCore))
    assert not isinstance(mx_cb, (DeviceWinSeqCore, ResidentWinSeqCore))
    assert isinstance(mx_val, ResidentWinSeqCore)  # real device work


def test_resident_rejects_incremental():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = ResidentWinSeqCore(WindowSpec(4, 2, WinType.CB),
                                  Reducer("sum"))
    with pytest.raises(TypeError):
        core.use_incremental()


# ------------------------------------------------------------- multi-stat

from windflow_tpu.core.winseq import WinSeqCore as _HostCore
from windflow_tpu.ops.functions import MultiReducer


def _assert_multi_equal(a, b, fields):
    assert len(a) == len(b)
    for f in ("key", "id", "ts") + fields:
        np.testing.assert_array_equal(a[f], b[f], err_msg=f)


@pytest.mark.parametrize("wt", [WinType.CB, WinType.TB], ids=["cb", "tb"])
def test_multi_stat_matches_host(wt):
    """count + max + sum over one shipped column in ONE fused dispatch
    must equal the host NIC evaluation of the same MultiReducer."""
    mk = lambda: MultiReducer(("count", None, "n"),
                              ("max", "value", "hi"),
                              ("sum", "value", "total"))
    spec = WindowSpec(12, 4, wt)
    stream = (cb_stream(3, 150) if wt is WinType.CB else tb_stream(3, 150))
    host = run_core(_HostCore(spec, mk()), stream)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, mk(), batch_len=16)
    assert isinstance(core, ResidentWinSeqCore)
    got = run_core(core, stream)
    assert len(host) > 0
    _assert_multi_equal(np.sort(host, order=["key", "id"]),
                        np.sort(got, order=["key", "id"]),
                        ("n", "hi", "total"))


def test_multi_stat_mesh_matches_host():
    """The same multi-stat windows over a mesh-sharded ring."""
    from windflow_tpu.parallel.mesh import make_mesh
    mk = lambda: MultiReducer(("count", None, "n"),
                              ("max", "value", "hi"))
    spec = WindowSpec(8, 8, WinType.CB)
    stream = cb_stream(7, 120)
    host = run_core(_HostCore(spec, mk()), stream)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, mk(), batch_len=8,
                             mesh=make_mesh(n_kf=4))
    got = run_core(core, stream)
    _assert_multi_equal(np.sort(host, order=["key", "id"]),
                        np.sort(got, order=["key", "id"]), ("n", "hi"))


def test_multi_stat_count_only_routes_host_forced_device_rejects():
    """A count-only MultiReducer is entirely host-free, so it routes to
    the host core; forcing the device still raises (nothing to ship)."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(WindowSpec(4, 2, WinType.CB),
                             MultiReducer(("count", None, "n")))
    assert not isinstance(core, (DeviceWinSeqCore, ResidentWinSeqCore))
    with pytest.raises(ValueError, match="non-count"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            make_core_for(WindowSpec(4, 2, WinType.CB),
                          MultiReducer(("count", None, "n")),
                          use_resident=True)


def test_multi_stat_two_fields_takes_multifield_rings():
    """Stats over different fields get one resident ring each (was a
    rejection before MultiFieldResidentExecutor existed)."""
    from windflow_tpu.ops.resident import MultiFieldResidentExecutor
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(WindowSpec(4, 2, WinType.CB),
                             MultiReducer(("sum", "value", "s"),
                                          ("max", "ts", "m")))
    assert isinstance(core.executor, MultiFieldResidentExecutor)
    assert core.executor.fields == ("value", "ts")


# ---------------------------------------------------------- latency bound

def test_max_delay_flushes_partial_batches():
    """With max_delay_ms, pending windows ship on the next process() after
    the deadline even though neither batch_len nor flush_rows was hit."""
    import time as _time
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        # use_resident=True pins the DEVICE path: this test covers the
        # device cores' force-flush timer, and the budget-aware routing
        # would otherwise (correctly) send a 1 ms budget to the host
        # core once any earlier test seeded the global service record
        core = make_core_for(WindowSpec(4, 4, WinType.CB), Reducer("sum"),
                             batch_len=1 << 20, flush_rows=1 << 20,
                             max_delay_ms=1, use_resident=True)
    b1 = cb_stream(1, 8, chunk=8)[0]
    got = core.process(b1)          # windows fire internally, none shipped
    _time.sleep(0.01)
    got2 = core.process(cb_stream(1, 8, chunk=8, seed=1)[0])
    # the delayed flush launched; poll on a later call (or drain) sees it
    deadline = _time.monotonic() + 5
    n = len(got) + len(got2)
    while n == 0 and _time.monotonic() < deadline:
        _time.sleep(0.01)
        n += len(core.process(np.zeros(0, dtype=b1.dtype)))
    assert n > 0, "max_delay did not ship the pending windows"
    core.flush()


# ---------------------------------------------------------------- multi-field

SCHEMA2 = Schema(a=np.int64, b=np.int64)


def two_field_stream(n_keys=4, per_key=400, chunk=61, seed=3):
    rng = np.random.default_rng(seed)
    batches = []
    for lo in range(0, per_key, chunk):
        m = min(chunk, per_key - lo)
        ids = np.repeat(np.arange(lo, lo + m), n_keys)
        keys = np.tile(np.arange(n_keys), m)
        batches.append(batch_from_columns(
            SCHEMA2, key=keys, id=ids, ts=ids,
            a=rng.integers(-50, 100, m * n_keys),
            b=rng.integers(0, 2000, m * n_keys)))
    return batches


def test_resident_multifield_multireducer_matches_host():
    """sum(a) + max(b) + count over per-field resident rings equals the
    host core row for row (the reference's device functors read whole POD
    tuples, win_seq_gpu.hpp:54-67 — here each field ships once into its
    own HBM ring)."""
    from windflow_tpu.ops.functions import MultiReducer
    from windflow_tpu.ops.resident import MultiFieldResidentExecutor

    def mk():
        return MultiReducer(("sum", "a", "sa"), ("max", "b", "mb"),
                            ("count", None, "n"))

    spec = WindowSpec(16, 4, WinType.CB)
    batches = two_field_stream()
    host = run_core(WinSeqCore(spec, mk()), batches)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, mk(), batch_len=32, flush_rows=100)
    assert isinstance(core, ResidentWinSeqCore)
    assert isinstance(core.executor, MultiFieldResidentExecutor)
    got = run_core(core, batches)
    assert len(host) == len(got)
    for f in ("key", "id", "ts", "sa", "mb", "n"):
        np.testing.assert_array_equal(host[f], got[f])


def test_resident_multifield_tiny_flush_rebases():
    """Multi-field rings rebuild correctly across ring rebases."""
    from windflow_tpu.ops.functions import MultiReducer

    def mk():
        return MultiReducer(("min", "a", "mn"), ("sum", "b", "sb"))

    spec = WindowSpec(12, 6, WinType.CB)
    batches = two_field_stream(n_keys=3, per_key=300, chunk=23, seed=9)
    host = run_core(WinSeqCore(spec, mk()), batches)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, mk(), batch_len=8, flush_rows=24)
    got = run_core(core, batches)
    for f in ("key", "id", "ts", "mn", "sb"):
        np.testing.assert_array_equal(host[f], got[f])


def test_resident_jax_fn_matches_restaging_and_host():
    """An arbitrary JAX window fn (sum of a*b per window) over resident
    rings (use_resident=True) equals the restaging executor and the host
    oracle."""
    import jax.numpy as jnp
    from windflow_tpu.ops.functions import FnWindowFunction
    from windflow_tpu.patterns.win_seq_tpu import JaxWindowFunction

    def dev_fn(keys, gwids, cols, mask):
        prod = jnp.where(mask, cols["a"] * cols["b"], 0)
        return jnp.sum(prod, axis=1)

    def host_fn(key, gwid, rows):
        return (int((rows["a"] * rows["b"]).sum()),)

    spec = WindowSpec(10, 5, WinType.CB)
    batches = two_field_stream(n_keys=3, per_key=250, chunk=41, seed=5,
                               )
    host = run_core(
        WinSeqCore(spec, FnWindowFunction(host_fn, {"value": np.int64})),
        batches)

    def jf():
        return JaxWindowFunction(dev_fn, fields=("a", "b"),
                                 result_fields={"value": np.int64})

    resident = run_core(
        make_core_for(spec, jf(), batch_len=32, flush_rows=90,
                      use_resident=True), batches)
    restaged = run_core(
        make_core_for(spec, jf(), batch_len=32), batches)
    assert_equal_results(host, resident)
    assert_equal_results(host, restaged)


def test_resident_jax_fn_multi_output():
    """A JAX fn returning several result columns maps them to its declared
    result_fields in order."""
    import jax.numpy as jnp
    from windflow_tpu.patterns.win_seq_tpu import JaxWindowFunction
    from windflow_tpu.ops.functions import FnWindowFunction

    def dev_fn(keys, gwids, cols, mask):
        a = jnp.where(mask, cols["a"], 0)
        return jnp.sum(a, axis=1), jnp.max(jnp.where(mask, cols["a"], -1 << 30), axis=1)

    def host_fn(key, gwid, rows):
        return (int(rows["a"].sum()),
                int(rows["a"].max()) if len(rows) else -(1 << 30))

    spec = WindowSpec(8, 8, WinType.CB)
    batches = two_field_stream(n_keys=2, per_key=200, chunk=33, seed=7)
    host = run_core(WinSeqCore(spec, FnWindowFunction(
        host_fn, {"s": np.int64, "m": np.int64})), batches)
    jf = JaxWindowFunction(dev_fn, fields=("a",),
                           result_fields={"s": np.int64, "m": np.int64})
    got = run_core(make_core_for(spec, jf, batch_len=16, flush_rows=64,
                                 use_resident=True), batches)
    for f in ("key", "id", "ts", "s", "m"):
        np.testing.assert_array_equal(host[f], got[f])


def test_resident_jax_fn_rejects_int64_ring_without_x64():
    """Declared 64-bit ring dtypes need jax x64 (otherwise jax silently
    truncates the ring to 32 bits)."""
    import jax
    from windflow_tpu.patterns.win_seq_tpu import JaxWindowFunction
    if jax.config.jax_enable_x64:
        pytest.skip("x64 enabled in this process")
    jf = JaxWindowFunction(lambda k, g, c, m: c["a"].sum(axis=1),
                           fields=("a",), result_fields={"v": np.int64},
                           field_dtypes={"a": np.int64})
    with pytest.raises(ValueError, match="x64"):
        make_core_for(WindowSpec(4, 2, WinType.CB), jf, use_resident=True)


def test_resident_float_column_into_int_ring_rejected():
    """A float column shipped into a default int32 ring must raise, not
    silently truncate (declare field_dtypes for float data)."""
    from windflow_tpu.patterns.win_seq_tpu import JaxWindowFunction
    schema = Schema(x=np.float64)
    b = batch_from_columns(schema, key=np.zeros(8), id=np.arange(8),
                           ts=np.arange(8),
                           x=np.full(8, 0.5, dtype=np.float64))
    jf = JaxWindowFunction(lambda k, g, c, m: c["x"].sum(axis=1),
                           fields=("x",), result_fields={"v": np.float64})
    core = make_core_for(WindowSpec(4, 4, WinType.CB), jf,
                         batch_len=4, flush_rows=4, use_resident=True)
    with pytest.raises(ValueError, match="float column"):
        core.process(b)
        core.flush()


def test_host_free_tb_aggregate_routes_to_host_core():
    """COUNT + MAX(ts) over TB windows has no device-worthy compute
    (counts from lens, max-ts from the ts-ordered archive): make_core_for
    routes it to the vectorised host core; use_resident=True still forces
    the device ring (wire benchmarking)."""
    from windflow_tpu.core.vecinc import VecIncTumblingCore
    from windflow_tpu.ops.functions import MultiReducer

    def agg():
        return MultiReducer(("count", None, "n"), ("max", "ts", "hi"))

    spec_args = (1000, 1000, WinType.TB)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(WindowSpec(*spec_args), agg())
        forced = make_core_for(WindowSpec(*spec_args), agg(),
                               use_resident=True)
        cb = make_core_for(WindowSpec(1000, 1000, WinType.CB), agg())
    assert isinstance(core, VecIncTumblingCore)     # tumbling TB -> vec
    assert isinstance(forced, ResidentWinSeqCore)   # explicit device
    # CB windows: ts is NOT the position field, max(ts) needs real work
    assert isinstance(cb, ResidentWinSeqCore)


def test_acc_dtype_warning_gated_on_value_range():
    """The int32-accumulate wrap warning must not fire
    when the Reducer's declared value_range plus the CB window length prove
    the results fit (bench/YSB configs run warning-clean); it still fires
    when no range is declared or the range genuinely overflows."""
    import warnings
    from windflow_tpu.core.windows import WindowSpec, WinType
    from windflow_tpu.patterns import win_seq_tpu
    from windflow_tpu.patterns.win_seq_tpu import select_acc_dtype

    spec = WindowSpec(256, 64, WinType.CB)
    tb = WindowSpec(256, 64, WinType.TB)

    def fires(reducer, spec_):
        win_seq_tpu._ACC_WARNED.clear()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            acc = select_acc_dtype(reducer, None, spec_)
        assert acc == np.dtype(np.int32)
        return any("wrap" in str(x.message) for x in w)

    # provably safe: |sum| <= 256 * 100 << 2^31
    assert not fires(Reducer("sum", value_range=(0, 100)), spec)
    # min/max never leave the input range, even for TB windows
    assert not fires(Reducer("max", value_range=(-7, 10 ** 6)), tb)
    # no declared range -> warn (the pre-r3 behavior)
    assert fires(Reducer("sum"), spec)
    # TB sum: row count unbounded, range proves nothing -> warn
    assert fires(Reducer("sum", value_range=(0, 100)), tb)
    # declared range too wide for the window length -> warn
    assert fires(Reducer("sum", value_range=(0, 2 ** 40)), spec)


def test_pos_max_split_ships_single_column():
    """r3: COUNT + MAX(ts) + SUM(revenue) over TB windows must ship ONLY
    the revenue column — max-ts is free from the ts-ordered archive, so
    the executor is the single-field ring, not multi-field — and results
    must match the host core."""
    from windflow_tpu.core.tuples import Schema, batch_from_columns
    from windflow_tpu.core.winseq import WinSeqCore
    from windflow_tpu.ops.functions import MultiReducer
    from windflow_tpu.ops.resident import (MultiFieldResidentExecutor,
                                           ResidentWindowExecutor)

    schema = Schema(revenue=np.int64)
    mk = MultiReducer(("count", None, "n"), ("max", "ts", "hi"),
                      ("sum", "revenue", "rev"))
    spec = WindowSpec(100, 100, WinType.TB)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, mk)
    assert isinstance(core, ResidentWinSeqCore)
    assert isinstance(core.executor, ResidentWindowExecutor)
    assert not isinstance(core.executor, MultiFieldResidentExecutor)
    assert core._ship_fields == ("revenue",)
    assert [p.out_field for p in core._pos_max_parts] == ["hi"]

    rng = np.random.default_rng(3)
    nk, per = 4, 300
    batches = []
    for lo in range(0, per, 60):
        m = min(60, per - lo)
        ts = np.repeat(np.arange(lo, lo + m) * 7, nk)
        batches.append(batch_from_columns(
            schema, key=np.tile(np.arange(nk), m),
            id=np.repeat(np.arange(lo, lo + m), nk), ts=ts,
            revenue=rng.integers(1, 98, m * nk)))

    def run(c):
        outs = [c.process(b) for b in batches]
        outs.append(c.flush())
        outs = [o for o in outs if len(o)]
        return np.sort(np.concatenate(outs), order=["key", "id"])

    got = run(core)
    want = run(WinSeqCore(spec, MultiReducer(
        ("count", None, "n"), ("max", "ts", "hi"),
        ("sum", "revenue", "rev"))))
    assert len(got) == len(want)
    for f in ("key", "id", "ts", "n", "hi", "rev"):
        np.testing.assert_array_equal(got[f], want[f], err_msg=f)
