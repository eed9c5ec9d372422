"""Differential: VecIncTumblingCore vs the reference per-key WinSeqCore.

The vectorised core must be row-for-row identical (per key) to WinSeqCore
on tumbling windows for every role / config / reducer / disorder mix it
claims to support (vec_core_supported)."""

import numpy as np
import pytest

from windflow_tpu.core.tuples import MARKER_FIELD, Schema, batch_from_columns
from windflow_tpu.core.vecinc import VecIncTumblingCore, vec_core_supported
from windflow_tpu.core.windows import PatternConfig, Role, WindowSpec, WinType
from windflow_tpu.core.winseq import WinSeqCore
from windflow_tpu.ops.functions import MultiReducer, Reducer

SCHEMA = Schema(value=np.int64)


def make_stream(rng, n_keys, n_chunks, rows_per_chunk, *, ooo_frac=0.0,
                gaps=False, markers_at_end=True):
    """Chunks of interleaved keyed rows with optional disorder and id gaps;
    the final chunk optionally carries per-key EOS markers (each key's last
    row replayed with the marker flag, as the farm emitters do)."""
    next_id = {k: 0 for k in range(n_keys)}
    last_row = {}
    chunks = []
    for _ in range(n_chunks):
        keys = rng.integers(0, n_keys, rows_per_chunk)
        ids = np.empty(rows_per_chunk, dtype=np.int64)
        for i, k in enumerate(keys):
            step = int(rng.integers(1, 4)) if gaps else 1
            ids[i] = next_id[k]
            next_id[k] += step
        if ooo_frac:
            flip = rng.random(rows_per_chunk) < ooo_frac
            ids[flip] = np.maximum(ids[flip] - rng.integers(1, 6, flip.sum()), 0)
        ts = ids * 3 + keys
        vals = rng.integers(-5, 50, rows_per_chunk)
        b = batch_from_columns(SCHEMA, key=keys, id=ids, ts=ts, value=vals)
        for i in range(rows_per_chunk):
            k = int(keys[i])
            if k not in last_row or ids[i] >= int(last_row[k]["id"]):
                last_row[k] = b[i].copy()
        chunks.append(b)
    if markers_at_end and last_row:
        mk = np.stack([last_row[k] for k in sorted(last_row)])
        mk[MARKER_FIELD] = True
        chunks.append(mk)
    return chunks


def run_calls(core, chunks):
    """The result batch of every ``process`` call, then the flush's."""
    return [core.process(c) for c in chunks] + [core.flush()]


def run_core(core, chunks):
    outs = [o for o in run_calls(core, chunks) if len(o)]
    return (np.concatenate(outs) if outs
            else np.zeros(0, dtype=core.result_schema.dtype()))


def per_key_sorted(res):
    """Row sequences grouped per key (cross-key emission order is not part
    of the contract — the reference's is thread-timing dependent too)."""
    out = {}
    for k in np.unique(res["key"]):
        out[int(k)] = res[res["key"] == k]
    return out


def assert_equivalent(a, b):
    ka, kb = per_key_sorted(a), per_key_sorted(b)
    assert set(ka) == set(kb)
    for k in ka:
        ra, rb = ka[k], kb[k]
        assert len(ra) == len(rb), f"key {k}: {len(ra)} vs {len(rb)} rows"
        for f in ra.dtype.names:
            np.testing.assert_array_equal(
                ra[f], rb[f], err_msg=f"key {k} field {f}")


CASES = [
    dict(),                                   # in-order, dense
    dict(ooo_frac=0.15),                      # out-of-order drops
    dict(gaps=True),                          # id gaps -> empty fired windows
    dict(gaps=True, ooo_frac=0.1),
    dict(markers_at_end=False),               # no EOS markers
]


@pytest.mark.parametrize("win_type", [WinType.CB, WinType.TB])
@pytest.mark.parametrize("case", range(len(CASES)))
def test_vec_vs_ref_seq(win_type, case):
    rng = np.random.default_rng(100 + case)
    spec = WindowSpec(4, 4, win_type)
    chunks = make_stream(rng, 37, 6, 200, **CASES[case])
    red = Reducer("sum")
    ref = WinSeqCore(spec, red).use_incremental()
    vec = VecIncTumblingCore(spec, red)
    assert_equivalent(run_core(vec, chunks), run_core(ref, chunks))


@pytest.mark.parametrize("role,map_indexes", [
    (Role.MAP, (1, 3)), (Role.PLQ, (0, 1)), (Role.WLQ, (0, 1)),
    (Role.REDUCE, (0, 1)),
])
def test_vec_vs_ref_roles(role, map_indexes):
    rng = np.random.default_rng(7)
    spec = WindowSpec(5, 5, WinType.CB)
    cfg = PatternConfig(id_outer=1, n_outer=2, slide_outer=10,
                        id_inner=1, n_inner=3, slide_inner=5)
    chunks = make_stream(rng, 23, 5, 150, gaps=True)
    red = Reducer("max")
    ref = WinSeqCore(spec, red, config=cfg, role=role,
                     map_indexes=map_indexes).use_incremental()
    vec = VecIncTumblingCore(spec, red, config=cfg, role=role,
                             map_indexes=map_indexes)
    assert_equivalent(run_core(vec, chunks), run_core(ref, chunks))


@pytest.mark.parametrize("op", ["sum", "min", "max", "prod", "count"])
def test_vec_vs_ref_ops(op):
    rng = np.random.default_rng(11)
    spec = WindowSpec(3, 3, WinType.CB)
    chunks = make_stream(rng, 11, 4, 90, ooo_frac=0.1)
    red = Reducer(op, out_field="r")
    ref = WinSeqCore(spec, red).use_incremental()
    vec = VecIncTumblingCore(spec, red)
    assert_equivalent(run_core(vec, chunks), run_core(ref, chunks))


def test_vec_vs_ref_multireducer():
    rng = np.random.default_rng(13)
    spec = WindowSpec(6, 6, WinType.TB)
    chunks = make_stream(rng, 19, 5, 120, gaps=True)
    mk = MultiReducer(("count", None, "cnt"), ("max", "value", "mx"),
                      ("sum", "value", "sm"))
    ref = WinSeqCore(spec, mk).use_incremental()
    vec = VecIncTumblingCore(spec, mk)
    assert_equivalent(run_core(vec, chunks), run_core(ref, chunks))


def test_vec_core_gate():
    """make_core picks the vectorised core exactly when supported:
    tumbling + sliding (W <= 64) vectorise; hopping and extreme
    win/slide ratios stay on the general per-key core."""
    from windflow_tpu.core.vecinc import VecIncSlidingCore
    from windflow_tpu.patterns.win_seq import WinSeq
    assert vec_core_supported(WindowSpec(4, 4, WinType.CB), Reducer("sum"))
    assert vec_core_supported(WindowSpec(8, 4, WinType.CB), Reducer("sum"))
    assert not vec_core_supported(WindowSpec(4, 8, WinType.CB),
                                  Reducer("sum"))         # hopping
    assert not vec_core_supported(WindowSpec(256, 1, WinType.CB),
                                  Reducer("sum"))         # W > 64
    assert isinstance(WinSeq(Reducer("sum"), 4, 4, WinType.CB).make_core(),
                      VecIncTumblingCore)
    from windflow_tpu.core.vecinc import LazySlidingCore
    assert isinstance(WinSeq(Reducer("sum"), 8, 4, WinType.CB).make_core(),
                      LazySlidingCore)
    assert isinstance(WinSeq(Reducer("sum"), 4, 8, WinType.CB).make_core(),
                      WinSeqCore)


def test_vec_initial_id_drop():
    """Rows below a worker's initial position are dropped identically."""
    rng = np.random.default_rng(17)
    spec = WindowSpec(4, 4, WinType.CB)
    cfg = PatternConfig(id_outer=1, n_outer=3, slide_outer=4,
                        id_inner=0, n_inner=1, slide_inner=4)
    chunks = make_stream(rng, 9, 4, 80)
    red = Reducer("sum")
    ref = WinSeqCore(spec, red, config=cfg).use_incremental()
    vec = VecIncTumblingCore(spec, red, config=cfg)
    assert_equivalent(run_core(vec, chunks), run_core(ref, chunks))


def test_vec_disorder_stays_vectorised_at_high_cardinality():
    """Sustained out-of-order input at 2e4 keys must not collapse into
    per-key Python (the segmented doubling running-max keeps the drop
    pass O(rows log rows)); results stay identical to the reference."""
    import time
    rng = np.random.default_rng(23)
    spec = WindowSpec(4, 4, WinType.CB)
    n_keys, rows = 20_000, 5
    chunks = []
    next_id = np.zeros(n_keys, dtype=np.int64)
    for _ in range(rows):
        keys = np.arange(n_keys)
        ids = next_id.copy()
        next_id += 1
        flip = rng.random(n_keys) < 0.15          # 15% disorder every chunk
        ids[flip] = np.maximum(ids[flip] - rng.integers(1, 4, flip.sum()), 0)
        chunks.append(batch_from_columns(
            SCHEMA, key=keys, id=ids, ts=ids * 2, value=ids + keys % 5))
    red = Reducer("sum")
    t0 = time.perf_counter()
    got = run_core(VecIncTumblingCore(spec, red), chunks)
    dt = time.perf_counter() - t0
    want = run_core(WinSeqCore(spec, red).use_incremental(), chunks)
    assert_equivalent(got, want)
    assert dt < 5, f"disordered vec path took {dt:.1f}s at {n_keys} keys"


# ---------------------------------------------------------------- sliding

from windflow_tpu.core.vecinc import VecIncSlidingCore  # noqa: E402


@pytest.mark.parametrize("win,slide", [(8, 4), (6, 2), (7, 3), (256, 64)])
@pytest.mark.parametrize("win_type", [WinType.CB, WinType.TB])
@pytest.mark.parametrize("case", range(len(CASES)))
def test_vec_sliding_vs_ref_seq(win, slide, win_type, case):
    rng = np.random.default_rng(300 + case + win * 7)
    spec = WindowSpec(win, slide, win_type)
    chunks = make_stream(rng, 17, 6, 200, **CASES[case])
    red = Reducer("sum")
    ref = WinSeqCore(spec, red).use_incremental()
    vec = VecIncSlidingCore(spec, red)
    assert_equivalent(run_core(vec, chunks), run_core(ref, chunks))


@pytest.mark.parametrize("role,map_indexes", [
    (Role.MAP, (1, 3)), (Role.PLQ, (0, 1)), (Role.WLQ, (0, 1)),
])
def test_vec_sliding_vs_ref_roles(role, map_indexes):
    rng = np.random.default_rng(31)
    spec = WindowSpec(10, 4, WinType.CB)
    cfg = PatternConfig(id_outer=1, n_outer=2, slide_outer=8,
                        id_inner=1, n_inner=3, slide_inner=4)
    chunks = make_stream(rng, 13, 5, 150, gaps=True)
    red = Reducer("max")
    ref = WinSeqCore(spec, red, config=cfg, role=role,
                     map_indexes=map_indexes).use_incremental()
    vec = VecIncSlidingCore(spec, red, config=cfg, role=role,
                            map_indexes=map_indexes)
    assert_equivalent(run_core(vec, chunks), run_core(ref, chunks))


@pytest.mark.parametrize("op", ["sum", "min", "max", "prod", "count"])
def test_vec_sliding_vs_ref_ops(op):
    rng = np.random.default_rng(37)
    spec = WindowSpec(9, 3, WinType.CB)
    chunks = make_stream(rng, 11, 4, 90, ooo_frac=0.1)
    red = Reducer(op, out_field="r")
    ref = WinSeqCore(spec, red).use_incremental()
    vec = VecIncSlidingCore(spec, red)
    assert_equivalent(run_core(vec, chunks), run_core(ref, chunks))


def test_vec_sliding_multireducer():
    rng = np.random.default_rng(41)
    spec = WindowSpec(12, 5, WinType.TB)
    chunks = make_stream(rng, 19, 5, 120, gaps=True)
    mk = MultiReducer(("count", None, "cnt"), ("max", "value", "mx"),
                      ("sum", "value", "sm"))
    ref = WinSeqCore(spec, mk).use_incremental()
    vec = VecIncSlidingCore(spec, mk)
    assert_equivalent(run_core(vec, chunks), run_core(ref, chunks))


def test_vec_sliding_high_cardinality_budget():
    """A 1e5-key SLIDING differential
    must complete in seconds — the general core's per-key-group path
    collapses here; the lane core is O(W * rows log rows)."""
    import time
    rng = np.random.default_rng(43)
    spec = WindowSpec(16, 4, WinType.CB)
    n_keys, n_chunks = 100_000, 8
    chunks = []
    for c in range(n_chunks):
        keys = np.arange(n_keys)
        ids = np.full(n_keys, c, dtype=np.int64)
        vals = rng.integers(-5, 50, n_keys)
        chunks.append(batch_from_columns(
            SCHEMA, key=keys, id=ids, ts=ids * 2, value=vals))
    red = Reducer("sum")
    t0 = time.perf_counter()
    got = run_core(VecIncSlidingCore(spec, red), chunks)
    dt = time.perf_counter() - t0
    assert dt < 10, f"sliding vec path took {dt:.1f}s at {n_keys} keys"
    # oracle on a key sample (the full per-key ref would take minutes)
    sample = [0, 1, 12345, 99_999]
    sub = [c[np.isin(c["key"], sample)] for c in chunks]
    want = run_core(WinSeqCore(spec, red).use_incremental(), sub)
    got_sub = got[np.isin(got["key"], sample)]
    assert_equivalent(got_sub, want)


def test_lazy_sliding_core_picks_by_cardinality():
    """Sliding windows defer the core choice to the first chunk: few
    distinct keys -> the per-key-group WinSeqCore (faster below the
    crossover), many -> the lane-vectorised core; results identical
    either way."""
    from windflow_tpu.core.vecinc import LazySlidingCore, VecIncSlidingCore
    spec = WindowSpec(8, 4, WinType.CB)

    def stream(n_keys):
        ids = np.repeat(np.arange(40), n_keys)
        keys = np.tile(np.arange(n_keys), 40)
        return [batch_from_columns(SCHEMA, key=keys, id=ids, ts=ids,
                                   value=ids + keys % 7)]

    small = LazySlidingCore(spec, Reducer("sum"))
    got_small = run_core(small, stream(10))
    assert isinstance(small._core, WinSeqCore)
    big = LazySlidingCore(spec, Reducer("sum"), threshold=16)
    got_big = run_core(big, stream(32))
    assert isinstance(big._core, VecIncSlidingCore)
    want_small = run_core(WinSeqCore(spec, Reducer("sum")).use_incremental(),
                          stream(10))
    assert_equivalent(got_small, want_small)
    want_big = run_core(WinSeqCore(spec, Reducer("sum")).use_incremental(),
                        stream(32))
    assert_equivalent(got_big, want_big)


@pytest.mark.parametrize("op", ["sum", "max", "count"])
@pytest.mark.parametrize("wt", [WinType.CB, WinType.TB])
def test_lazy_sliding_core_escalates_mid_stream(wt, op):
    """A key-clustered stream (first chunks carry few keys) must not lock
    the lazy selector into the per-key core: when observed cardinality
    crosses the threshold, the per-key state migrates into the lane core
    mid-stream, with results identical to the reference oracle."""
    from windflow_tpu.core.vecinc import LazySlidingCore, VecIncSlidingCore
    spec = WindowSpec(9, 4, wt)
    rng = np.random.default_rng(57)
    n_keys = 40

    def clustered():
        chunks = []
        # phase 1: two keys only, long runs (under-represents the set)
        for lo in range(0, 30, 10):
            ids = np.repeat(np.arange(lo, lo + 10), 2)
            keys = np.tile(np.arange(2), 10)
            chunks.append(batch_from_columns(
                SCHEMA, key=keys, id=ids, ts=ids * 3 + keys,
                value=rng.integers(-5, 50, 20)))
        # phase 2: every key arrives (ids resume mid-stream per key)
        for lo in range(0, 40, 8):
            ids = np.repeat(np.arange(lo, lo + 8), n_keys)
            keys = np.tile(np.arange(n_keys), 8)
            # keys 0/1 continue beyond their phase-1 ids
            ids = np.where(keys < 2, ids + 30, ids)
            chunks.append(batch_from_columns(
                SCHEMA, key=keys, id=ids, ts=ids * 3 + keys,
                value=rng.integers(-5, 50, 8 * n_keys)))
        return chunks

    chunks = clustered()
    red = Reducer(op, out_field="r")
    lazy = LazySlidingCore(spec, Reducer(op, out_field="r"), threshold=16)
    got = run_core(lazy, chunks)
    assert isinstance(lazy._core, VecIncSlidingCore), \
        "selector never escalated despite crossing the threshold"
    want = run_core(WinSeqCore(spec, red).use_incremental(), chunks)
    assert_equivalent(got, want)


@pytest.mark.parametrize("role,map_indexes", [
    (Role.SEQ, (0, 1)), (Role.MAP, (1, 3)), (Role.PLQ, (0, 1)),
])
@pytest.mark.parametrize("case", [1, 3])   # ooo+markers / gaps+ooo
def test_lazy_sliding_escalation_roles_disorder(role, map_indexes, case):
    """Escalation under the hard paths: role renumbering state
    (emit_counter for MAP/PLQ), out-of-order drops, id gaps, and
    mid-stream markers must all survive the per-key -> lane migration."""
    from windflow_tpu.core.vecinc import LazySlidingCore, VecIncSlidingCore
    rng = np.random.default_rng(71 + case)
    spec = WindowSpec(10, 4, WinType.CB)
    cfg = PatternConfig(id_outer=1, n_outer=2, slide_outer=8,
                        id_inner=1, n_inner=3, slide_inner=4)
    # clustered prefix (1 key) keeps the selector on the per-key core,
    # then the full stream crosses the tiny threshold -> escalate
    pre = batch_from_columns(SCHEMA, key=np.zeros(12),
                             id=np.arange(12), ts=np.arange(12) * 3,
                             value=rng.integers(-5, 50, 12))
    chunks = [pre] + make_stream(rng, 25, 4, 150, **CASES[case])

    def mk():
        return Reducer("max")

    lazy = LazySlidingCore(spec, mk(), threshold=8, config=cfg, role=role,
                           map_indexes=map_indexes)
    got = run_core(lazy, chunks)
    assert isinstance(lazy._core, VecIncSlidingCore)
    ref = WinSeqCore(spec, mk(), config=cfg, role=role,
                     map_indexes=map_indexes).use_incremental()
    assert_equivalent(got, run_core(ref, chunks))


def test_lazy_sliding_escalation_multireducer():
    """MultiReducer accumulators (count + max + sum lanes) migrate too."""
    from windflow_tpu.core.vecinc import LazySlidingCore, VecIncSlidingCore
    rng = np.random.default_rng(83)
    spec = WindowSpec(12, 5, WinType.TB)

    def mk():
        return MultiReducer(("count", None, "cnt"), ("max", "value", "mx"),
                            ("sum", "value", "sm"))

    pre = batch_from_columns(SCHEMA, key=np.zeros(10),
                             id=np.arange(10), ts=np.arange(10) * 3,
                             value=rng.integers(-5, 50, 10))
    chunks = [pre] + make_stream(rng, 21, 4, 130, gaps=True)
    lazy = LazySlidingCore(spec, mk(), threshold=8)
    got = run_core(lazy, chunks)
    assert isinstance(lazy._core, VecIncSlidingCore)
    assert_equivalent(got, run_core(WinSeqCore(spec, mk()).use_incremental(),
                                    chunks))


def test_sliding_crossover_is_derived_not_encoded():
    """r3 weak #4: the per-key vs lane-core crossover is MEASURED on the
    running host (derived_sliding_threshold), not a baked-in constant —
    and whatever value the measurement returns, both cores agree
    differentially on streams straddling it."""
    from windflow_tpu.core.vecinc import (LazySlidingCore,
                                          VecIncSlidingCore,
                                          derived_sliding_threshold)
    from windflow_tpu.core.winseq import WinSeqCore

    th = derived_sliding_threshold()
    assert 64 <= th <= 8192, th
    assert derived_sliding_threshold() == th, "must cache per process"
    # default-constructed selector adopts the derived value
    spec = WindowSpec(8, 2, WinType.CB)
    lazy = LazySlidingCore(spec, Reducer("sum"))
    assert lazy._threshold == th
    # differential straddle: a stream just under and just over the
    # measured crossover picks different cores, same results
    for nk in (max(th - 8, 2), th + 8):
        n = 6 * nk
        ids = np.repeat(np.arange(n // nk, dtype=np.int64), nk)
        keys = np.tile(np.arange(nk, dtype=np.int64), n // nk)
        b = batch_from_columns(Schema(value=np.int64), key=keys, id=ids,
                               ts=ids, value=(ids * 7 + keys) % 101)
        lz = LazySlidingCore(spec, Reducer("sum"))
        got = np.concatenate([lz.process(b), lz.flush()])
        picked = type(lz._core)
        assert picked is (VecIncSlidingCore if nk >= th else WinSeqCore)
        ref = WinSeqCore(spec, Reducer("sum"))
        want = np.concatenate([ref.process(b), ref.flush()])
        got = np.sort(got, order=["key", "id"])
        want = np.sort(want, order=["key", "id"])
        np.testing.assert_array_equal(got, want, err_msg=f"nk={nk}")


# ------------------------------------------------------------------------
# dense_positions on the vectorised cores: the closing position moves one
# ahead (a Pane_Farm's WLQ that is a monoid Reducer lands here), call for
# call what WinSeqCore does with the property on, row for row what either
# does with it off.

def _vec_for(spec, red, **kw):
    from windflow_tpu.core.vecinc import VecIncSlidingCore
    cls = VecIncTumblingCore if spec.is_tumbling else VecIncSlidingCore
    return cls(spec, red, **kw)


DENSE_ROLES = {
    "seq": (Role.SEQ, None),
    # worker 1 of a WLQ farm of 2: rows below its first window are dropped
    "wlq_worker": (Role.WLQ, (0, 1, 0, 1, 2, 0)),
}


@pytest.mark.parametrize("shape", [(5, 40, 7), (3, 6, 100), (2, 150, 1)],
                         ids=["chunks_of_7", "chunks_of_100", "single_rows"])
@pytest.mark.parametrize("who,win,slide", [
    ("seq", 4, 4), ("seq", 8, 4), ("seq", 20, 1), ("seq", 7, 3),
    ("wlq_worker", 4, 4), ("wlq_worker", 8, 4), ("wlq_worker", 20, 2),
    ("wlq_worker", 12, 6)])
def test_vec_dense_positions_fire_with_the_last_row(who, win, slide, shape):
    role, cfg_t = DENSE_ROLES[who]
    spec = WindowSpec(win, slide, WinType.CB)
    cfg = None
    if cfg_t is not None:
        # the farm's slide is half the worker's private one
        cfg = PatternConfig(*(cfg_t[:2] + (slide // 2,) + cfg_t[3:5]
                              + (slide // 2,)))
    chunks = make_stream(np.random.default_rng(win * 31 + slide), *shape)
    red = Reducer("sum")
    kw = dict(config=cfg, role=role)
    vec_on = _vec_for(spec, red, dense_positions=True, **kw)
    ref_on = WinSeqCore(spec, red, dense_positions=True,
                        **kw).use_incremental()
    vec_off = _vec_for(spec, red, **kw)
    on_calls, ref_calls = run_calls(vec_on, chunks), run_calls(ref_on, chunks)
    off_calls = run_calls(vec_off, chunks)
    for got, want in zip(on_calls, ref_calls):       # call for call
        assert_equivalent(got, want)
    assert_equivalent(np.concatenate(on_calls), np.concatenate(off_calls))
    assert vec_on.windows_fired_complete \
        == ref_on.windows_fired_complete \
        == sum(len(o) for o in on_calls[:-1]) > 0
    assert vec_off.windows_fired_complete is None
    # the reference's rule leaves a window that ends with a key's last id
    # to the flush; fired with that id it is in no flush
    assert len(on_calls[-1]) <= len(off_calls[-1])


def test_lazy_sliding_core_carries_dense_positions_across_its_escalation():
    from windflow_tpu.core.vecinc import LazySlidingCore, VecIncSlidingCore
    spec = WindowSpec(8, 4, WinType.CB)
    few = make_stream(np.random.default_rng(3), 2, 4, 20,
                      markers_at_end=False)
    many = make_stream(np.random.default_rng(4), 40, 6, 400)
    seen = np.concatenate(few)["key"]
    for c in many:                  # the two early keys carry on, densely
        for k in (0, 1):
            c["id"][c["key"] == k] += np.count_nonzero(seen == k)
    chunks = few + many
    idle = LazySlidingCore(spec, Reducer("sum"), dense_positions=True)
    assert idle.windows_fired_complete == 0
    assert LazySlidingCore(spec, Reducer("sum")).windows_fired_complete \
        is None
    lazy = LazySlidingCore(spec, Reducer("sum"), threshold=16,
                           dense_positions=True)
    ref = WinSeqCore(spec, Reducer("sum"),
                     dense_positions=True).use_incremental()
    for got, want in zip(run_calls(lazy, chunks), run_calls(ref, chunks)):
        assert_equivalent(got, want)
    assert isinstance(lazy._core, VecIncSlidingCore)
    assert lazy.windows_fired_complete == ref.windows_fired_complete > 0
