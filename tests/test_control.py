"""Control-plane tests (windflow_tpu/control/, docs/CONTROL.md): rule
hysteresis/cooldown state machines, the live-rescale differential (a
Key_Farm rescaled N→N+k and back mid-stream must be byte-identical to
the fixed-width oracle, across every host core flavour), adaptive-shed
threshold movement, admission-control rate clamps, the knob-unset
no-import contract, and the new event kinds' schema.
"""

import importlib.util
import os
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

from windflow_tpu import (Accumulator, KeyFarm, MultiPipe, OverloadPolicy,
                          RecoveryPolicy, Reducer, Sink, Source, WinFarm)
from windflow_tpu.control import (Admission, AdaptiveShed, ControlPolicy,
                                  Rescale, TokenBucket)
from windflow_tpu.core.tuples import Schema
from windflow_tpu.runtime.engine import Dataflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCHEMA = Schema(value=np.int64)


@pytest.fixture(autouse=True)
def _no_ambient_obs_env(monkeypatch):
    """Ambient WF_LOG_DIR/WF_SAMPLE_PERIOD would change which warnings
    fire and write files the assertions don't expect."""
    monkeypatch.delenv("WF_LOG_DIR", raising=False)
    monkeypatch.delenv("WF_SAMPLE_PERIOD", raising=False)


def keyed_batches(n_batches=60, rows=50, n_keys=13, seed=7):
    """Per-key dense ids / monotone ts — the pristine-source contract."""
    rng = np.random.default_rng(seed)
    ctr = {}
    for _ in range(n_batches):
        b = np.zeros(rows, dtype=SCHEMA.dtype())
        keys = rng.integers(0, n_keys, rows)
        b["key"] = keys
        b["value"] = rng.integers(0, 100, rows)
        for i, k in enumerate(keys.tolist()):
            b["id"][i] = ctr.get(k, 0)
            ctr[k] = ctr.get(k, 0) + 1
        b["ts"] = b["id"]
        yield b


def _run_quiet(fn):
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message=r"\[WF20[79]\]")
        return fn()


def per_key(rows):
    """(key, id, value) rows grouped by key, ARRIVAL ORDER KEPT — the
    differential invariant live rescale must preserve is each key's
    result sequence (cross-key interleave is scheduling-dependent in
    both runs); comparing these dicts checks order, drops, and dups at
    once."""
    d = {}
    for k, i, v in rows:
        d.setdefault(k, []).append((i, v))
    return d


#: a Rescale rule that never fires on its own — scripted requests only
def _manual_rule(max_workers=4):
    return Rescale("kf", max_workers=max_workers, min_workers=1,
                   up_depth=10 ** 9, down_depth=-1, cooldown=10 ** 9)


# --------------------------------------------------------------- policy


def test_policy_validation():
    with pytest.raises(ValueError, match="at least one rule"):
        ControlPolicy([])
    with pytest.raises(TypeError, match="unknown rule"):
        ControlPolicy([object()])
    with pytest.raises(ValueError, match="period"):
        ControlPolicy([_manual_rule()], period=0)
    with pytest.raises(ValueError, match="duplicate Rescale"):
        ControlPolicy([_manual_rule(), _manual_rule()])
    with pytest.raises(ValueError, match="AdaptiveShed"):
        ControlPolicy([AdaptiveShed(8, 2), AdaptiveShed(9, 3)])
    with pytest.raises(ValueError, match="max_workers"):
        Rescale("kf", max_workers=2, min_workers=2)
    with pytest.raises(ValueError, match="low threshold"):
        Rescale("kf", max_workers=4, up_depth=2, down_depth=5)
    with pytest.raises(ValueError, match="min_rate"):
        Admission(max_rate=10, min_rate=20, high_depth=8, low_depth=2)
    with pytest.raises(ValueError, match="down"):
        Admission(max_rate=10, min_rate=1, high_depth=8, low_depth=2,
                  down=1.5)
    with pytest.raises(ValueError, match="overlapping Admission"):
        ControlPolicy([
            Admission(max_rate=10, min_rate=1, high_depth=8,
                      low_depth=2),
            Admission(max_rate=5, min_rate=1, high_depth=8,
                      low_depth=2)])
    # distinct source patterns may each carry their own cap
    ControlPolicy([
        Admission(max_rate=10, min_rate=1, high_depth=8, low_depth=2,
                  pattern="a"),
        Admission(max_rate=5, min_rate=1, high_depth=8, low_depth=2,
                  pattern="b")])
    with pytest.raises(TypeError, match="ControlPolicy"):
        Dataflow("x", control=object())


def test_rescale_without_recovery_refused():
    with pytest.raises(ValueError, match="WF211"):
        Dataflow("x", control=ControlPolicy([_manual_rule()]))
    # non-rescale rules need no recovery
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        Dataflow("x", control=ControlPolicy(
            [Admission(max_rate=10, min_rate=1, high_depth=8,
                       low_depth=2)]))


def test_blind_control_warns_wf209():
    with pytest.warns(UserWarning, match=r"WF209.*blind"):
        Dataflow("x", metrics=None, recovery=RecoveryPolicy(),
                 control=ControlPolicy([_manual_rule()]))


def test_policy_agreement():
    def mk():
        return ControlPolicy([_manual_rule(),
                              AdaptiveShed(8, 2)], period=0.5)
    assert mk().agrees_with(mk())
    other = ControlPolicy([_manual_rule()], period=0.5)
    assert not mk().agrees_with(other)


# ------------------------------------------------- rule state machines


def test_hysteresis_requires_consecutive_samples():
    r = AdaptiveShed(high_depth=10, low_depth=2, hysteresis=3,
                     cooldown=0.0)
    assert r.observe(12, 0.0) == 0
    assert r.observe(12, 1.0) == 0
    assert r.observe(12, 2.0) == 1          # third consecutive high
    # a low sample resets the high streak
    assert r.observe(12, 3.0) == 0
    assert r.observe(1, 4.0) == 0
    assert r.observe(12, 5.0) == 0
    assert r.observe(12, 6.0) == 0
    assert r.observe(12, 7.0) == 1


def test_cooldown_suppresses_actions():
    r = AdaptiveShed(high_depth=10, low_depth=2, hysteresis=1,
                     cooldown=5.0)
    assert r.observe(12, 0.0) == 1
    assert r.observe(12, 1.0) == 0          # inside the cooldown
    assert r.observe(12, 4.9) == 0
    assert r.observe(12, 5.0) == 1          # cooldown elapsed
    assert r.observe(1, 10.1) == -1         # low side symmetric


def test_rescale_rule_shed_signal():
    r = Rescale("kf", max_workers=4, up_depth=100, down_depth=0,
                up_shed=50.0, hysteresis=1, cooldown=0.0)
    assert r.observe((0, 80.0), 0.0) == 1   # shed rate alone scales up
    assert r.observe((0, 0.0), 1.0) == -1   # idle depth scales down
    assert r.observe((5, 0.0), 2.0) == 0    # neither side


def test_token_bucket_rates_and_debt():
    b = TokenBucket(rate=1000.0, burst=100.0)
    t0 = time.monotonic()
    b.throttle(100)                          # the full burst: immediate
    b.throttle(500)                          # > burst: debt, rate-bound
    b.throttle(1)
    dt = time.monotonic() - t0
    assert dt >= 0.4, f"600 tokens at 1000/s took only {dt:.3f}s"


# ------------------------------------------- live-rescale differential


def _kf_pattern(flavour):
    if flavour == "tumbling":        # VecIncTumblingCore
        return KeyFarm(Reducer("sum", "value"), win_len=4, slide_len=4,
                       pardegree=2, name="kf")
    if flavour in ("sliding", "sliding_vec"):  # LazySlidingCore
        return KeyFarm(Reducer("sum", "value"), win_len=8, slide_len=4,
                       pardegree=2, name="kf")
    if flavour == "nic":             # general WinSeqCore, NIC archive
        return KeyFarm(lambda key, gwid, rows: (int(rows["value"].sum()),),
                       win_len=8, slide_len=4, pardegree=2, name="kf",
                       result_fields={"value": np.int64})
    raise AssertionError(flavour)


def _build_pipe(out, pattern, control=None, recovery=None, metrics=None):
    pipe = MultiPipe("job", capacity=8, recovery=recovery,
                     metrics=metrics, control=control)
    pipe.add_source(Source(batches=lambda i: keyed_batches(),
                           name="src"))
    pipe.add(pattern)
    pipe.add_sink(Sink(
        lambda r: out.append((int(r["key"]), int(r["id"]),
                              int(r["value"])))
        if r is not None else None, name="sink"))
    return pipe


def _await_width(ctl, width, timeout=60.0):
    t0 = time.monotonic()
    while ctl.width_of("kf") != width:
        if time.monotonic() - t0 > timeout:
            raise AssertionError(
                f"rescale to {width} did not land in {timeout}s "
                f"(width {ctl.width_of('kf')})")
        time.sleep(0.01)


@pytest.mark.parametrize("flavour", ["tumbling", "sliding",
                                     "sliding_vec", "nic"])
def test_keyfarm_rescale_up_and_back_matches_oracle(flavour):
    """Acceptance (ISSUE 12): a Key_Farm rescaled N→N+k and back
    mid-stream produces output identical to the fixed-width oracle —
    per-key order preserved, no drops, no dups — across every host core
    flavour (vec tumbling, lazy sliding per-key and lane-escalated,
    general NIC)."""
    oracle = []
    _build_pipe(oracle, _kf_pattern(flavour)).run_and_wait_end(timeout=120)

    got = []
    pipe = _build_pipe(
        got, _kf_pattern(flavour),
        control=ControlPolicy([_manual_rule()], period=0.02),
        recovery=RecoveryPolicy(epoch_batches=5, restart_backoff=0.01),
        metrics=True)
    if flavour == "sliding_vec":
        # pin the lazy cores' crossover BEFORE run so the first chunk
        # escalates to the lane-vectorised sliding core on every worker
        for n in pipe._build().nodes:
            core = getattr(n, "core", None)
            if core is not None and hasattr(core, "_threshold"):
                core._threshold = 1
    _run_quiet(pipe.run)
    ctl = pipe.controller
    assert ctl.request_rescale("kf", 4)
    _await_width(ctl, 4)
    assert ctl.request_rescale("kf", 2)
    pipe.wait(timeout=120)
    history = [h for fc in ctl.farms for h in fc.history]
    assert history and history[0][:2] == (2, 4), history
    assert per_key(got) == per_key(oracle)


def test_keyfarm_scale_down_matches_oracle():
    oracle = []
    _build_pipe(oracle, KeyFarm(Reducer("sum", "value"), 8, 4,
                                pardegree=3, name="kf")
                ).run_and_wait_end(timeout=120)
    got = []
    pipe = _build_pipe(
        got, KeyFarm(Reducer("sum", "value"), 8, 4, pardegree=3,
                     name="kf"),
        control=ControlPolicy([_manual_rule()], period=0.02),
        recovery=RecoveryPolicy(epoch_batches=4, restart_backoff=0.01),
        metrics=True)
    _run_quiet(pipe.run)
    assert pipe.controller.request_rescale("kf", 1)
    pipe.wait(timeout=120)
    assert per_key(got) == per_key(oracle)


def test_accumulator_farm_rescale_matches_oracle():
    """Keyed Accumulator farms migrate their fold dicts."""
    def acc():
        a = Accumulator(lambda row, a_: a_.__setitem__(
            "value", a_["value"] + row["value"]), SCHEMA, parallelism=2,
            name="kf")
        return a

    oracle = []
    _build_pipe(oracle, acc()).run_and_wait_end(timeout=120)
    got = []
    pipe = _build_pipe(
        got, acc(),
        control=ControlPolicy([_manual_rule()], period=0.02),
        recovery=RecoveryPolicy(epoch_batches=5, restart_backoff=0.01),
        metrics=True)
    _run_quiet(pipe.run)
    ctl = pipe.controller
    assert ctl.request_rescale("kf", 4)
    _await_width(ctl, 4)
    assert ctl.request_rescale("kf", 2)
    pipe.wait(timeout=120)
    assert per_key(got) == per_key(oracle)


def test_threshold_driven_rescale_differential():
    """Rule-driven (not scripted) scale-up under a slow sink still
    matches the oracle, and the decision surfaces in ctl_* metrics."""
    def build(out, **kw):
        pipe = MultiPipe("job", capacity=4, **kw)
        pipe.add_source(Source(batches=lambda i: keyed_batches(),
                               name="src"))
        pipe.add(KeyFarm(Reducer("sum", "value"), 4, 4, pardegree=2,
                         name="kf"))
        def sink(r):
            if r is not None:
                time.sleep(0.0002)
                out.append((int(r["key"]), int(r["id"]),
                            int(r["value"])))
        pipe.add_sink(Sink(sink, name="sink"))
        return pipe

    oracle = []
    build(oracle).run_and_wait_end(timeout=120)
    got = []
    pipe = build(got, control=ControlPolicy(
        [Rescale("kf", max_workers=4, min_workers=1, up_depth=1,
                 down_depth=-1, hysteresis=1, cooldown=0.0)],
        period=0.02),
        recovery=RecoveryPolicy(epoch_batches=4, restart_backoff=0.01),
        metrics=True)
    _run_quiet(lambda: pipe.run_and_wait_end(timeout=120))
    hist = [h for fc in pipe.controller.farms for h in fc.history]
    assert hist, "threshold rule never fired"
    snap = pipe.metrics.snapshot()
    assert snap["counters"]["ctl_rescale_up"] >= 1
    assert snap["gauges"]["ctl_width_kf"] == hist[-1][1]
    assert per_key(got) == per_key(oracle)


@pytest.mark.parametrize("own_ts", [False, True], ids=["ts_is_id", "own_ts"])
def test_native_keyfarm_threshold_rescale_matches_oracle(own_ts):
    """ISSUE 17 acceptance: a threshold-driven Rescale on a Key_Farm of
    native C++ cores migrates per-key wf_core state at the epoch
    barrier — per-key result sequences identical to the fixed-width
    oracle (order, drops, dups checked per key).  `own_ts`: every row
    carries a timestamp of its own and the results' ts are compared too —
    a migrated key's first window after the barrier takes its ts from a
    row archived on the old owner, which the key's blob carries."""
    from windflow_tpu.native import enabled
    lib = enabled()
    if lib is None or not getattr(lib, "wf_has_state_abi", False):
        pytest.skip("native library with the state ABI unavailable")
    from windflow_tpu.patterns.native_core import NativeResidentCore
    from windflow_tpu.patterns.win_seq_tpu import KeyFarmTPU

    def build(out, **kw):
        pipe = MultiPipe("job", capacity=4, **kw)

        def batches(_i):
            for b in keyed_batches():
                if own_ts:
                    b["ts"] = b["id"] * 7 + b["key"] * 3 + 11
                yield b

        pipe.add_source(Source(batches=batches, name="src"))
        pipe.add(KeyFarmTPU(Reducer("sum", "value"), 8, 4, pardegree=2,
                            batch_len=64, name="kf"))

        def sink(r):
            if r is not None:
                time.sleep(0.0002)    # slow sink: inbox depth drives the rule
                out.append((int(r["key"]), int(r["id"]),
                            (int(r["ts"]), int(r["value"]))))
        pipe.add_sink(Sink(sink, name="sink"))
        return pipe

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # int32-accumulation advisory
        oracle = []
        build(oracle).run_and_wait_end(timeout=300)
        got = []
        pipe = build(got, control=ControlPolicy(
            [Rescale("kf", max_workers=4, min_workers=1, up_depth=1,
                     down_depth=-1, hysteresis=1, cooldown=0.0)],
            period=0.02),
            recovery=RecoveryPolicy(epoch_batches=4,
                                    restart_backoff=0.01),
            metrics=True)
        df = pipe._build()
        workers = [n for n in df.nodes if n.name.startswith("kf.")
                   and "emitter" not in n.name
                   and "collector" not in n.name]
        assert workers
        for w in workers:
            assert isinstance(w.core, NativeResidentCore)
            assert w.core.has_state_abi and w.core.keyed_migratable
        pipe.run_and_wait_end(timeout=300)
    hist = [h for fc in pipe.controller.farms for h in fc.history]
    assert hist, "threshold rule never fired"
    assert per_key(got) == per_key(oracle)
    if own_ts:
        # a full window's ts is its last row's: id 4w + 7 of its key
        assert all(t == (4 * i + 7) * 7 + k * 3 + 11
                   for k, i, (t, _v) in oracle[:200])


def test_crash_after_rescale_restores_migrated_placement():
    """A worker crash after a completed rescale restores the
    POST-migration snapshot (re-committed through the writer path) and
    still matches the oracle."""
    oracle = []
    _build_pipe(oracle, KeyFarm(Reducer("sum", "value"), 4, 4,
                                pardegree=2, name="kf")
                ).run_and_wait_end(timeout=120)
    got = []
    pipe = _build_pipe(
        got, KeyFarm(Reducer("sum", "value"), 4, 4, pardegree=2,
                     name="kf"),
        control=ControlPolicy([_manual_rule()], period=0.02),
        recovery=RecoveryPolicy(epoch_batches=4, restart_backoff=0.01),
        metrics=True)
    df = pipe._build()
    workers = [n for n in df.nodes if n.name.startswith("kf.")
               and "emitter" not in n.name and "collector" not in n.name]
    assert len(workers) == 4          # pre-provisioned to max_workers
    state = {"n": 0, "fired": False}
    for w in workers:
        orig = w.svc

        def svc(batch, channel=0, _o=orig):
            state["n"] += 1
            if not state["fired"] and state["n"] == 30:
                state["fired"] = True
                raise RuntimeError("injected crash post-rescale")
            return _o(batch, channel)

        w.svc = svc
    _run_quiet(pipe.run)
    ctl = pipe.controller
    assert ctl.request_rescale("kf", 4)
    pipe.wait(timeout=120)
    assert state["fired"], "kill point never reached"
    assert [h[:2] for fc in ctl.farms for h in fc.history] == [(2, 4)]
    assert per_key(got) == per_key(oracle)


def test_migration_failure_fails_graph_without_restart():
    """A fault inside the migration leaves sibling cores inconsistent:
    the graph must fail like the seed engine (RescaleError is never
    restored through), not restart into silently-wrong state."""
    from windflow_tpu.control import RescaleError
    pipe = _build_pipe(
        [], KeyFarm(Reducer("sum", "value"), 4, 4, pardegree=2,
                    name="kf"),
        control=ControlPolicy([_manual_rule()], period=0.02),
        recovery=RecoveryPolicy(epoch_batches=4, restart_backoff=0.01,
                                max_restarts=5),
        metrics=True)
    df = pipe._build()
    for n in df.nodes:
        core = getattr(n, "core", None)
        if core is not None and hasattr(core, "keyed_state_import"):
            def bad_import(frag, _c=core):
                raise RuntimeError("injected migration fault")
            core.keyed_state_import = bad_import
    _run_quiet(pipe.run)
    assert pipe.controller.request_rescale("kf", 4)
    with pytest.raises(RescaleError):
        pipe.wait(timeout=120)
    ev = [e for e in pipe.events.recent if e["event"] == "node_error"]
    assert any("migration" in e.get("message", "") for e in ev)


def test_rescale_rule_targeting_winfarm_refused():
    """Window-parallel farms own window slices, not keys: the wiring
    layer refuses the rule loudly (WF210; docs/CONTROL.md)."""
    pipe = _build_pipe(
        [], WinFarm(Reducer("sum", "value"), 8, 4, pardegree=2,
                    name="kf"),
        control=ControlPolicy([_manual_rule()], period=0.05),
        recovery=RecoveryPolicy(epoch_batches=5), metrics=True)
    with pytest.raises(ValueError, match="WF210"):
        _run_quiet(pipe.run)


def test_rescale_rule_targeting_device_core_refused(monkeypatch):
    """Device cores INHERIT the host keyed hooks from WinSeqCore but
    mirror per-key rows into HBM rings the hooks cannot move — the
    keyed_migratable opt-out must make attach refuse (both the native
    and the Python resident core)."""
    from windflow_tpu import KeyFarmTPU
    monkeypatch.setenv("WF_NO_NATIVE_CORE", "1")
    pipe = MultiPipe("dev", metrics=True,
                     recovery=RecoveryPolicy(epoch_batches=3),
                     control=ControlPolicy([_manual_rule()], period=0.05))
    pipe.add_source(Source(batches=lambda i: keyed_batches(n_batches=2),
                           name="src"))
    pipe.add(KeyFarmTPU(Reducer("sum", "value"), 4, 4, pardegree=2,
                        name="kf", batch_len=8))
    pipe.add_sink(Sink(lambda r: None, name="sink"))
    with pytest.raises(ValueError, match="keyed-state migration"):
        _run_quiet(pipe.run)


def test_rescale_rule_unknown_pattern_refused():
    pipe = _build_pipe(
        [], KeyFarm(Reducer("sum", "value"), 8, 4, pardegree=2,
                    name="other"),
        control=ControlPolicy([_manual_rule()], period=0.05),
        recovery=RecoveryPolicy(epoch_batches=5), metrics=True)
    with pytest.raises(ValueError, match="no key-partitioned farm"):
        _run_quiet(pipe.run)


# ------------------------------------------- adaptive shed / admission


def _overload_pipe(out, control):
    pipe = MultiPipe("ovl", capacity=4, metrics=True,
                     overload=OverloadPolicy(shed="shed_oldest"),
                     control=control)
    pipe.add_source(Source(batches=lambda i: keyed_batches(n_batches=80),
                           name="src"))

    def sink(r):
        if r is not None:
            time.sleep(0.001)
            out.append(1)

    pipe.add_sink(Sink(sink, name="sink"))
    return pipe


def test_adaptive_shed_moves_soft_limit():
    got = []
    pipe = _overload_pipe(got, ControlPolicy(
        [AdaptiveShed(high_depth=3, low_depth=0, min_limit=1, step=1,
                      hysteresis=1, cooldown=0.0)], period=0.02))
    _run_quiet(lambda: pipe.run_and_wait_end(timeout=180))
    snap = pipe.metrics.snapshot()
    assert snap["counters"].get("ctl_shed_tighten", 0) >= 1
    # the policy object itself moved (min_limit clamps the floor)
    lim = pipe._df.overload.soft_limit
    assert lim is None or lim >= 1
    assert got, "sink starved"


def test_adaptive_shed_requires_shedding_policy():
    pipe = MultiPipe("ovl", capacity=4, metrics=True,
                     control=ControlPolicy([AdaptiveShed(3, 0)],
                                           period=0.05))
    pipe.add_source(Source(batches=lambda i: keyed_batches(n_batches=2),
                           name="src"))
    pipe.add_sink(Sink(lambda r: None, name="sink"))
    with pytest.raises(ValueError, match="AdaptiveShed"):
        _run_quiet(pipe.run)


def test_admission_rate_clamped_and_content_preserved():
    """Admission throttling delays emission but never drops: content is
    oracle-identical, the rate gauge moves and respects min_rate."""
    def build(out, control=None):
        pipe = MultiPipe("adm", capacity=4,
                         metrics=True if control else None,
                         control=control)
        pipe.add_source(Source(
            batches=lambda i: keyed_batches(n_batches=30), name="src"))

        def sink(r):
            if r is not None:
                time.sleep(0.0005)
                out.append((int(r["key"]), int(r["id"]),
                            int(r["value"])))

        pipe.add_sink(Sink(sink, name="sink"))
        return pipe

    oracle = []
    build(oracle).run_and_wait_end(timeout=120)
    got = []
    min_rate = 2e4
    pipe = build(got, ControlPolicy(
        [Admission(max_rate=1e6, min_rate=min_rate, high_depth=2,
                   low_depth=0, hysteresis=1, cooldown=0.0)],
        period=0.02))
    _run_quiet(lambda: pipe.run_and_wait_end(timeout=180))
    snap = pipe.metrics.snapshot()
    assert snap["counters"].get("ctl_admission_down", 0) >= 1
    assert snap["gauges"]["ctl_admission_rate"] >= min_rate
    assert per_key(got) == per_key(oracle)


def test_rescale_width_outside_rule_range_reported_not_raised():
    """A pre-build conflict the wiring layer refuses (declared width
    outside the rule's range) must surface as a WF210 diagnostic from
    validate()/wf-lint, not as a raw build ValueError."""
    from windflow_tpu.check import validate
    pipe = _build_pipe(
        [], KeyFarm(Reducer("sum", "value"), 8, 4, pardegree=6,
                    name="kf"),
        control=ControlPolicy([_manual_rule(max_workers=4)],
                              period=0.05),
        recovery=RecoveryPolicy(epoch_batches=5), metrics=True)
    report = validate(pipe)
    assert "WF210" in report.codes(), report.render()
    with pytest.raises(ValueError, match="outside"):
        _run_quiet(pipe.run)


def test_admission_replica_name_overlap_refused():
    """'src' and 'src.0' both match replica src.0: the attach-time
    guard must refuse the double wrap the policy check cannot see."""
    pipe = MultiPipe("adm2", metrics=True, control=ControlPolicy([
        Admission(max_rate=10, min_rate=1, high_depth=8, low_depth=2,
                  pattern="src"),
        Admission(max_rate=5, min_rate=1, high_depth=8, low_depth=2,
                  pattern="src.0"),
    ], period=0.05))
    pipe.add_source(Source(batches=lambda i: keyed_batches(n_batches=2),
                           name="src"))
    pipe.add_sink(Sink(lambda r: None, name="sink"))
    with pytest.raises(ValueError, match="double-throttle"):
        _run_quiet(pipe.run)


def test_admission_unknown_source_refused():
    pipe = MultiPipe("adm", metrics=True, control=ControlPolicy(
        [Admission(max_rate=10, min_rate=1, high_depth=8, low_depth=2,
                   pattern="nosuch")], period=0.05))
    pipe.add_source(Source(batches=lambda i: keyed_batches(n_batches=2),
                           name="src"))
    pipe.add_sink(Sink(lambda r: None, name="sink"))
    with pytest.raises(ValueError, match="Admission"):
        _run_quiet(pipe.run)


# ----------------------------------------------------------------- drain


def test_drain_quiesces_then_resumes_content_preserved():
    """request_drain gates the sources and settles the graph; release
    resumes exactly where it parked — content oracle-identical, the
    drain observable as counter + gauge + events."""
    from windflow_tpu.control import Drain

    def build(out, control=None):
        pipe = MultiPipe("drn", capacity=4,
                         metrics=True if control else None,
                         control=control)
        pipe.add_source(Source(
            batches=lambda i: keyed_batches(n_batches=40), name="src"))

        def sink(r):
            if r is not None:
                time.sleep(0.0005)
                out.append((int(r["key"]), int(r["id"]),
                            int(r["value"])))

        pipe.add_sink(Sink(sink, name="sink"))
        return pipe

    oracle = []
    build(oracle).run_and_wait_end(timeout=120)
    got = []
    pipe = build(got, ControlPolicy(
        [Drain(deadline=30.0, poll=0.01)], period=0.05))
    _run_quiet(pipe.run)
    time.sleep(0.05)                    # let some rows flow
    assert pipe.request_drain() is True
    assert pipe.controller.draining
    # inboxes are empty; the batch the sink had already popped may
    # still be mid-iteration — let it finish, then nothing moves
    time.sleep(0.3)
    n_at_drain = len(got)
    time.sleep(0.3)
    assert len(got) == n_at_drain
    # idempotent while draining
    assert pipe.request_drain(timeout=5.0) is True
    pipe.release_drain()
    assert not pipe.controller.draining
    pipe.wait(timeout=120)
    assert per_key(got) == per_key(oracle)
    snap = pipe.metrics.snapshot()
    assert snap["counters"].get("ctl_drains", 0) == 1
    assert snap["gauges"]["ctl_draining"] == 0
    phases = [e.get("phase") for e in pipe.events.recent
              if e["event"] == "drain"]
    assert phases[:2] == ["requested", "quiesced"]
    assert "released" in phases


def test_drain_without_rule_or_run_refused():
    from windflow_tpu.control import Drain
    with pytest.raises(ValueError, match="one Drain"):
        ControlPolicy([Drain(), Drain()])
    pipe = MultiPipe("drn2", capacity=4, metrics=True,
                     control=ControlPolicy([AdaptiveShed(3, 0)],
                                           period=0.05))
    with pytest.raises(RuntimeError, match="running"):
        pipe.request_drain()
    pipe2 = MultiPipe(
        "drn3", capacity=4, metrics=True,
        overload=OverloadPolicy(shed="shed_oldest"),
        control=ControlPolicy([AdaptiveShed(3, 0)], period=0.05))
    pipe2.add_source(Source(batches=lambda i: keyed_batches(n_batches=2),
                            name="src"))
    pipe2.add_sink(Sink(lambda r: None, name="sink"))
    _run_quiet(pipe2.run)
    try:
        with pytest.raises(RuntimeError, match="Drain"):
            pipe2.request_drain()
    finally:
        pipe2.wait(timeout=60)


# ------------------------------------------------------ sampler/obs/ui


def test_sampler_subscribe_receives_snapshots_and_survives_errors():
    from windflow_tpu.obs.sampler import Sampler
    from windflow_tpu.runtime.farm import build_pipeline
    got, bad = [], []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        df2 = Dataflow("sub", capacity=8, metrics=True,
                       sample_period=0.02)
    build_pipeline(df2, [
        Source(batches=lambda i: keyed_batches(n_batches=20),
               name="src"),
        Sink(lambda r: time.sleep(0.005) if r is not None else None,
             vectorized=True, name="sink"),
    ])

    def boom(rec):
        bad.append(rec)
        raise RuntimeError("bad subscriber")

    df2.run()
    sampler = df2._sampler
    assert isinstance(sampler, Sampler)
    sampler.subscribe(boom)
    sampler.subscribe(got.append)
    df2.wait(timeout=60)
    assert got and bad, "subscribers never called"
    assert isinstance(sampler.sub_error, RuntimeError)
    # the good subscriber kept receiving after the bad one raised
    assert {r["dataflow"] for r in got} == {"sub"}
    assert all("nodes" in r for r in got)


def test_control_events_schema_and_files(tmp_path):
    """control/rescale events pass the documented schema end-to-end
    (obs_schema) and land in events.jsonl."""
    from obs_schema import validate_event, validate_file
    got = []
    pipe = _build_pipe(
        got, KeyFarm(Reducer("sum", "value"), 4, 4, pardegree=2,
                     name="kf"),
        control=ControlPolicy([_manual_rule()], period=0.02),
        recovery=RecoveryPolicy(epoch_batches=4, restart_backoff=0.01),
        metrics=True)
    pipe.trace_dir = str(tmp_path)
    pipe.run()
    ctl = pipe.controller
    assert ctl.request_rescale("kf", 3)
    pipe.wait(timeout=120)
    kinds = {e["event"] for e in pipe.events.recent}
    assert {"control", "rescale"} <= kinds, kinds
    for e in pipe.events.recent:
        validate_event(e)
    n = validate_file(os.path.join(str(tmp_path), "events.jsonl"),
                      validate_event)
    assert n > 0


def test_wf_top_renders_control_line():
    spec = importlib.util.spec_from_file_location(
        "wf_top", os.path.join(REPO, "scripts", "wf_top.py"))
    wf_top = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(wf_top)
    sample = {
        "t": time.time(), "seq": 3, "dataflow": "job", "nodes": [],
        "dead_letters": 0,
        "counters": {"ctl_rescale_up": 2, "ctl_decisions": 5,
                     "other": 1},
        "gauges": {"ctl_width_kf": 4.0, "ctl_admission_rate": 50000.0,
                   "ctl_soft_limit": 12.0},
        "histograms": {},
    }
    frame = wf_top.render(sample, None)
    assert "control:" in frame
    assert "width[kf]=4" in frame
    assert "admit[*]=50000/s" in frame
    assert "soft_limit=12" in frame
    assert "rescale_up=2" in frame
    # ctl counters live on the control line, not the counters line
    assert "counters: other=1" in frame


# ------------------------------------------------------- knob contract


def test_control_unset_never_imports_package():
    """Seed contract: control= unset => windflow_tpu.control is never
    imported (subprocess keeps sys.modules clean)."""
    code = (
        "import sys\n"
        "import numpy as np\n"
        "from windflow_tpu.api import MultiPipe\n"
        "from windflow_tpu.core.tuples import Schema\n"
        "from windflow_tpu.patterns.basic import Sink, Source\n"
        "S = Schema(value=np.int64)\n"
        "def gen(sh):\n"
        "    sh.push(key=0, id=0, ts=0, value=1)\n"
        "got = []\n"
        "p = (MultiPipe('seed')\n"
        "     .add_source(Source(gen, S))\n"
        "     .chain_sink(Sink(lambda b: got.append(b),"
        " vectorized=True)))\n"
        "p.run_and_wait_end()\n"
        "assert any(b is not None and len(b) for b in got)\n"
        "bad = [m for m in sys.modules"
        " if m.startswith('windflow_tpu.control')]\n"
        "assert not bad, f'control package imported on seed path: {bad}'\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


def test_preview_build_keeps_initial_width():
    """getNumThreads() before run() must not promote the pre-provisioned
    ceiling into the initial active width (the preview build provisions
    the same pattern object)."""
    oracle = []
    _build_pipe(oracle, KeyFarm(Reducer("sum", "value"), 4, 4,
                                pardegree=2, name="kf")
                ).run_and_wait_end(timeout=120)
    got = []
    pipe = _build_pipe(
        got, KeyFarm(Reducer("sum", "value"), 4, 4, pardegree=2,
                     name="kf"),
        control=ControlPolicy([_manual_rule()], period=0.05),
        recovery=RecoveryPolicy(epoch_batches=5, restart_backoff=0.01),
        metrics=True)
    n = pipe.getNumThreads()
    _run_quiet(lambda: pipe.run_and_wait_end(timeout=120))
    assert pipe.getNumThreads() == n      # preview == materialised
    assert pipe.controller.width_of("kf") == 2
    assert per_key(got) == per_key(oracle)


def test_union_control_policies_must_agree():
    from windflow_tpu import union_multipipes

    def gen(sh):
        sh.push(key=0, id=0, ts=0, value=1)

    def mk(name, pol):
        p = MultiPipe(name, metrics=True, control=pol)
        p.add_source(Source(gen, SCHEMA))
        return p

    adm = [Admission(max_rate=10, min_rate=1, high_depth=8, low_depth=2)]
    u = union_multipipes(mk("a", ControlPolicy(adm)), mk("b", None))
    assert u.control is not None
    with pytest.raises(ValueError, match="conflicting control"):
        union_multipipes(
            mk("c", ControlPolicy(adm)),
            mk("d", ControlPolicy(adm, period=9.0)))


def test_blind_control_runs_without_controller():
    """control= without metrics/sample_period: warned (WF209) and
    inert, but the graph still runs to completion."""
    got = []
    pipe = _build_pipe(
        got, KeyFarm(Reducer("sum", "value"), 4, 4, pardegree=2,
                     name="kf"),
        control=ControlPolicy([_manual_rule()], period=0.05),
        recovery=RecoveryPolicy(epoch_batches=10))
    with pytest.warns(UserWarning, match="WF209"):
        pipe.run_and_wait_end(timeout=120)
    assert pipe.controller is None
    assert got


# ------------------------------------------------------------- soak slice


@pytest.mark.slow
def test_soak_rescale_slice():
    """Small in-suite slice of scripts/soak_rescale.py (the full soak is
    a standalone seeded harness, docs/CONTROL.md)."""
    spec = importlib.util.spec_from_file_location(
        "soak_rescale", os.path.join(REPO, "scripts", "soak_rescale.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    total = 0
    for case in range(6):
        total += mod.run_case(seed=23, case=case)["rescales"]
    assert total > 0, "no rescale completed across the slice"
