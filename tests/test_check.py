"""Static graph validation (windflow_tpu/check/, docs/CHECKS.md):

* a parametrized corpus where every WF### id has a minimal failing
  graph AND a minimally-fixed twin that must validate clean;
* the ``check=`` knob contract: unset never imports the package,
  'warn' reports CheckWarnings and still runs, 'error' raises
  CheckError before any thread starts (WF id + node_stats_name in the
  message), union merges by strictness;
* suppression directives (``# wf-lint: disable=WF###``) and the
  closure analyzer's lock heuristic;
* the tier-1 self-lint: the four bench apps validate diagnostic-free;
* the ``scripts/wf_lint.py`` CLI over the seeded misconfig corpus
  (tests/check_corpus.py) and over the bench apps.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time
import warnings

import numpy as np
import pytest

from windflow_tpu.api import MultiPipe, union_multipipes
from windflow_tpu.check import CheckError, CheckWarning, validate
from windflow_tpu.control import Admission, ControlPolicy, Rescale
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.patterns.key_farm import KeyFarm
from windflow_tpu.core.tuples import Schema
from windflow_tpu.core.windows import WindowSpec, WinType
from windflow_tpu.parallel.channel import WireConfig
from windflow_tpu.parallel.plane import PlanePolicy
from windflow_tpu.patterns.basic import (Map, Sink, Source,
                                         _AccumulatorNode)
from windflow_tpu.patterns.pane_farm import PaneFarm
from windflow_tpu.patterns.win_seq import WinSeq, WinSeqNode
from windflow_tpu.recovery.policy import RecoveryPolicy
from windflow_tpu.runtime.emitters import StandardEmitter, default_routing
from windflow_tpu.runtime.engine import Dataflow
from windflow_tpu.runtime.overload import OverloadPolicy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCHEMA = Schema(value=np.int64)


@pytest.fixture(autouse=True)
def _no_ambient_obs_env(monkeypatch):
    """The corpus pins exact diagnostic sets: an ambient WF_LOG_DIR
    would silence WF207, an ambient WF_SAMPLE_PERIOD would plant it
    everywhere."""
    monkeypatch.delenv("WF_LOG_DIR", raising=False)
    monkeypatch.delenv("WF_SAMPLE_PERIOD", raising=False)


def _src(shipper):
    return None


def _red(key, gwid, rows):
    return {"value": rows["value"].sum()}


def _win_fields():
    return {"value": np.int64}


def _sink():
    return Sink(lambda b: None, vectorized=True)


def _pipe(*patterns, **kw):
    p = MultiPipe(kw.pop("name", "chk"), **kw)
    p.add_source(Source(_src, SCHEMA))
    for pat in patterns:
        p.add(pat)
    p.add_sink(_sink())
    return p


# ------------------------------------------------------- stub cores

class NativeResidentCore:
    """Stub matching the WF215 duck-type probe (class name + missing
    has_state_abi), so the corpus runs with or without the native .so.
    The real core sets ``has_state_abi`` from the loaded library; the
    stub's default (absent → False) models a pre-ABI .so."""
    spec = WindowSpec(4, 2, WinType.CB)

    def __init__(self, abi=False):
        if abi:
            self.has_state_abi = True


class _StubAsyncCore:
    """Async device core shape: process_batches + max_delay_s."""
    spec = WindowSpec(4, 2, WinType.CB)
    max_delay_s = None

    def process_batches(self, batch):
        return []


def _acc_node(name):
    return _AccumulatorNode(lambda row, acc: None, None, SCHEMA, name,
                            rich=False)


def _routing_df(routing):
    df = Dataflow("route")
    em = df.add(StandardEmitter(2, routing, name="em"))
    a = df.add(_acc_node("acc.0"))
    b = df.add(_acc_node("acc.1"))
    df.connect(em, a)
    df.connect(em, b)
    return df


def _native_df(abi=False):
    df = Dataflow("nat", recovery=RecoveryPolicy())
    df.add(WinSeqNode(NativeResidentCore(abi=abi), name="agg.0"))
    return df


def _async_df(max_delay):
    core = _StubAsyncCore()
    core.max_delay_s = max_delay
    df = Dataflow("dev", recovery=RecoveryPolicy())
    df.add(WinSeqNode(core, name="agg.0"))
    return df


def _comb_df(async_first):
    from windflow_tpu.runtime.comb import make_comb
    from windflow_tpu.patterns.basic import _MapNode
    win = WinSeqNode(_StubAsyncCore(), name="agg.0")
    mp = _MapNode(lambda b: None, "map.0", False, True, None)
    stages = [win, mp] if async_first else [mp, win]
    df = Dataflow("comb", recovery=RecoveryPolicy())
    df.add(make_comb(stages, name="chain.0"))
    return df


def _recovery_sink_pipe(opt_in):
    s = _sink()
    if opt_in:
        s.recoverable = True
    p = MultiPipe("recsink", recovery=RecoveryPolicy())
    p.add_source(Source(_src, SCHEMA))
    p.add_sink(s)
    return p


def _join_pipe(recovery):
    from windflow_tpu.patterns.win_join_tpu import WinJoinTPU
    join = WinJoinTPU(1000, side_field="value", left=(0, "key"),
                      right=(1, "key"), key_range=(0, 1 << 20),
                      window_rows=1024)
    return _pipe(join, name="joined",
                 recovery=RecoveryPolicy() if recovery else None)


def _race_pipe(guarded):
    counts = [0]
    lock = threading.Lock()

    if guarded:
        def bump(batch):
            with lock:
                counts[0] += len(batch)
    else:
        def bump(batch):
            counts[0] += len(batch)

    return _pipe(Map(bump, parallelism=2, vectorized=True))


def _ctl_pipe(t, *, rescale=True, recovery=True, obs=True,
              recoverable=None, target="kf"):
    """Control-plane corpus factory (WF209-212): a keyed farm under a
    ControlPolicy, with the blinding / recoverable / recovery / target
    knobs toggled per case.  The sink opts into restart so recovery=
    twins stay WF204-clean."""
    if rescale:
        rules = [Rescale(target, max_workers=4)]
    else:
        rules = [Admission(max_rate=1e6, min_rate=1e3, high_depth=8,
                           low_depth=2)]
    kf = KeyFarm(Reducer("sum", "value"), win_len=8, slide_len=4,
                 pardegree=2, name="kf")
    if recoverable is not None:
        kf.recoverable = recoverable
    s = _sink()
    s.recoverable = True
    p = MultiPipe("ctl", control=ControlPolicy(rules),
                  recovery=RecoveryPolicy() if recovery else None,
                  metrics=True if obs else None,
                  trace_dir=str(t) if obs else None)
    p.add_source(Source(_src, SCHEMA))
    p.add(kf)
    p.add_sink(s)
    return p


def _trace_pipe(trace_dir):
    from windflow_tpu.obs.trace import TracePolicy
    return _pipe(name="tr", trace=TracePolicy(sample_rate=0.5),
                 trace_dir=trace_dir)


def _fed_pipe(t, obs=False):
    from windflow_tpu.obs.federation import FederationPolicy
    kw = dict(metrics=True, trace_dir=str(t)) if obs else {}
    return _pipe(name="fed", federate=FederationPolicy(host="chk"), **kw)


_G = 0


def _global_pipe(bad):
    if bad:
        def fn(batch):
            global _G
            _G += 1
    else:
        def fn(batch):
            return None
    return _pipe(Map(fn, parallelism=2, vectorized=True))


def _plane(flaw=None):
    """WF22x corpus: a declared 2-host plane (check/plane.py), clean by
    construction; ``flaw`` plants exactly one defect."""
    from windflow_tpu.check.plane import HostSpec, PlaneSpec
    wire = WireConfig(connect_deadline=30.0, heartbeat=2.0,
                      stall_timeout=10.0, resume=True, recovery=True)
    addresses = {0: ("10.0.0.1", 9000), 1: ("10.0.0.2", 9000)}
    if flaw == "orphan":
        addresses[2] = ("10.0.0.3", 9000)
    h0 = HostSpec(0, sends="<i8", resume=True,
                  plane=PlanePolicy(wire=wire), federate=True)
    h1 = HostSpec(1, sends="<i8",
                  expects="<f8" if flaw == "dtype" else None,
                  resume=None if flaw == "resume" else True,
                  ckpt_sink=None if flaw == "nosink" else True,
                  aggregator=flaw != "noagg")
    return PlaneSpec(addresses, [h0, h1], name="pl", wire=wire)


def _replay_pipe(kind):
    """WF303/WF304 corpus: a recoverable Map under recovery= whose fn
    commits (or avoids) the flagged effect."""
    if kind == "time":
        def fn(b):
            if b is not None:
                b["ts"][:] = int(time.time())
            return b
    elif kind == "rng":
        rng = np.random.default_rng(7)

        def fn(b):
            if b is not None:
                b["value"][:] = rng.integers(0, 10, len(b))
            return b
    elif kind == "file":
        def fn(b):
            open(os.devnull, "a").close()
            return b
    else:
        def fn(b):
            return b
    s = _sink()
    s.recoverable = True
    p = MultiPipe("eff", recovery=RecoveryPolicy())
    p.add_source(Source(_src, SCHEMA))
    p.add(Map(fn, vectorized=True))
    p.add_sink(s)
    return p


def _latency_pipe(t, blocking, latency=True):
    """WF305 corpus: a keyed farm whose window fn does (or does not)
    block, under a Rescale rule that is (or is not) latency-triggered."""
    if blocking:
        def wfn(key, gwid, rows):
            time.sleep(0.001)
            return {"value": rows["value"].sum()}
    else:
        def wfn(key, gwid, rows):
            return {"value": rows["value"].sum()}
    rule = (Rescale("kf", max_workers=4, up_q95_us=5000.0) if latency
            else Rescale("kf", max_workers=4))
    kf = KeyFarm(wfn, win_len=8, slide_len=4, pardegree=2, name="kf",
                 result_fields=_win_fields())
    s = _sink()
    s.recoverable = True
    p = MultiPipe("lat", control=ControlPolicy([rule]),
                  recovery=RecoveryPolicy(), metrics=True,
                  trace_dir=str(t))
    p.add_source(Source(_src, SCHEMA))
    p.add(kf)
    p.add_sink(s)
    return p


#: WF### -> (bad_factory, good_factory); factories take tmp_path.
#: Every bad graph must report exactly its id (subset check: the id is
#: present); every good twin must validate with ZERO diagnostics.
CORPUS = {
    "WF101": (lambda t: _routing_df(None),
              lambda t: _routing_df(default_routing)),
    "WF102": (lambda t: _pipe(WinSeq(_red, 4, 8, WinType.CB,
                                     result_fields=_win_fields())),
              lambda t: _pipe(WinSeq(_red, 8, 4, WinType.CB,
                                     result_fields=_win_fields()))),
    "WF103": (lambda t: _pipe(PaneFarm(_red, _red, 10, 3, WinType.CB,
                                       plq_result_fields=_win_fields(),
                                       wlq_result_fields=_win_fields())),
              lambda t: _pipe(PaneFarm(_red, _red, 10, 5, WinType.CB,
                                       plq_result_fields=_win_fields(),
                                       wlq_result_fields=_win_fields()))),
    "WF202": (lambda t: _async_df(0.005), lambda t: _async_df(None)),
    "WF203": (lambda t: _comb_df(async_first=True),
              lambda t: _comb_df(async_first=False)),
    "WF204": (lambda t: _recovery_sink_pipe(False),
              lambda t: _recovery_sink_pipe(True)),
    "WF205": (lambda t: WireConfig(heartbeat=5.0, stall_timeout=2.0),
              lambda t: WireConfig.hardened()),
    "WF206": (lambda t: WireConfig(heartbeat=2.0),
              lambda t: WireConfig(heartbeat=2.0, stall_timeout=10.0)),
    "WF207": (lambda t: _pipe(name="obs", metrics=True),
              lambda t: _pipe(name="obs", metrics=True,
                              trace_dir=str(t))),
    "WF208": (lambda t: _pipe(name="ovl", capacity=0,
                              overload=OverloadPolicy(shed="shed_newest")),
              lambda t: _pipe(name="ovl", capacity=16,
                              overload=OverloadPolicy(shed="shed_newest"))),
    "WF209": (lambda t: _ctl_pipe(t, rescale=False, recovery=False,
                                  obs=False),
              lambda t: _ctl_pipe(t, rescale=False, recovery=False)),
    "WF210": (lambda t: _ctl_pipe(t, recoverable=False),
              lambda t: _ctl_pipe(t)),
    "WF211": (lambda t: _ctl_pipe(t, recovery=False),
              lambda t: _ctl_pipe(t)),
    "WF212": (lambda t: _ctl_pipe(t, target="kfarm"),
              lambda t: _ctl_pipe(t)),
    "WF213": (lambda t: _trace_pipe(None),
              lambda t: _trace_pipe(str(t))),
    "WF214": (lambda t: WireConfig(resume=True),
              lambda t: WireConfig(resume=True, recovery=True)),
    "WF215": (lambda t: _native_df(), lambda t: _native_df(abi=True)),
    "WF217": (lambda t: _fed_pipe(t),
              lambda t: _fed_pipe(t, obs=True)),
    "WF218": (lambda t: _join_pipe(True), lambda t: _join_pipe(False)),
    "WF216": (lambda t: PlanePolicy(wire=WireConfig.hardened()),
              lambda t: PlanePolicy(wire=WireConfig(
                  connect_deadline=60.0, heartbeat=2.0,
                  stall_timeout=10.0, resume=True, recovery=True))),
    "WF220": (lambda t: _plane("orphan"), lambda t: _plane()),
    "WF221": (lambda t: _plane("dtype"), lambda t: _plane()),
    "WF222": (lambda t: _plane("resume"), lambda t: _plane()),
    "WF223": (lambda t: _plane("nosink"), lambda t: _plane()),
    "WF224": (lambda t: _plane("noagg"), lambda t: _plane()),
    "WF301": (lambda t: _race_pipe(guarded=False),
              lambda t: _race_pipe(guarded=True)),
    "WF302": (lambda t: _global_pipe(True),
              lambda t: _global_pipe(False)),
    "WF303": (lambda t: _replay_pipe("time"),
              lambda t: _replay_pipe("rng")),
    "WF304": (lambda t: _replay_pipe("file"),
              lambda t: _replay_pipe("pure")),
    "WF305": (lambda t: _latency_pipe(t, blocking=True),
              lambda t: _latency_pipe(t, blocking=False)),
}


def test_corpus_covers_catalog():
    from windflow_tpu.check.diagnostics import CATALOG
    assert set(CORPUS) == set(CATALOG), (
        "every catalog id needs a minimal failing graph + fixed twin")


@pytest.mark.parametrize("code", sorted(CORPUS))
def test_minimal_failing_graph(code, tmp_path):
    bad, _good = CORPUS[code]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # WF207's construction warning
        report = validate(bad(tmp_path))
    assert code in report.codes(), (
        f"{code} not reported; got: {report.render()}")
    from windflow_tpu.check.diagnostics import CATALOG
    for d in report:
        if d.code == code:
            assert d.severity == CATALOG[code][0]


@pytest.mark.parametrize("code", sorted(CORPUS))
def test_minimally_fixed_twin(code, tmp_path):
    _bad, good = CORPUS[code]
    report = validate(good(tmp_path))
    assert len(report) == 0, (
        f"fixed twin for {code} still reports: {report.render()}")


# ---------------------------------------------------------- knob tests

def test_check_error_raises_before_threads():
    """Acceptance (ISSUE 11): an error diagnostic (recovery= x
    max_delay_ms device core) under check='error' raises BEFORE any
    thread starts, naming the WF id and the node's canonical
    node_stats_name."""
    df = _async_df(0.005)
    df.check = "error"
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(CheckError) as ei:
            df.run()
    msg = str(ei.value)
    assert "WF202" in msg
    from windflow_tpu.utils.tracing import node_stats_name
    assert node_stats_name("dev", 0, "agg.0") in msg
    assert df._threads == []          # nothing started
    assert ei.value.report.has_errors


def test_check_native_stale_so_is_warning():
    """ISSUE 17: the retired WF201 error is now the WF215 warning — a
    native core on a pre-ABI .so under recovery= warns (default paths
    run; the first snapshot declines loudly at the barrier) instead of
    blocking run, and a state-ABI library reports nothing."""
    report = validate(_native_df())
    [d] = [d for d in report if d.code == "WF215"]
    assert d.severity == "warning"
    assert not report.has_errors      # check='error' no longer blocks
    from windflow_tpu.utils.tracing import node_stats_name
    assert d.node == node_stats_name("nat", 0, "agg.0")
    from windflow_tpu.check.diagnostics import CATALOG
    assert "WF201" not in CATALOG     # retired, never reused


def test_check_warn_reports_and_still_runs():
    pipe = _pipe(WinSeq(_red, 4, 8, WinType.CB,
                        result_fields=_win_fields()),
                 name="warnrun", check="warn")
    with pytest.warns(CheckWarning, match="WF102"):
        pipe.run_and_wait_end()


def test_check_mode_validated():
    with pytest.raises(ValueError, match="check="):
        Dataflow("bad", check="loud")


def test_check_events_mirrored(tmp_path):
    """check diagnostics land in the event log (kind 'check') when the
    graph is observed."""
    pipe = _pipe(WinSeq(_red, 4, 8, WinType.CB,
                        result_fields=_win_fields()),
                 name="evt", check="warn", metrics=True,
                 trace_dir=str(tmp_path))
    with pytest.warns(CheckWarning):
        pipe.run_and_wait_end()
    kinds = [e for e in pipe.events.recent if e["event"] == "check"]
    assert kinds and kinds[0]["code"] == "WF102"
    assert kinds[0]["severity"] == "warning"


def test_union_takes_strictest_check_mode():
    def mk(name, check):
        p = MultiPipe(name, check=check)
        p.add_source(Source(_src, SCHEMA))
        return p
    u = union_multipipes(mk("a", "warn"), mk("b", "error"))
    assert u.check == "error"
    u2 = union_multipipes(mk("c", "off"), mk("d", None))
    assert u2.check == "off"
    u3 = union_multipipes(mk("e", None), mk("f", None))
    assert u3.check is None
    with pytest.raises(ValueError, match="check="):
        MultiPipe("typo", check="eror")   # eager, not deferred to run()


def test_union_branch_trace_dir_no_false_wf207(tmp_path):
    """A union where one branch supplies metrics and the OTHER the
    trace_dir writes telemetry — no WF207 on the merged graph."""
    a = MultiPipe("a", metrics=True)
    a.add_source(Source(_src, SCHEMA))
    b = MultiPipe("b", trace_dir=str(tmp_path))
    b.add_source(Source(_src, SCHEMA))
    u = union_multipipes(a, b)
    u.add_sink(_sink())
    report = validate(u)
    assert "WF207" not in report.codes(), report.render()


def test_check_unset_never_imports_package():
    """Seed contract: check= unset => the check package is never
    imported (subprocess keeps sys.modules clean)."""
    code = textwrap.dedent("""
        import sys
        import numpy as np
        from windflow_tpu.api import MultiPipe
        from windflow_tpu.core.tuples import Schema
        from windflow_tpu.patterns.basic import Sink, Source
        S = Schema(value=np.int64)
        def gen(sh):
            sh.push(key=0, id=0, ts=0, value=1)
        got = []
        p = (MultiPipe("seed")
             .add_source(Source(gen, S))
             .chain_sink(Sink(lambda b: got.append(b), vectorized=True)))
        p.run_and_wait_end()
        assert any(b is not None and len(b) for b in got)
        bad = [m for m in sys.modules if m.startswith("windflow_tpu.check")]
        assert not bad, f"check package imported on seed path: {bad}"
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


def test_wf207_one_shot_engine_warning():
    """Satellite (ISSUE 11): metrics with no resolvable trace_dir warns
    at construction, naming the missing knob."""
    with pytest.warns(UserWarning, match=r"WF207.*trace_dir"):
        Dataflow("noop", metrics=True)


def test_wireconfig_validate_raises():
    with pytest.raises(ValueError, match="WF205"):
        WireConfig(heartbeat=5.0, stall_timeout=2.0).validate()
    WireConfig.hardened().validate()     # clean config chains through


def test_open_row_plane_rejects_bad_wire():
    from windflow_tpu.parallel.multihost import open_row_plane
    with pytest.raises(ValueError, match="WF205"):
        open_row_plane(0, {0: ("127.0.0.1", 1), 1: ("127.0.0.1", 2)},
                       wire=WireConfig(heartbeat=9.0, stall_timeout=1.0))


# ------------------------------------------------- suppression directives

def _validate_tmp_module(tmp_path, body, name):
    mod = tmp_path / f"{name}.py"
    mod.write_text(body)
    import importlib.util
    spec = importlib.util.spec_from_file_location(name, str(mod))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return validate(m.build())


_SUPPRESSED_SRC = """
import numpy as np
from windflow_tpu.api import MultiPipe
from windflow_tpu.core.tuples import Schema
from windflow_tpu.core.windows import WinType
from windflow_tpu.patterns.basic import Map, Sink, Source
from windflow_tpu.patterns.win_seq import WinSeq

S = Schema(value=np.int64)
RF = {{"value": np.int64}}


def red(k, g, r):
    return {{"value": r["value"].sum()}}


def build():
    counts = [0]

    def bump(b):
        counts[0] += len(b){mark301}

    win = WinSeq(red, 4, 8, WinType.CB, result_fields=RF){mark102}
    return (MultiPipe("sup")
            .add_source(Source(lambda sh: None, S))
            .add(Map(bump, parallelism=2, vectorized=True))
            .add(win)
            .chain_sink(Sink(lambda b: None, vectorized=True)))
"""


def test_suppression_directives(tmp_path):
    noisy = _validate_tmp_module(
        tmp_path, _SUPPRESSED_SRC.format(mark301="", mark102=""),
        "wfmod_noisy")
    assert {"WF301", "WF102"} <= noisy.codes()

    quiet = _validate_tmp_module(
        tmp_path, _SUPPRESSED_SRC.format(
            mark301="   # wf-lint: disable=WF301",
            mark102="   # wf-lint: disable=WF102"),
        "wfmod_quiet")
    assert quiet.codes() == set()
    assert {d.code for d in quiet.suppressed} >= {"WF102"}


def test_directive_parser():
    from windflow_tpu.check.directives import parse_directive
    assert parse_directive("x = 1  # wf-lint: disable=WF102") == {"WF102"}
    assert parse_directive("# wf-lint: disable=wf102, WF301") == \
        {"WF102", "WF301"}
    assert parse_directive("# wf-lint: disable") == {"all"}
    assert parse_directive("# wf-lint:disable=WF102") == {"WF102"}
    assert parse_directive("plain line") is None
    # a typo'd id suppresses NOTHING — it must never widen to "all"
    assert parse_directive("# wf-lint: disable=nonsense") == set()
    assert parse_directive("# wf-lint: disable=WF30l") == set()


# ------------------------------------------------- effect analyzer (WF30x)

def _stamp_helper():
    return time.time()


def test_effects_seeded_generator_exempt():
    """A fn that captures a seeded Generator is trusted for WF303 —
    seeded-generator state rides the snapshot, the blessed pattern."""
    from windflow_tpu.check.effects import analyze_effects

    def bad(b):
        np.random.shuffle(b)

    def good(b, _rng=np.random.default_rng(7)):
        np.random.shuffle(b)

    assert any(d.code == "WF303"
               for d in analyze_effects(bad, {"WF303"}, "kf"))
    assert analyze_effects(good, {"WF303"}, "kf") == []


def test_effects_helper_following():
    """One level of same-module call following: a helper defined next
    to the user fn is scanned too, reported 'via helper'."""
    from windflow_tpu.check.effects import analyze_effects

    def fn(b):
        return _stamp_helper()

    ds = analyze_effects(fn, {"WF303"}, "m")
    assert ds and ds[0].code == "WF303"
    assert "via helper" in ds[0].message
    assert "_stamp_helper" in ds[0].message


def test_effects_blocking_acquire_untimed_only():
    """WF305's name heuristic: an untimed .acquire() flags, a timed one
    (bounded wait) does not."""
    from windflow_tpu.check.effects import analyze_effects
    lk = threading.Lock()

    def bad(b):
        lk.acquire()
        lk.release()

    def good(b):
        if lk.acquire(timeout=0.1):
            lk.release()

    assert any(d.code == "WF305"
               for d in analyze_effects(bad, {"WF305"}, "svc"))
    assert analyze_effects(good, {"WF305"}, "svc") == []


def test_effects_gating_by_contract(tmp_path):
    """A blocking fn under a depth-triggered Rescale (no up_q95_us/
    up_slo_burn) must NOT arm WF305 — the rule does not watch latency."""
    report = validate(_latency_pipe(tmp_path, blocking=True,
                                    latency=False))
    assert "WF305" not in report.codes(), report.render()


def test_effects_suppression_directive(tmp_path):
    """# wf-lint: disable=WF303 on the call line suppresses, same as
    the closure analyzer's directives."""
    from windflow_tpu.check.effects import analyze_effects
    mod = tmp_path / "eff_sup.py"
    mod.write_text(textwrap.dedent("""
        import time

        def noisy(b):
            return time.time()

        def quiet(b):
            return time.time()   # wf-lint: disable=WF303
    """))
    import importlib.util
    spec = importlib.util.spec_from_file_location("eff_sup", str(mod))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    assert any(d.code == "WF303"
               for d in analyze_effects(m.noisy, {"WF303"}, "n"))
    assert analyze_effects(m.quiet, {"WF303"}, "n") == []


# ------------------------------------------------------------- self-lint

APP_MODULES = ("windflow_tpu.apps.micro", "windflow_tpu.apps.pipe",
               "windflow_tpu.apps.spatial", "windflow_tpu.apps.ysb")


@pytest.mark.parametrize("modname", APP_MODULES)
def test_bench_apps_self_lint(modname):
    """Tier-1 gate (ISSUE 11): the four bundled bench apps validate
    diagnostic-free through their wf_check_pipelines() hooks."""
    import importlib
    mod = importlib.import_module(modname)
    targets = mod.wf_check_pipelines()
    assert targets
    for target in targets:
        report = validate(target)
        assert len(report) == 0, (
            f"{modname}: {report.render()}")


SOAK_SCRIPTS = ("soak_overload.py", "soak_crash.py", "soak_rescale.py",
                "soak_wire.py", "soak_handoff.py", "wf_roll.py")


def _load_script(fname):
    import importlib.util
    path = os.path.join(REPO, "scripts", fname)
    name = os.path.splitext(fname)[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("fname", SOAK_SCRIPTS)
def test_soak_scripts_self_lint(fname):
    """Tier-1 gate (ISSUE 20): the soak/roll scripts validate
    diagnostic-free through their wf_check_pipelines() hooks — incl.
    the new WF30x effect analysis over their recovery-opted sinks and
    the WF22x plane lint of soak_handoff's declared topology."""
    mod = _load_script(fname)
    targets = mod.wf_check_pipelines()
    assert targets
    for target in targets:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            report = validate(target)
        assert len(report) == 0, f"{fname}: {report.render()}"


# ------------------------------------------------------------ wf-lint CLI

def _run_lint(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("WF_LOG_DIR", None)
    env.pop("WF_SAMPLE_PERIOD", None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "wf_lint.py"),
         *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)


def _load_corpus():
    import importlib.util
    path = os.path.join(REPO, "tests", "check_corpus.py")
    spec = importlib.util.spec_from_file_location("check_corpus", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_wf_lint_cli_corpus():
    """The CLI reports every planted diagnostic of the seeded misconfig
    corpus and (under --error) exits nonzero."""
    r = _run_lint(["tests/check_corpus.py", "--error"])
    assert r.returncode == 1, r.stdout + r.stderr
    corpus = _load_corpus()
    for code in corpus.PLANTED:
        assert code in r.stdout, (
            f"{code} missing from CLI output:\n{r.stdout}\n{r.stderr}")


@pytest.mark.slow
def test_wf_lint_cli_apps_clean():
    """All four bench apps lint clean through the CLI (exit 0 even with
    --error).  Slow-marked: the subprocess cold-imports jax + the apps;
    the in-process self-lint above is the tier-1 gate."""
    r = _run_lint(["--error", *APP_MODULES])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 diagnostic(s)" in r.stdout


def test_wf_lint_cli_plane_corpus():
    """Acceptance (ISSUE 20): --plane over the seeded misconfigured
    2-host spec reports the full planted WF22x + cross-host set; the
    minimally-fixed twin reports zero."""
    r = _run_lint(["--plane", "tests/plane_corpus.py", "--error"])
    assert r.returncode == 1, r.stdout + r.stderr
    import importlib.util
    path = os.path.join(REPO, "tests", "plane_corpus.py")
    spec = importlib.util.spec_from_file_location("plane_corpus", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for code in mod.PLANTED:
        assert code in r.stdout, (
            f"{code} missing from --plane output:\n{r.stdout}\n{r.stderr}")

    r2 = _run_lint(["--plane", "tests/plane_corpus_fixed.py", "--error"])
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "0 diagnostic(s)" in r2.stdout


def test_wf_lint_cli_json():
    """--json emits one machine-readable document: every planted id of
    the misconfig corpus as {id, severity, module, target, message}
    records plus the target count."""
    r = _run_lint(["tests/check_corpus.py", "--json"])
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["targets"] > 0
    recs = doc["diagnostics"]
    corpus = _load_corpus()
    assert set(corpus.PLANTED) <= {d["id"] for d in recs}
    for d in recs:
        assert {"id", "severity", "module", "target", "message"} <= set(d)
    anchored = [d for d in recs if "file" in d]
    assert anchored and all(isinstance(d["line"], int) for d in anchored)


def test_wf_lint_cli_module_scan_fallback(tmp_path):
    """A manual-graph script with NO wf_check_pipelines() hook is still
    lintable: module-level Dataflow objects are picked up by the
    fallback scan (here a round-robin emitter over keyed state ->
    WF101)."""
    mod = tmp_path / "manual_graph.py"
    mod.write_text(textwrap.dedent("""
        import numpy as np
        from windflow_tpu.core.tuples import Schema
        from windflow_tpu.patterns.basic import _AccumulatorNode
        from windflow_tpu.runtime.emitters import StandardEmitter
        from windflow_tpu.runtime.engine import Dataflow

        S = Schema(value=np.int64)
        DF = Dataflow("manual")
        _em = DF.add(StandardEmitter(2, None, name="em"))
        _a = DF.add(_AccumulatorNode(lambda row, acc: None, None, S,
                                     "acc.0", rich=False))
        _b = DF.add(_AccumulatorNode(lambda row, acc: None, None, S,
                                     "acc.1", rich=False))
        DF.connect(_em, _a)
        DF.connect(_em, _b)
    """))
    r = _run_lint([str(mod), "--error"])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "WF101" in r.stdout


def test_wf_lint_cli_exit2_contract():
    """Usage/import failures exit 2, distinct from 'findings' (1) and
    'clean' (0) — the documented scriptable contract."""
    r = _run_lint([])
    assert r.returncode == 2
    r = _run_lint(["tests/no_such_module_xyz.py"])
    assert r.returncode == 2
    # a module with no lintable targets is a usage error too
    r = _run_lint(["tests/oracle.py"])
    assert r.returncode == 2
