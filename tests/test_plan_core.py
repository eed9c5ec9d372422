"""The window core router: ``plan_core`` decides which core and executor
family run a window function, from its arguments alone, and ``make_core_for``
builds what it says.  The table's expected values were read off the router as
it stood before ``plan_core`` existed (one case per branch, refusals
included), so it pins the routing across the rewrite."""

import warnings

import numpy as np
import pytest

from windflow_tpu.core.tuples import Schema, batch_from_columns
from windflow_tpu.core.windows import WindowSpec, WinType
from windflow_tpu.ops.functions import ArgReducer, MultiReducer, Reducer
from windflow_tpu.parallel.mesh import make_mesh
from windflow_tpu.patterns.win_seq_tpu import (CorePlan, JaxWindowFunction,
                                               _native_core_fields,
                                               make_core_for, plan_core)

CB = WindowSpec(16, 4, WinType.CB)
TB = WindowSpec(10, 10, WinType.TB)
R = dict(value_range=(0, 100))
#: what the library reports (wf_max_fields) where it is built
FIELDS = 4


def arg():
    return ArgReducer("max", "price", **R)


def jax_fn():
    return JaxWindowFunction(lambda k, g, c, m: c["value"].sum(axis=1))


def count_max_ts():
    return MultiReducer(("count", None, "n"), ("max", "ts", "last"))


def ysb():
    return MultiReducer(("count", None, "n"), ("max", "ts", "last"),
                        Reducer("sum", "rev", **R))


def two_fields():
    return MultiReducer(Reducer("sum", "a", **R), Reducer("max", "b", **R))


def two_ops_one_field():
    return MultiReducer(Reducer("sum", "a", out_field="s", **R),
                        Reducer("max", "a", out_field="m", **R))


def fsum():
    return Reducer("sum", dtype=np.float32)


def isum():
    return Reducer("sum", **R)


NATIVE_ARGEXT = CorePlan("native", "argext", False)
HOST = CorePlan("host", None, False)
RESTAGE = CorePlan("restage", None, False)

#: (id, spec, window function, plan_core's options, the plan or the refusal's
#: text); ``mesh=True`` stands for a mesh, ``native`` defaults to FIELDS
CASES = [
    # -- arg-extremum: the native core's own family, or a refusal
    ("arg", CB, arg, {}, NATIVE_ARGEXT),
    ("arg+siblings", CB, lambda: MultiReducer(
        arg(), ("count", None, "n"), Reducer("sum", "qty", **R)), {},
     NATIVE_ARGEXT),
    ("arg-restage", CB, arg, dict(use_resident=False),
     "on the resident path"),
    ("arg-mesh", CB, arg, dict(mesh=True), "one shard on one device"),
    ("arg-shards", CB, arg, dict(shards=2), "one shard on one device"),
    ("arg-float-sibling", CB, lambda: MultiReducer(
        arg(), Reducer("sum", "qty", dtype=np.float32)), {},
     "sibling stats must be count or"),
    ("arg-no-native", CB, arg, dict(native=None),
     "native resident core is unavailable"),
    # -- nothing for a device to do: the host core
    ("count", CB, lambda: Reducer("count"), {}, HOST),
    ("max-id-cb", CB, lambda: Reducer("max", "id", out_field="hi"), {}, HOST),
    ("min-ts-tb", TB, lambda: Reducer("min", "ts", out_field="lo"), {}, HOST),
    ("max-ts-cb-is-device-work", CB,
     lambda: Reducer("max", "ts", out_field="hi", **R), {},
     CorePlan("native", "regular", False)),
    ("count+max-ts", TB, count_max_ts, {}, HOST),
    ("count-restage", CB, lambda: Reducer("count"), dict(use_resident=False),
     RESTAGE),
    # -- MultiReducer: resident only
    ("count-only-forced", CB, lambda: MultiReducer(("count", None, "n")),
     dict(use_resident=True), "needs >=1 non-count stat"),
    ("multi-restage", CB, ysb, dict(use_resident=False),
     "resident device path only"),
    ("multi-float-sum", CB, lambda: MultiReducer(
        Reducer("sum", "x", dtype=np.float32), ("count", None, "n")), {},
     "no float sum"),
    ("ysb", TB, ysb, {}, CorePlan("native", "regular", False)),
    ("ysb-no-native", TB, ysb, dict(native=None),
     CorePlan("resident_py", "regular", False)),
    ("ysb-mesh", TB, ysb, dict(mesh=True),
     CorePlan("native", "regular", True)),
    ("two-fields", CB, two_fields, {}, CorePlan("native", "multi", False)),
    ("two-fields-mesh", CB, two_fields, dict(mesh=True),
     CorePlan("native", "multi", True)),
    ("two-fields-no-native", CB, two_fields, dict(native=None),
     CorePlan("resident_py", "multi", False)),
    ("two-ops-one-field", CB, two_ops_one_field, {},
     CorePlan("native", "multi", False)),
    ("two-ops-one-field-no-native", CB, two_ops_one_field, dict(native=None),
     CorePlan("resident_py", "regular", False)),
    # -- what the native core does not take goes to the Python one
    ("five-fields", CB, lambda: MultiReducer(
        *[Reducer("sum", f, out_field="s" + f, **R) for f in "abcde"]), {},
     CorePlan("resident_py", "multi", False)),
    ("one-field-of-one", CB, lambda: MultiReducer(
        *[Reducer("sum", f, out_field="s" + f, **R) for f in "ab"]),
     dict(native=1), CorePlan("resident_py", "multi", False)),
    ("float-max-beside-int-sum", CB, lambda: MultiReducer(
        Reducer("max", "x", dtype=np.float32), Reducer("sum", "a", **R)), {},
     CorePlan("resident_py", "multi", False)),
    ("one-float-max", CB, lambda: MultiReducer(
        Reducer("max", "x", dtype=np.float32), ("count", None, "n")), {},
     CorePlan("native", "regular", False)),
    ("host-free-forced", TB, count_max_ts, dict(use_resident=True),
     CorePlan("resident_py", "regular", False)),
    ("host-free-mesh", TB, count_max_ts, dict(mesh=True),
     CorePlan("resident_py", "regular", True)),
    # -- a JAX window function: restaged unless asked onto rings
    ("jax-fn", CB, jax_fn, {}, RESTAGE),
    ("jax-fn-resident", CB, jax_fn, dict(use_resident=True),
     CorePlan("resident_py", "multi", False)),
    ("jax-fn-mesh", CB, jax_fn, dict(mesh=True),
     CorePlan("resident_py", "multi", True)),
    ("jax-fn-resident-no-native", CB, jax_fn,
     dict(use_resident=True, native=None),
     CorePlan("resident_py", "multi", False)),
    # -- one Reducer
    ("sum", CB, isum, {}, CorePlan("native", "regular", False)),
    ("sum-shards", CB, isum, dict(shards=4),
     CorePlan("native", "regular", False)),
    ("sum-no-native", CB, isum, dict(native=None),
     CorePlan("resident_py", "regular", False)),
    ("sum-restage", CB, isum, dict(use_resident=False), RESTAGE),
    ("float-sum", CB, fsum, {}, RESTAGE),
    ("float-sum-forced", CB, fsum, dict(use_resident=True),
     CorePlan("native", "regular", False)),
    ("float-max", CB, lambda: Reducer("max", dtype=np.float32), {},
     CorePlan("native", "regular", False)),
    # -- on a mesh: the resident path or a refusal
    ("sum-mesh", CB, isum, dict(mesh=True),
     CorePlan("native", "regular", True)),
    ("sum-mesh-shards", CB, isum, dict(mesh=True, shards=2),
     CorePlan("native", "regular", True)),
    ("sum-mesh-no-native", CB, isum, dict(mesh=True, native=None),
     CorePlan("resident_py", "regular", True)),
    ("count-mesh", CB, lambda: Reducer("count"), dict(mesh=True),
     "needs a resident-path Reducer"),
    ("float-sum-mesh", CB, fsum, dict(mesh=True),
     "requires the resident path"),
    ("sum-mesh-restage", CB, isum, dict(mesh=True, use_resident=False),
     "requires the resident path"),
    ("float-sum-mesh-forced", CB, fsum, dict(mesh=True, use_resident=True),
     CorePlan("native", "regular", True)),
]

_FAMILY = {"ResidentWindowExecutor": "regular",
           "MultiFieldResidentExecutor": "multi",
           "ArgExtResidentExecutor": "argext"}
_CORE = {"NativeResidentCore": "native", "ResidentWinSeqCore": "resident_py",
         "DeviceWinSeqCore": "restage"}


def built(core) -> CorePlan:
    """The plan a built core embodies, read from its classes and from
    where its executor's rings live."""
    kind = _CORE.get(type(core).__name__, "host")
    ex = getattr(core, "executor", None)
    return CorePlan(kind, _FAMILY.get(type(ex).__name__),
                    getattr(ex, "mesh", None) is not None)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(n_kf=2)


def _options(opts, mesh):
    opts = dict(opts)
    if opts.get("mesh"):
        opts["mesh"] = mesh
    opts.setdefault("native", FIELDS)
    return opts


@pytest.mark.parametrize("spec,fn,opts,want",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_plan_core_table(spec, fn, opts, want, mesh, monkeypatch):
    opts = _options(opts, mesh)
    if isinstance(want, str):
        with pytest.raises(ValueError, match=want):
            plan_core(spec, fn(), **opts)
    else:
        assert plan_core(spec, fn(), **opts) == want
    # and make_core_for builds exactly that, where this host can show it
    native = opts.pop("native")
    if native is None:
        monkeypatch.setenv("WF_NO_NATIVE_CORE", "1")
    if native != _native_core_fields():
        return
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        if isinstance(want, str):
            with pytest.raises(ValueError, match=want):
                make_core_for(spec, fn(), **opts)
        else:
            assert built(make_core_for(spec, fn(), **opts)) == want


def _launch_some(core):
    ids = np.arange(64)
    core.process(batch_from_columns(Schema(value=np.int64),
                                    key=np.zeros(64), id=ids, ts=ids,
                                    value=ids % 7))
    return core.flush()


def test_plan_does_not_depend_on_what_ran_before(monkeypatch):
    """Fixed arguments, one plan: launches in this process, their measured
    service and the environment change nothing — and a stage with a latency
    budget no launch could meet still gets the core its arguments name (it
    moved to the host core once a warm-up had taught the process a floor)."""
    args = dict(use_resident=None, mesh=None, shards=1, native=FIELDS)
    before = [plan_core(CB, isum(), **args), plan_core(TB, ysb(), **args)]
    tight = make_core_for(CB, isum(), max_delay_ms=1e-6)
    assert len(_launch_some(make_core_for(CB, isum(), batch_len=4,
                                          flush_rows=16))) > 0
    assert len(_launch_some(tight)) > 0
    monkeypatch.setenv("WF_NO_NATIVE_CORE", "1")
    monkeypatch.setenv("WF_NO_NATIVE", "1")
    assert [plan_core(CB, isum(), **args),
            plan_core(TB, ysb(), **args)] == before
    monkeypatch.delenv("WF_NO_NATIVE_CORE")
    monkeypatch.delenv("WF_NO_NATIVE")
    again = make_core_for(CB, isum(), max_delay_ms=1e-6)
    assert built(again) == built(tight)
    assert built(again).core != "host"


@pytest.mark.parametrize("fn,opts", [
    (isum, {}), (isum, dict(use_resident=False)), (ysb, {}),
    (two_fields, {}), (jax_fn, dict(use_resident=True)), (arg, {}),
    (lambda: Reducer("count"), {})],
    ids=["sum", "sum-restage", "ysb", "two-fields", "jax-fn-resident", "arg",
         "count"])
@pytest.mark.parametrize("no_native", [False, True], ids=["native", "python"])
def test_max_delay_ms_is_a_timer_on_the_planned_core(fn, opts, no_native,
                                                     monkeypatch):
    if no_native:
        monkeypatch.setenv("WF_NO_NATIVE_CORE", "1")
    native = _native_core_fields()
    try:
        want = plan_core(CB, fn(), native=native, **opts)
    except ValueError:
        with pytest.raises(ValueError):
            make_core_for(CB, fn(), max_delay_ms=25, **opts)
        return
    core = make_core_for(CB, fn(), max_delay_ms=25, **opts)
    assert built(core) == want
    assert built(make_core_for(CB, fn(), **opts)) == want
    if want.core in ("native", "resident_py"):
        assert core.max_delay_s == 0.025


# -- fire_on="stream": the plan goes through stream_fire_plan, which keeps it
#    on the host cores and on the native resident core, or names the reason
SLIDING = WindowSpec(10, 5, WinType.TB)
HOPPING = WindowSpec(5, 10, WinType.TB)

#: (id, spec, window function, make_core_for's options, the built plan or
#: the refusal's text)
STREAM_CASES = [
    ("sum", SLIDING, isum, {}, CorePlan("native", "regular", False)),
    ("sum-tumbling", TB, isum, dict(holdback=30),
     CorePlan("native", "regular", False)),
    ("ysb", SLIDING, ysb, dict(holdback=7),
     CorePlan("native", "regular", False)),
    ("two-fields", SLIDING, two_fields, {},
     CorePlan("native", "multi", False)),
    ("count", SLIDING, lambda: Reducer("count"), dict(holdback=7), HOST),
    ("count+max-ts", SLIDING, count_max_ts, {}, HOST),
    ("sum-cb", CB, isum, {}, "needs time-based windows"),
    ("sum-hopping", HOPPING, isum, {}, "hopping windows stay on the host"),
    ("sum-shards", SLIDING, isum, dict(shards=2), "one shard on one device"),
    ("sum-mesh", SLIDING, isum, dict(mesh=True), "one shard on one device"),
    ("sum-max-delay", SLIDING, isum, dict(max_delay_ms=5), "wall clock"),
    ("arg", SLIDING, arg, {}, "arg-extremum family"),
    ("sum-restage", SLIDING, isum, dict(use_resident=False),
     "planned onto the 'restage' core"),
    ("sum-no-native", SLIDING, isum, dict(native=None),
     "planned onto the 'resident_py' core"),
    ("five-fields", SLIDING, lambda: MultiReducer(
        *[Reducer("sum", f, out_field="s" + f, **R) for f in "abcde"]), {},
     "planned onto the 'resident_py' core"),
    ("negative-holdback", SLIDING, isum, dict(holdback=-1), "span of time"),
]


@pytest.mark.parametrize("spec,fn,opts,want", [c[1:] for c in STREAM_CASES],
                         ids=[c[0] for c in STREAM_CASES])
def test_stream_fire_plan_table(spec, fn, opts, want, mesh, monkeypatch):
    opts = dict(opts)
    if opts.get("mesh"):
        opts["mesh"] = mesh
    if "native" in opts and opts.pop("native") is None:
        monkeypatch.setenv("WF_NO_NATIVE_CORE", "1")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        if isinstance(want, str):
            with pytest.raises(ValueError, match=want):
                make_core_for(spec, fn(), fire_on="stream", **opts)
            return
        core = make_core_for(spec, fn(), fire_on="stream", **opts)
    assert built(core) == want
    assert core.fire_on == "stream"
    assert core.holdback == opts.get("holdback", 0)
