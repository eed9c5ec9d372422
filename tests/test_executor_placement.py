"""Where a ring lives is a value the resident executor holds
(ops/resident.py: `_OneDevice`, `_OnMesh`), not a second class.

(a) the same launches through one device and through meshes of 1, 2, 4 and 8
virtual devices give the same results and the same ring contents row for
row; (b) the step keys and executable names a fixed sequence of launches
leaves, and the siblings `prewarm_regular_ladder` adds for each natural kind,
are PR 44's, read off that tree by a scratch script before the executors
were merged and written here field for field; (c) a one-device launch makes
exactly one ``jax.device_put`` of one tuple and asks nothing of the mesh
placement; (d) ``ring_snapshot`` / ``ring_restore`` round-trip under both
placements."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from windflow_tpu.ops import resident
from windflow_tpu.ops.resident import StepKey, _ANY_DEVICE, make_executor
from windflow_tpu.parallel.mesh import make_mesh
from windflow_tpu.patterns.win_seq_tpu import JaxWindowFunction

K, R, CAP = 5, 3, 64          # five keys: no multiple of 2, 4 or 8 shards
KINDS = ("regular", "minmax", "multi", "fn")
I8, I16, I32 = "|i1", "<i2", "<i4"


def _z(n):
    return np.zeros(n, dtype=np.int64)


def _udf(keys, gwids, cols, mask):
    """Tells a window by its header: a row laid out under the wrong (shard,
    slot) shows in the result."""
    return (jnp.sum(jnp.where(mask, cols["x"], 0), axis=1) + 1000 * keys,
            gwids)


_FN = JaxWindowFunction(_udf, fields=("x",),
                        result_fields={"r": np.int64, "g": np.int64})


def _executor(kind, **where):
    i32 = {"value": np.int32}
    if kind == "regular":
        return make_executor("regular", ("value",), (("sum", "value"),), i32,
                             **where)
    if kind == "minmax":
        return make_executor("regular", ("value",),
                             (("min", "value"), ("max", "value")), i32,
                             **where)
    if kind == "multi":
        return make_executor(
            "multi", ("a", "b"), (("sum", "a"), ("max", "b"), ("min", "a")),
            {"a": np.int32, "b": np.int32}, **where)
    return make_executor("multi", ("x",), (), {"x": np.int32}, jax_fn=_FN,
                         **where)


def _launch(ex, kind, step, rows=R, keys=K):
    """Launch number `step`: `rows` new rows a key appended behind the
    earlier ones, and windows over what the ring then holds."""
    rng = np.random.default_rng(step)
    blk = rng.integers(-100, 100, (keys, rows)).astype(np.int8)
    offs = np.full(keys, step * R, dtype=np.int64)
    live = (step + 1) * R
    if kind == "regular":
        rcount = np.asarray([2, 1, 0, 2, 1])[:keys]
        wrows = np.repeat(np.arange(keys), rcount)
        widx = np.concatenate([np.arange(c) for c in rcount])
        ex.launch_regular(step, blk, offs, rcount, np.arange(keys) % 2,
                          np.full(keys, live - 2), 1, wrows, widx)
        return
    wrows = np.asarray([0, 1, 2, 3, 4, 0, 2])
    wstarts = np.asarray([0, 1, 0, 2, 1, 2, 1])
    wlens = np.asarray([live, live - 1, 2, 1, live - 1, 1, 0])
    if kind == "minmax":
        ex.launch(step, blk, offs, wrows, wstarts, wlens)
    elif kind == "multi":
        ex.launch(step, {"a": blk, "b": (blk.astype(np.int16) * 3)}, offs,
                  wrows, wstarts, wlens)
    else:
        ex.launch(step, {"x": blk}, offs, wrows, wstarts, wlens,
                  wkeys=wrows + 7, wgwids=np.arange(len(wrows)) * 11)


def _drive(kind, **where):
    """Three launches; ([(meta, results)], [ring rows in dense-key order])."""
    ex = _executor(kind, **where)
    ex.reset(K, CAP)
    got = []
    for step in range(3):
        _launch(ex, kind, step)
        got.extend(ex.drain())
    prow = ex.place.phys_rows(np.arange(K), ex.KP)
    rings = [np.asarray(r)[prow] for r in ex._rings_tuple()]
    return ex, got, rings


def _flat(res):
    return [np.asarray(a) for a in (res if isinstance(res, tuple) else (res,))]


# ------------------------------------------------- (a) one device == a mesh

@pytest.mark.parametrize("n", [1, 2, 4, 8])
@pytest.mark.parametrize("kind", KINDS)
def test_a_mesh_answers_as_one_device_does(kind, n):
    one, want, want_rings = _drive(kind)
    on, got, got_rings = _drive(kind, mesh=make_mesh(n_kf=n))
    assert one.mesh is None and on.mesh.shape["kf"] == n
    assert on.KP == n * 8 and type(on) is type(one)
    assert [m for m, _r in got] == [m for m, _r in want] == [0, 1, 2]
    for (_m, a), (_m2, b) in zip(got, want):
        assert all(np.array_equal(x, y) and x.dtype == y.dtype
                   for x, y in zip(_flat(a), _flat(b)))
    assert len(got_rings) == len(want_rings)
    for g, w in zip(got_rings, want_rings):
        assert np.array_equal(g, w[:K]) and g[:, :9].any()
    for ring in on._rings_tuple():
        assert len(ring.sharding.device_set) == n
        assert tuple(ring.sharding.spec) == ("kf", None)


# ------------------------------------- (b) keys, names and ladder siblings

def _fixed_sequence(**where):
    """The launches PR 44's tree was read with: {step key: name} of
    `_STEP_CACHE`, then of the bound function's own cache."""
    resident._STEP_CACHE.clear()
    resident._PREWARMED.clear()
    blk = np.arange(15, dtype=np.int8).reshape(5, 3)
    ex = _executor("regular", **where)
    ex.reset(5, 64)
    ex.launch_regular("m", blk, _z(5), np.full(5, 2), _z(5), np.full(5, 2),
                      1, np.repeat(np.arange(5), 2), np.tile(np.arange(2), 5))
    ex.drain()
    ex = make_executor("regular", ("value",), (("max", "value"),),
                       {"value": np.int32}, **where)
    ex.reset(5, 64)
    ex.launch("m", blk, _z(5), np.arange(5), _z(5), np.full(5, 3))
    ex.drain()
    ex = make_executor("multi", ("a", "b"), (("sum", "a"), ("max", "b")),
                       {"a": np.int32, "b": np.int32}, **where)
    ex.reset(5, 64)
    ex.launch("m", {"a": blk, "b": blk.astype(np.int16)}, _z(5),
              np.arange(5), _z(5), np.full(5, 3))
    ex.drain()

    def mine(keys, gwids, cols, mask):
        return _udf(keys, gwids, cols, mask)[0]

    ex = make_executor(
        "multi", ("x",), (), {"x": np.int32},
        jax_fn=JaxWindowFunction(mine, fields=("x",),
                                 result_fields={"r": np.int64}), **where)
    ex.reset(5, 64)
    ex.launch("m", {"x": blk}, _z(5), np.arange(5), _z(5), np.full(5, 3),
              wkeys=np.arange(5), wgwids=np.arange(5) * 10)
    ex.drain()
    names = lambda cache: {k: fn.__name__ for k, fn in cache.items()}
    return names(resident._STEP_CACHE), names(resident._FN_STEP_CACHE[mine])


@pytest.fixture
def step_cache():
    """The process-wide caches as the test found them, afterwards."""
    saved, warm = dict(resident._STEP_CACHE), set(resident._PREWARMED)
    yield
    resident._STEP_CACHE.clear()
    resident._STEP_CACHE.update(saved)
    resident._PREWARMED.clear()
    resident._PREWARMED.update(warm)


def _reg(place, KP, **kw):
    return StepKey("regular", place, "sum", 64, 8, 8, KP, I8, I32,
                   slide=1)._replace(**kw)


def _irr(place, KP, **kw):
    return StepKey("append_eval", place, ("max",), 64, 8, 8, KP, I8, I32,
                   8)._replace(**kw)


def test_one_device_keys_and_names_are_the_parents(step_cache):
    # the parent's ('reg', 'sum', 64, 8, 8, 8, '|i1', '<i4', 1),
    # (('max',), 64, 8, 8, 8, '|i1', '<i4', 8) and (('a', 'b'), (('sum',
    # 'a'), ('max', 'b')), None, 64, 8, 8, 8, ('|i1', '<i2'), ('<i4',
    # '<i4'), 8); the function's (('x',), (), ('x',), 64, 8, 8, 8, ...)
    built, bound = _fixed_sequence()
    assert built == {
        _reg(_ANY_DEVICE, 8): "wf_step_regular",
        _irr(_ANY_DEVICE, 8): "wf_step_append_eval",
        StepKey("multi", _ANY_DEVICE, (("sum", "a"), ("max", "b")), 64, 8, 8,
                8, (I8, I16), (I32, I32), 8, ("a", "b")): "wf_step_multi"}
    assert bound == {
        StepKey("multi", _ANY_DEVICE, (), 64, 8, 8, 8, (I8,), (I32,), 8,
                ("x",), ("x",)): "wf_step_multi"}
    assert all(isinstance(hash(k), int) and "StepKey(" in repr(k)
               for k in built)


def test_mesh_keys_and_names_are_the_parents(step_cache):
    # the parent's ('mesh-reg', 'sum', 64, 8, 16, 8, ..., mesh, 'kf'),
    # ('mesh', ('max',), 64, 8, 8, 16, ...), ('mesh-multi', ..., 64, 8, 8,
    # 16, ...) and, the function's, ('mesh-multi', ..., 64, 8, 4, 16, ...):
    # two shards of eight rows, a function's windows bucketed from 1
    mesh = make_mesh(n_kf=2)
    on = resident._OnMesh(mesh, "kf")
    built, bound = _fixed_sequence(mesh=mesh)
    assert built == {
        _reg(on, 16): "wf_step_regular_mesh",
        _irr(on, 16): "wf_step_append_eval_mesh",
        StepKey("multi", on, (("sum", "a"), ("max", "b")), 64, 8, 8, 16,
                (I8, I16), (I32, I32), 8, ("a", "b")): "wf_step_multi_mesh"}
    assert bound == {
        StepKey("multi", on, (), 64, 8, 4, 16, (I8,), (I32,), 8, ("x",),
                ("x",)): "wf_step_multi_mesh"}


def test_argext_key_and_name_are_the_parents(step_cache):
    # the parent's ('argext', ('v',), (('argmax', 'v'),), 64, 8, 8, 1,
    # ('|i1',), ('<i4',), 0, 64): one ring row, the block capped by the ring
    resident._STEP_CACHE.clear()
    ex = make_executor("argext", ("v",), (("argmax", "v"),),
                       {"v": np.int32})
    ex.reset(1, 64)
    ex.launch("m", {"v": np.arange(5, dtype=np.int8).reshape(1, 5)}, _z(1),
              _z(1), _z(1), np.asarray([5]))
    (_m, (ext, first, n)), = ex.drain()
    assert (int(ext[0]), int(first[0]), int(n[0])) == (4, 4, 1)
    assert {k: fn.__name__ for k, fn in resident._STEP_CACHE.items()} == {
        StepKey("argext", _ANY_DEVICE, (("argmax", "v"),), 64, 8, 8, 1,
                (I8,), (I32,), 0, ("v",), eb=64): "wf_step_argext"}


def test_argext_refuses_a_mesh():
    with pytest.raises(ValueError, match="no mesh"):
        make_executor("argext", ("v",), (("argmax", "v"),), {"v": np.int32},
                      mesh=make_mesh(n_kf=2))


def test_a_mesh_without_the_key_group_axis_is_refused():
    from jax.sharding import Mesh
    with pytest.raises(ValueError, match="no axis 'kf'"):
        make_executor("regular", ("value",), (("sum", "value"),),
                      {"value": np.int32},
                      mesh=Mesh(np.asarray(jax.devices()[:2]), ("x",)))


#: (Rb, Bb) of the siblings the parent's ladder adds to a natural step of
#: (8, 8) under a ring of 64 columns: the lower triangle, of one device's
#: explicit descriptors the diagonal
_TRIANGLE = {(16, 8), (16, 16), (32, 8), (32, 16), (32, 32), (64, 8),
             (64, 16), (64, 32), (64, 64)}
_DIAGONAL = {(16, 16), (32, 32), (64, 64)}


@pytest.mark.parametrize("kind,on_mesh,shapes", [
    ("regular", False, _TRIANGLE), ("minmax", False, _DIAGONAL),
    ("regular", True, _TRIANGLE), ("minmax", True, _TRIANGLE)])
def test_the_ladder_adds_the_parents_siblings(step_cache, kind, on_mesh,
                                              shapes):
    mesh = make_mesh(n_kf=2) if on_mesh else None
    resident._STEP_CACHE.clear()
    resident._PREWARMED.clear()
    ex = _executor(kind, **({"mesh": mesh} if on_mesh else {}))
    ex.reset(K, CAP)
    _launch(ex, kind, 0)
    ex.drain()
    (natural,) = resident._STEP_CACHE
    name = resident._STEP_CACHE[natural].__name__
    assert (natural.Rb, natural.Bb, natural.cap) == (8, 8, 64)
    assert resident.prewarm_regular_ladder(
        devices=jax.devices()[:2]) == len(shapes)
    added = set(resident._STEP_CACHE) - {natural}
    assert added == {natural._replace(Rb=rb, Bb=bb) for rb, bb in shapes}
    assert added == resident._PREWARMED
    assert {fn.__name__ for fn in resident._STEP_CACHE.values()} == {name}
    # a sibling seeds no ladder of its own, and a second call has nothing
    # left to do
    assert resident.prewarm_regular_ladder() == 0
    # ... and a sibling is the step a launch of its shape finds
    if (16, 8) in shapes:
        _launch(ex, kind, 1, rows=9)
        ex.drain()
        assert set(resident._STEP_CACHE) == added | {natural}


def test_the_ladder_leaves_the_ring_a_field_families_alone(step_cache):
    resident._STEP_CACHE.clear()
    resident._PREWARMED.clear()
    _drive("multi")
    assert resident._STEP_CACHE
    assert resident.prewarm_regular_ladder() == 0


def test_the_benchmarks_recorder_still_builds_its_step():
    """benchmarks/tests/record_wf_trace.py passes the positional key."""
    fn = resident._make_regular_step(
        ("reg", "sum", 64, 8, 8, 8, "<i2", "<i4", 64))
    assert fn.__name__ == "wf_step_regular"


# --------------------------- (c) what a launch costs on one device: a read

@pytest.mark.parametrize("kind", KINDS + ("argext",))
def test_a_one_device_launch_puts_one_tuple_once(kind, monkeypatch):
    if kind == "argext":
        ex = make_executor("argext", ("v",), (("argmax", "v"),),
                           {"v": np.int32})
        ex.reset(1, CAP)
        launch = lambda step: ex.launch(
            step, {"v": np.arange(8, dtype=np.int8).reshape(1, 8)},
            np.asarray([step * 8]), _z(1), _z(1), np.asarray([8]))
    else:
        ex = _executor(kind)
        ex.reset(K, CAP)
        launch = lambda step: _launch(ex, kind, step)
    launch(0)                       # allocates the ring(s)
    ex.drain()
    puts = []
    real = jax.device_put

    def spy(x, *a, **kw):
        puts.append(x)
        return real(x, *a, **kw)

    def never(*_a, **_kw):
        raise AssertionError("a one-device launch asked the mesh placement")

    monkeypatch.setattr(jax, "device_put", spy)
    for name in ("put", "batch", "phys_rows", "compile", "win_shape"):
        monkeypatch.setattr(resident._OnMesh, name, never)
    launch(1)
    monkeypatch.undo()
    (_m, _res), = ex.drain()
    (args,) = puts
    assert isinstance(args, tuple)
    leaves = jax.tree.leaves(args)
    rects = [a for a in leaves if a.ndim == 2]
    assert all(a.shape == (ex.KP, 8) for a in rects)
    assert len(rects) == len(ex._rings_tuple())
    # per-row vectors and flat window descriptors: no (S, Bs) rectangle
    assert all(a.ndim == 1 and len(a) in (ex.KP, 8)
               for a in leaves if a.ndim != 2)


@pytest.mark.parametrize("kind", ["regular", "minmax"])
def test_a_full_rectangle_is_put_as_it_comes(kind, monkeypatch):
    """The `blk.shape == (KP, Rb)` fast path: no padded copy."""
    ex = _executor(kind)
    ex.reset(8, CAP)
    _launch(ex, kind, 0, rows=8, keys=5)
    ex.drain()
    puts = []
    real = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda x, *a, **kw: puts.append(x) or real(x, *a,
                                                                    **kw))
    monkeypatch.setattr(resident, "_pad2", None)       # a call would raise
    blk = np.ones((8, 8), dtype=np.int8)
    if kind == "regular":
        ex.launch_regular(1, blk, _z(8), _z(8), _z(8), _z(8), 1, _z(0), _z(0))
    else:
        ex.launch(1, blk, _z(8), _z(0), _z(0), _z(0))
    monkeypatch.undo()
    ex.drain()
    assert puts[0][0] is blk


# ------------------------------------------- (d) snapshot and restore

@pytest.mark.parametrize("on_mesh", [False, True])
@pytest.mark.parametrize("kind", ["minmax", "multi"])
def test_ring_snapshot_round_trips(kind, on_mesh):
    where = {"mesh": make_mesh(n_kf=4)} if on_mesh else {}
    ex = _executor(kind, **where)
    ex.reset(K, CAP)
    _launch(ex, kind, 0)
    _launch(ex, kind, 1)
    ex.drain()
    snap = ex.ring_snapshot().resolve()           # as a checkpoint holds it
    _launch(ex, kind, 2)
    (_m, want), = ex.drain()
    after = [np.asarray(r) for r in ex._rings_tuple()]

    fresh = _executor(kind, **where)
    fresh.ring_restore(snap)
    assert (fresh.KP, fresh.cap) == (ex.KP, ex.cap)
    for ring in fresh._rings_tuple():
        assert ring.sharding.device_set == \
            ex._rings_tuple()[0].sharding.device_set
    _launch(fresh, kind, 2)
    (_m, got), = fresh.drain()
    assert all(np.array_equal(a, b) for a, b in zip(_flat(got), _flat(want)))
    assert all(np.array_equal(np.asarray(r), a)
               for r, a in zip(fresh._rings_tuple(), after))
    fresh.invalidate()
    assert fresh._rings_tuple() is None and fresh.KP == 0
