"""AOT-compile every device step family for a TPU v5e, on the CPU.

``jax.experimental.topologies`` describes a v5e:2x2 host to the installed
libtpu without a chip, so XLA:TPU runs its whole pipeline here: a step the
compiler refuses fails this suite instead of waiting for a chip run.  Shapes
are the main-path buckets (bench.py / apps/pipe.py: CB 256/64, 64 keys,
flush_rows 2^19) and the ones chip_smoke.py drives.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from windflow_tpu.ops import resident
from windflow_tpu.ops.device import DeviceWindowExecutor, builtin_batch_fn

KP, CAP, RB, C, SLIDE = 64, 16384, 8192, 128, 64
I8, I32, F32 = np.dtype(np.int8).str, np.dtype(np.int32).str, \
    np.dtype(np.float32).str
ONE = resident._ANY_DEVICE


@pytest.fixture(scope="module")
def v5e():
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


def _one(devices):
    sh = SingleDeviceSharding(devices[0])
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)


def _mesh(devices):
    mesh = Mesh(np.array(devices).reshape(4, 1, 1), ("kf", "wf", "sp"))

    def S(shape, dt, *spec):
        return jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, P(*spec)))
    return mesh, S


@pytest.mark.parametrize("cap", [CAP, 2 * CAP])
def test_regular_step_compiles(v5e, cap):
    S = _one(v5e)
    fn = resident._make_regular_step(resident.StepKey(
        "regular", ONE, "sum", cap, RB, C, KP, I8, I32, slide=SLIDE))
    k = S((KP,), jnp.int32)
    fn.lower(S((KP, cap), jnp.int32), S((KP, RB), jnp.int8), k, k, k,
             k).compile()


@pytest.mark.parametrize("ops,pad", [(("sum",), 0), (("max",), 256),
                                     (("sum", "max"), 256),
                                     (("min",), 4096)])
def test_irregular_step_compiles(v5e, ops, pad):
    S = _one(v5e)
    B = 8192
    fn = resident._make_step(resident.StepKey(
        "append_eval", ONE, ops, CAP, RB, B, KP, I8, I32, pad))
    b = S((B,), jnp.int32)
    fn.lower(S((KP, CAP), jnp.int32), S((KP, RB), jnp.int8),
             S((KP,), jnp.int32), b, b, b).compile()


def test_multi_step_compiles(v5e):
    S = _one(v5e)
    B = 8192
    key = resident.StepKey(
        "multi", ONE, (("sum", "a"), ("max", "b")), CAP, RB, B, KP, (I8, I8),
        (I32, I32), 256, fields=("a", "b"))
    fn = resident._make_multi_step(key, None)
    ring, blk = S((KP, CAP), jnp.int32), S((KP, RB), jnp.int8)
    b = S((B,), jnp.int32)
    fn.lower((ring, ring), (blk, blk), S((KP,), jnp.int32), b, b, b, b,
             b).compile()


@pytest.mark.parametrize("stage", ["map", "reduce"])
def test_argext_step_compiles_and_fits(v5e, stage):
    """The arg-extremum family at NEXMark Q7's widths: a MAP worker's one
    ring row of 2^26 cells appended 2^20 rows at a time, and the REDUCE
    worker's three small rings.  The ring is donated: the step may hold one
    more ring for a compaction, never a copy per launch."""
    S = _one(v5e)
    if stage == "map":
        fields, stats, cap, rb = ("price",), (("argmax", "price"),), \
            1 << 26, 1 << 20
    else:
        fields = ("price", "count", "lastUpdate")
        stats = (("argmax", "price"), ("sum", "count"),
                 ("max", "lastUpdate"))
        cap, rb = 1 << 22, 8
    n = len(fields)
    key = resident.StepKey(
        "argext", ONE, stats, cap, rb, 8, 1, (I32,) * n, (I32,) * n,
        fields=fields, eb=min(resident.ARGEXT_BLOCK, cap))
    fn = resident._make_argext_step(key)
    k, b = S((1,), jnp.int32), S((8,), jnp.int32)
    compiled = fn.lower((S((1, cap), jnp.int32),) * n,
                        (S((1, rb), jnp.int32),) * n, k, k, b, b,
                        b).compile()
    mem = compiled.memory_analysis()
    ring_bytes = n * cap * 4
    assert mem.alias_size_in_bytes >= ring_bytes        # appended in place
    assert mem.temp_size_in_bytes <= ring_bytes + (64 << 20)


def test_mesh_regular_step_compiles(v5e):
    mesh, S = _mesh(v5e)
    fn = resident._make_regular_step(resident.StepKey(
        "regular", resident._OnMesh(mesh, "kf"), "sum", CAP, RB, C, KP, I8,
        I32, slide=SLIDE))
    assert fn.__name__ == "wf_step_regular_mesh"
    k = S((KP,), jnp.int32, "kf")
    fn.lower(S((KP, CAP), jnp.int32, "kf", None),
             S((KP, RB), jnp.int8, "kf", None), k, k, k, k).compile()


def test_mesh_irregular_step_compiles(v5e):
    mesh, S = _mesh(v5e)
    Bs = 2048
    fn = resident._make_step(resident.StepKey(
        "append_eval", resident._OnMesh(mesh, "kf"), ("max",), CAP, RB, Bs,
        KP, I8, I32, 256))
    assert fn.__name__ == "wf_step_append_eval_mesh"
    d = S((4, Bs), jnp.int32, "kf", None)
    fn.lower(S((KP, CAP), jnp.int32, "kf", None),
             S((KP, RB), jnp.int8, "kf", None), S((KP,), jnp.int32, "kf"),
             d, d, d).compile()


def test_restaging_gather_compiles(v5e):
    """The segment-restaging executor's gather + reduce (ops/device.py) at
    B 32768, pad 256, N 2^20."""
    S = _one(v5e)
    B, pad, N = 32768, 256, 1 << 20
    ex = DeviceWindowExecutor(builtin_batch_fn("mean"))
    b = S((B,), jnp.int32)
    ex._compiled(B, pad, N).lower({"value": S((N,), jnp.int32)}, b, b, b,
                                  b).compile()


def _app_skyline():
    from windflow_tpu.apps.spatial import device_skyline
    return device_skyline()


def _cell_skyline():
    """The window function of the benchmark's ``spatial_wf`` configuration
    (benchmarks/configs/spatial_wf.py)."""
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from configs import spatial_wf
    return spatial_wf.window_function()


def _cell_pane_skyline():
    """The pane function of the benchmark's ``spatial_pf`` configuration
    (benchmarks/configs/spatial_pf.py): the all-pairs test, then the
    frontier compacted into 64 slots -- rank-2 outputs."""
    _cell_skyline()          # (puts benchmarks/ on the path)
    from configs import spatial_pf
    return spatial_pf.pane_function(64)


@pytest.mark.parametrize("make,kp,cap,rb,B,pad", [
    (_app_skyline, 8, 8192, 2048, 256, 512),
    # what the cell spatial_wf.paced meets: a pipeline's first launch, the
    # steady launch of one window, and launches of two and four
    (_cell_skyline, 8, 262144, 131072, 1, 131072),
    (_cell_skyline, 8, 262144, 16384, 1, 131072),
    (_cell_skyline, 8, 262144, 16384, 2, 131072),
    (_cell_skyline, 8, 262144, 16384, 4, 131072),
    # ... and since a function-bound launch pads its windows to the ladder
    # of ops/device._bucket_fine: 102,400 points run as 106,496
    (_cell_skyline, 8, 262144, 131072, 1, 106496),
    (_cell_skyline, 8, 262144, 16384, 1, 106496),
    (_cell_skyline, 8, 262144, 16384, 2, 106496),
    # what the cell spatial_pf.paced meets: a whole pane in one launch, and
    # two panes in one where a worker fell behind
    (_cell_pane_skyline, 8, 524288, 131072, 1, 106496),
    (_cell_pane_skyline, 8, 524288, 131072, 2, 106496)])
def test_skyline_step_compiles(v5e, make, kp, cap, rb, B, pad):
    """A device skyline, the (B, pad, pad) dominance test, on the multi-field
    resident step it runs on (use_resident=True): XLA has to fuse compare
    and reduce, so no buffer of a pair matrix's size exists, and the user's
    function stands under its own name in the compiled step."""
    S = _one(v5e)
    key = resident.StepKey(
        "multi", ONE, (), cap, rb, B, kp, (F32, F32), (F32, F32), pad,
        fields=("x", "y"), fn_slot=("x", "y"))
    udf = make()             # held here: a step holds its function weakly
    fn = resident._make_multi_step(key, udf)
    ring, blk = S((kp, cap), jnp.float32), S((kp, rb), jnp.float32)
    b = S((B,), jnp.int32)
    compiled = fn.lower((ring, ring), (blk, blk), S((kp,), jnp.int32), b, b,
                        b, b, b).compile()
    # ... not even at one bit a pair
    assert compiled.memory_analysis().temp_size_in_bytes < B * pad * pad // 8
    assert "wf_udf" in compiled.as_text()


def _join_core(window_rows, max_results, flush_rows):
    """The join worker of NEXMark Q8's shape (a key, a side, a time and two
    carried fields: five int32 rings of one row)."""
    from windflow_tpu.patterns.win_join_tpu import WinJoinTPU
    return WinJoinTPU(
        10_000_000, side_field="event_type", left=(0, "person"),
        right=(1, "seller"), key_range=(0, 2_000_000_000),
        right_fields=("auction", "reserve"),
        field_ranges={"auction": (0, 2_000_000_000),
                      "reserve": (0, 200_000_001)},
        window_rows=window_rows, max_results=max_results,
        flush_rows=flush_rows).make_core()


def test_join_step_compiles(v5e):
    """The step that closes a window of the join (ops/join.py on the
    multi-field resident step): two sorts that carry the rows' fields and two
    running maxima, at a sixteenth of the cell's window;
    its temporaries stay a small multiple of the window's columns, and the
    function stands under its own name inside the user-function scope."""
    S = _one(v5e)
    core = _join_core(1_900_000, 1_500_000, 1 << 16)
    ex, n = core.executor, len(core.fields)
    pad = ex._pad_for(np.array([1_000_000]))
    assert pad == 1_966_080 and core.cap == 1_572_864 and ex.KP == 1
    wires = tuple(core._wire[f].str for f in core.fields)
    key = resident.StepKey(
        "multi", ONE, (), ex.cap, 1 << 16, 1, 1, wires, (I32,) * n, pad,
        fields=core.fields, fn_slot=core.fields)
    fn = resident._make_multi_step(key, core.fn)
    rings = tuple(S((1, ex.cap), jnp.int32) for _ in core.fields)
    blks = tuple(S((1, 1 << 16), core._wire[f]) for f in core.fields)
    b = S((1,), jnp.int32)
    compiled = fn.lower(rings, blks, S((1,), jnp.int32), b, b, b, b,
                        b).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * pad * 4
    text = compiled.as_text()
    assert "wf_udf" in text and "wf_join" in text


def test_join_append_step_compiles_in_place(v5e):
    """The append-only step at the cell's own widths: five rings of 2^25
    cells, rectangles of 2^20 rows, the rings donated, so a launch that
    ships rows copies no ring."""
    S = _one(v5e)
    core = _join_core(30_000_000, 24_000_000, 1 << 20)
    ex, n = core.executor, len(core.fields)
    assert ex.cap == 1 << 25 and core.cap == 25_165_824
    key = resident.StepKey(
        "append", ONE, (), ex.cap, 1 << 20, 0, 1,
        tuple(core._wire[f].str for f in core.fields), (I32,) * n,
        fields=core.fields)
    fn = resident._make_append_step(key)
    rings = tuple(S((1, ex.cap), jnp.int32) for _ in core.fields)
    blks = tuple(S((1, 1 << 20), core._wire[f]) for f in core.fields)
    compiled = fn.lower(rings, blks, S((1,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= n * ex.cap * 4
    assert mem.temp_size_in_bytes <= 64 << 20
