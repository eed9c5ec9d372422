"""chip_smoke.py's legs at a tiny size on the CPU backend, and the contract of
the script around them: it refuses to run without a TPU, and the compile
cache it turns on can be placed from outside."""

import os
import subprocess
import sys

import pytest

import chip_smoke as cs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(platform="cpu", chunk=1 << 12)
N = 64 * 300          # 300 rows per key: 5 windows each, 320 in all


def _check(facts, core):
    assert facts["core"] == core
    assert facts["cold_wall_s"] > 0 and facts["warm_wall_s"] > 0
    return facts


def test_leg_pipe_tiny():
    f = _check(cs.leg_pipe(n_tuples=N, flush_rows=1 << 12, **TINY),
               "NativeResidentCore")
    assert f["leg"] == "A pipe" and all(d > 0 for d in
                                        f["dispatches_per_ring"])


def test_leg_sum_tiny():
    f = _check(cs.leg_sum(n_tuples=N, batch_len=128, flush_rows=1 << 12,
                          **TINY), "NativeResidentCore")
    assert f["windows"] == 320 and f["dispatches"] > 0


def test_leg_sum_on_a_mesh_of_one_tiny():
    """Leg F as a one-chip host runs it: the mesh placement over one
    device."""
    from windflow_tpu.parallel.mesh import make_mesh
    f = _check(cs.leg_sum(n_tuples=N, batch_len=128, flush_rows=1 << 12,
                          mesh=make_mesh(1, 1), **TINY), "NativeResidentCore")
    assert f["leg"] == "F mesh ring x1" and f["dispatches"] > 0
    assert (f["ring_devices"], f["windows"]) == (1, 320)
    assert f["ring_spec"] == "PartitionSpec('kf', None)"


def test_legs_families_tiny():
    legs = [leg() for leg in cs.legs_families(
        n_tuples=N, batch_len=128, flush_rows=1 << 12, **TINY)]
    assert [f["leg"][:2] for f in legs] == ["C1", "C2", "C3", "C4"]
    for f in legs[:3]:
        _check(f, "NativeResidentCore")
    assert legs[2]["rings"] == 2
    assert _check(legs[3], "DeviceWinSeqCore")["launches"] > 0


def test_legs_multichip_tiny():
    """conftest's 8 virtual devices stand in for the four chips."""
    e, f, g = [leg() for leg in cs.legs_multichip(
        4, pipe_tuples=N, sum_tuples=N, flush_rows=1 << 12, **TINY)]
    assert len(e["devices"]) == 4 and len(e["dispatches_per_ring"]) == 4
    assert f["ring_devices"] == 4 and "kf" in f["ring_spec"]
    assert g["devices"] == 4


def test_assert_device_work_refuses_a_host_core():
    """The per-leg assertion is what keeps make_core_for's host routes from
    passing for device work."""
    from windflow_tpu.core.windows import WinType
    from windflow_tpu.ops.functions import Reducer
    from windflow_tpu.patterns.native_core import NativeResidentCore
    from windflow_tpu.patterns.win_seq import WinSeq
    from windflow_tpu.patterns.win_seq_tpu import WinSeqTPU
    host = WinSeq(Reducer("sum"), 8, 4, WinType.CB).make_core()
    with pytest.raises(AssertionError, match="window core is"):
        cs.assert_device_work([host], NativeResidentCore, "cpu")
    idle = WinSeqTPU(Reducer("sum", value_range=(0, 9)), 8, 4,
                     WinType.CB).make_core()
    with pytest.raises(AssertionError, match="expected tpu"):
        cs.assert_device_work([idle], NativeResidentCore, "tpu")
    with pytest.raises(AssertionError, match="never dispatched"):
        cs.assert_device_work([idle], NativeResidentCore, "cpu")


def _python(args, env_extra):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    return subprocess.run([sys.executable, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)


def test_smoke_refuses_to_run_without_a_tpu():
    p = _python([os.path.join(REPO, "chip_smoke.py")],
                {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""        # no result line of any kind


_CACHE_PROBE = (
    "import jax\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "from windflow_tpu.ops.backend import enable_compile_cache\n"
    "print(before); print(enable_compile_cache())\n"
    "print(jax.config.jax_persistent_cache_min_compile_time_secs,"
    " jax.config.jax_persistent_cache_min_entry_size_bytes)\n")


def test_compile_cache_is_placed_from_outside(tmp_path):
    outside = str(tmp_path / "cache")
    p = _python(["-c", _CACHE_PROBE],
                {"JAX_PLATFORMS": "cpu",
                 "JAX_COMPILATION_CACHE_DIR": outside})
    assert p.returncode == 0, p.stderr
    before, after, thresholds = p.stdout.split("\n")[:3]
    assert before == after == outside    # JAX read the variable; untouched
    assert thresholds == "0.0 -1" or thresholds == "0 -1"


def test_compile_cache_default_is_a_fixed_checkout_path():
    outs = [_python(["-c", _CACHE_PROBE], {"JAX_PLATFORMS": "cpu"})
            for _ in range(2)]
    for p in outs:
        assert p.returncode == 0, p.stderr
    dirs = {p.stdout.split("\n")[1] for p in outs}
    assert dirs == {os.path.join(REPO, ".jax_cache")}
