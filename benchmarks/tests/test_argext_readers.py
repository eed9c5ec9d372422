"""The two readers of the arg-extremum family on a hand-made observation:
the family's own executables only, whatever their shape; nothing to read
where the program lacks the family or its counters (the parent's case)."""

import pytest

from harness import bytes_model_argext
from layer_metrics.readers import argext_roofline, family_device

PARAMS = {"family": "argext"}


def _obs(executables, counters):
    return {"trace": {"executables": executables},
            "slice_counters": counters,
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_family_time_takes_every_shape_of_the_family_and_nothing_else():
    execs = {"jit_wf_step_argext(93)": (0.009, 44),
             "jit_wf_step_argext(15)": (0.001, 1),
             "jit_wf_step_append_eval(7)": (5.0, 9),
             "jit_wf_step_argextra(8)": (7.0, 3),
             "jit_broadcast_in_dim(2)": (0.1, 3)}
    got = family_device.read(_obs(execs, {}), PARAMS)
    assert got["value"] == pytest.approx(1e3 * 0.010 / 45)
    counters = {"bytes_shipped": 184e6, "eval_rows": 1.4e8,
                "eval_windows": 5.0}
    share = argext_roofline.read(_obs(execs, counters), PARAMS)
    n_bytes = 2 * 184e6 + 4 * 1.4e8 + 5 * 3 * 4
    assert bytes_model_argext.argext_bytes(184e6, 1.4e8, 5) == n_bytes
    assert share["value"] == pytest.approx(100 * n_bytes / 0.010 / 819e9)
    assert 0 < share["value"] < 100


def test_nothing_to_read_without_the_family_or_its_counters():
    old = {"jit_wf_step_append_eval(7)": (5.0, 9)}
    assert family_device.read(_obs(old, {}), PARAMS) is None
    assert family_device.read({"trace": None}, PARAMS) is None
    # a program without the counter (the parent), with or without a trace
    assert argext_roofline.read(
        _obs(old, {"bytes_shipped": 1.0, "windows": 2.0}), PARAMS) is None
    assert argext_roofline.read(
        {"trace": None, "slice_counters": {}, "peaks": None}, PARAMS) is None
    mine = {"jit_wf_step_argext(1)": (0.0, 0)}
    assert argext_roofline.read(
        _obs(mine, {"eval_rows": 1.0, "bytes_shipped": 1.0}), PARAMS) is None
