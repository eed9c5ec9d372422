"""The assertion that keeps a fallback from hiding (copied in idea from
``chip_smoke.py:assert_device_work``): a run whose window workers are not all
on the core class its configuration names, whose executors' devices are not
of the platform the run reports, or whose executors never dispatched, is not
a slower result -- it did not measure the device path, and the run fails.

What a configuration names (``expected_core`` in its file) is either one
class name, held by every window core of the graph, all of them on the
device; or a list with one entry per window stage, in the order the program
walks its graph::

    [{"stage": "count per key", "core": "<class>", "workers": 1, "device": false},
     {"stage": "sum of counts", "core": "<class>", "workers": 2, "device": true}]

A stage with ``"device": false`` is one the program rightly keeps on the
host (every statistic of it is free from host bookkeeping): its cores must be
the host class it names, and are excused from the executor, platform and
dispatch checks.  At least one stage is on the device.
"""

from __future__ import annotations


class DevicePathError(RuntimeError):
    pass


def stages_of(expected, n_workers):
    """``expected_core`` as a list of stages; ``n_workers`` is the count of
    device window workers the configuration's builder states."""
    if isinstance(expected, str):
        return [{"stage": "window", "core": expected,
                 "workers": int(n_workers), "device": True}]
    stages = [dict(s, workers=int(s["workers"])) for s in expected]
    on_device = sum(s["workers"] for s in stages if s["device"])
    if not on_device:
        raise DevicePathError(
            "the configuration names no window stage on the device")
    if on_device != int(n_workers):
        raise DevicePathError(
            f"the configuration's stages hold {on_device} device window "
            f"workers, its builder states {n_workers}")
    return stages


def describe(expected, n_workers):
    """One line for the run's output: ``2 x <core>`` per stage."""
    return " > ".join(
        f"{s['workers']} x {s['core']}" + ("" if s["device"] else " (host)")
        for s in stages_of(expected, n_workers))


def _assert_on_device(core, platform):
    """``(devices, dispatches)`` of one device core's executors."""
    name = type(core).__name__
    delegate = getattr(core, "_delegate", None)
    if delegate is not None:
        raise DevicePathError(
            f"{name} handed the stream to {type(delegate).__name__}")
    executors = getattr(core, "executors", None) \
        or [getattr(core, "executor", None)]
    if executors[0] is None:
        raise DevicePathError(f"{name} has no executor: it is a host core")
    devices, dispatches = set(), 0
    for ex in executors:
        mesh = getattr(ex, "mesh", None)
        owned = list(mesh.devices.flat) if mesh is not None else [ex.device]
        for dev in owned:
            if dev.platform != platform:
                raise DevicePathError(
                    f"executor on {dev} ({dev.platform}), the run is on "
                    f"{platform}")
        sent = getattr(ex, "dispatches", None)
        if sent is None:
            sent = ex.launches
        if sent <= 0:
            raise DevicePathError(
                f"{type(ex).__name__} on {owned[0]} never dispatched")
        dispatches += sent
        devices.update(owned)
    return devices, dispatches


def assert_device_path(cores, expected, n_workers, platform):
    """Returns ``(devices, dispatches)`` of the device stages' executors.
    ``cores`` are the graph's window cores in the order the program walks
    it; ``expected`` is the configuration's ``expected_core``."""
    stages = stages_of(expected, n_workers)
    total = sum(s["workers"] for s in stages)
    if len(cores) != total:
        raise DevicePathError(
            f"{len(cores)} window cores for {total} window workers")
    devices, dispatches, at = set(), 0, 0
    for stage in stages:
        for core in cores[at:at + stage["workers"]]:
            if type(core).__name__ != stage["core"]:
                raise DevicePathError(
                    f"window core of stage {stage['stage']!r} is "
                    f"{type(core).__name__}, the configuration names "
                    f"{stage['core']}"
                    + (": a host route bypasses the device"
                       if stage["device"] else ""))
            if stage["device"]:
                devs, sent = _assert_on_device(core, platform)
                devices |= devs
                dispatches += sent
        at += stage["workers"]
    return devices, dispatches
