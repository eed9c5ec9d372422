"""The assertion that keeps a fallback from hiding (copied in idea from
``chip_smoke.py:assert_device_work``): a run whose window workers are not all
on the core class its configuration names, whose executors' devices are not
of the platform the run reports, or whose executors never dispatched, is not
a slower result -- it did not measure the device path, and the run fails.
"""

from __future__ import annotations


class DevicePathError(RuntimeError):
    pass


def assert_device_path(cores, core_name, n_workers, platform):
    """Returns ``(devices, dispatches)`` of the window cores' executors."""
    if len(cores) != n_workers:
        raise DevicePathError(
            f"{len(cores)} window cores for {n_workers} window workers")
    devices, dispatches = set(), 0
    for core in cores:
        if type(core).__name__ != core_name:
            raise DevicePathError(
                f"window core is {type(core).__name__}, the configuration "
                f"names {core_name}: a host route bypasses the device")
        delegate = getattr(core, "_delegate", None)
        if delegate is not None:
            raise DevicePathError(
                f"{core_name} handed the stream to {type(delegate).__name__}")
        for ex in getattr(core, "executors", None) or [core.executor]:
            mesh = getattr(ex, "mesh", None)
            owned = list(mesh.devices.flat) if mesh is not None \
                else [ex.device]
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
