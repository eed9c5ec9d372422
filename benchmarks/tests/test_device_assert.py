"""The device-path assertion on cores made by hand: one class name for every
window core (the form of the accepted configurations), and a core per window
stage (a list), with a stage the program rightly keeps on the host."""

import pytest

from harness import device_assert
from harness.device_assert import DevicePathError, assert_device_path


class Dev:
    def __init__(self, n, platform="tpu"):
        self.id, self.platform = n, platform

    def __repr__(self):
        return f"{self.platform}:{self.id}"


class Ex:
    def __init__(self, device, dispatches=7):
        self.device, self.dispatches = device, dispatches


class Launching:                  # an executor that counts launches instead
    def __init__(self, device, launches):
        self.device, self.launches = device, launches


class NativeResidentCore:
    def __init__(self, *executors, delegate=None):
        self.executors, self._delegate = list(executors), delegate


class ResidentWinSeqCore:
    def __init__(self, executor):
        self.executor = executor


class VecIncSlidingCore:          # a host core: no executor at all
    pass


TPU0, TPU1, CPU0 = Dev(0), Dev(1), Dev(0, "cpu")
TWO_STAGES = [
    {"stage": "count per key", "core": "VecIncSlidingCore", "workers": 1,
     "device": False},
    {"stage": "sum of counts", "core": "NativeResidentCore", "workers": 2,
     "device": True}]


def test_string_form_as_today():
    cores = [NativeResidentCore(Ex(TPU0, 3)), NativeResidentCore(Ex(TPU0, 4))]
    devices, sent = assert_device_path(cores, "NativeResidentCore", 2, "tpu")
    assert devices == {TPU0} and sent == 7
    # several executors of one core; one core with ``executor``; launches
    devices, sent = assert_device_path(
        [NativeResidentCore(Ex(TPU0, 1), Launching(TPU1, 2))],
        "NativeResidentCore", 1, "tpu")
    assert devices == {TPU0, TPU1} and sent == 3
    assert assert_device_path([ResidentWinSeqCore(Ex(TPU1, 5))],
                              "ResidentWinSeqCore", 1, "tpu") == ({TPU1}, 5)
    assert device_assert.describe("NativeResidentCore", 2) \
        == "2 x NativeResidentCore"


def test_two_stages_with_a_host_count_stage_pass():
    cores = [VecIncSlidingCore(), NativeResidentCore(Ex(TPU0, 3)),
             NativeResidentCore(Ex(TPU0, 4))]
    devices, sent = assert_device_path(cores, TWO_STAGES, 2, "tpu")
    assert devices == {TPU0} and sent == 7       # the host stage adds none
    assert device_assert.describe(TWO_STAGES, 2) \
        == "1 x VecIncSlidingCore (host) > 2 x NativeResidentCore"


def test_the_string_form_refuses_the_same_graph():
    """What a two-stage query with a count in it met before the list form:
    every core held to the one class."""
    cores = [VecIncSlidingCore(), NativeResidentCore(Ex(TPU0)),
             NativeResidentCore(Ex(TPU0))]
    with pytest.raises(DevicePathError, match="3 window cores for 2"):
        assert_device_path(cores, "NativeResidentCore", 2, "tpu")
    with pytest.raises(DevicePathError, match="is VecIncSlidingCore"):
        assert_device_path(cores, "NativeResidentCore", 3, "tpu")


def _cores(**over):
    return [over.get("first", VecIncSlidingCore()),
            over.get("second", NativeResidentCore(Ex(TPU0))),
            over.get("third", NativeResidentCore(Ex(TPU0)))]


FAILURES = {
    "a wrong class on the device stage":
        (_cores(third=ResidentWinSeqCore(Ex(TPU0))), TWO_STAGES, 2,
         "is ResidentWinSeqCore, the configuration names NativeResidentCore"),
    "a wrong class on the host stage":
        (_cores(first=NativeResidentCore(Ex(TPU0))), TWO_STAGES, 2,
         "'count per key' is NativeResidentCore"),
    "a wrong count":
        (_cores()[:2], TWO_STAGES, 2, "2 window cores for 3 window workers"),
    "a device stage that never dispatched":
        (_cores(second=NativeResidentCore(Ex(TPU0, 0))), TWO_STAGES, 2,
         "never dispatched"),
    "a device stage that handed its stream to a host core":
        (_cores(second=NativeResidentCore(
            Ex(TPU0), delegate=VecIncSlidingCore())), TWO_STAGES, 2,
         "handed the stream to VecIncSlidingCore"),
    "an executor on another platform":
        (_cores(third=NativeResidentCore(Ex(CPU0))), TWO_STAGES, 2,
         "the run is on tpu"),
    "a list with no device stage":
        ([VecIncSlidingCore()], TWO_STAGES[:1], 0, "no window stage on the"),
    "a builder that states another count of device workers":
        (_cores(), TWO_STAGES, 3, "its builder states 3"),
}


@pytest.mark.parametrize("what", sorted(FAILURES))
def test_one_failure_each(what):
    cores, expected, n_workers, text = FAILURES[what]
    with pytest.raises(DevicePathError, match=text):
        assert_device_path(cores, expected, n_workers, "tpu")


def test_a_host_stage_is_excused_from_the_executor_checks():
    """It has no executor, no device and no dispatch to show."""
    assert not hasattr(VecIncSlidingCore(), "executor")
    assert_device_path(_cores(), TWO_STAGES, 2, "tpu")


def test_a_host_core_named_as_a_device_stage_is_refused_not_crashed_on():
    stages = [dict(TWO_STAGES[0], device=True), TWO_STAGES[1]]
    with pytest.raises(DevicePathError, match="has no executor"):
        assert_device_path(_cores(), stages, 3, "tpu")
