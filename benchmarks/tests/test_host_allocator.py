"""The harness sets the malloc thresholds a configuration file states, and
leaves the process alone where the file states none or the environment has
set a ``MALLOC_*`` variable itself."""

import ctypes
import glob
import os
import platform

import pytest

from conftest import BENCH, load
from harness import host_allocator


class FakeLibc:
    def __init__(self, ok=1):
        self.calls, self.ok = [], ok

    def mallopt(self, param, value):
        self.calls.append((param, value))
        return self.ok


ALLOC = {"mmap_threshold": 1 << 26, "trim_threshold": 1 << 29,
         "top_pad": 1 << 26}


def test_nothing_stated_nothing_set():
    libc = FakeLibc()
    assert host_allocator.apply({}, environ={}, libc=libc) == "none stated"
    assert libc.calls == []


def test_a_malloc_variable_in_the_environment_wins():
    libc = FakeLibc()
    assert host_allocator.apply(
        {"host_allocator": ALLOC}, environ={"MALLOC_ARENA_MAX": "2"},
        libc=libc) == "left to the environment"
    assert libc.calls == []


def test_the_three_thresholds_go_to_mallopt_under_glibcs_numbers():
    libc = FakeLibc()
    assert host_allocator.apply({"host_allocator": ALLOC}, environ={},
                                libc=libc) == "set"
    assert sorted(libc.calls) == [(-3, 1 << 26), (-2, 1 << 26), (-1, 1 << 29)]


@pytest.mark.parametrize("libc", [FakeLibc(ok=0), object()],
                         ids=["refused", "absent"])
def test_a_c_library_without_mallopt_is_said_not_hidden(libc):
    assert host_allocator.apply({"host_allocator": ALLOC}, environ={},
                                libc=libc) == "no mallopt"


def _configs_with_allocator():
    out = []
    for path in sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))):
        name = os.path.basename(path)
        if "host_allocator" in load("configs", name):
            out.append(name)
    return out


@pytest.mark.parametrize("name", _configs_with_allocator())
def test_a_stated_threshold_holds_the_configurations_largest_chunk(name):
    """Every chunk a cell of the configuration pushes comes from the heap."""
    cfg = load("configs", name)
    alloc = cfg["host_allocator"]
    assert alloc["trim_threshold"] >= alloc["mmap_threshold"]
    import importlib
    dtype = importlib.import_module(
        "configs." + name[:-len(".json")]).record_dtype(cfg)
    for path in glob.glob(os.path.join(BENCH, "workloads", "*.json")):
        cell = load("workloads", os.path.basename(path))
        if cell["config"] + ".json" == name:
            assert int(cell["chunk"]) * dtype.itemsize \
                < alloc["mmap_threshold"], cell["name"]


def test_set_for_real_a_batch_sized_block_comes_from_the_heap():
    if platform.libc_ver()[0] != "glibc":
        pytest.skip("mallopt is glibc's")
    assert host_allocator.apply({"host_allocator": ALLOC},
                                environ={}) == "set"
    libc = ctypes.CDLL(None)
    libc.malloc.restype = ctypes.c_void_p
    libc.malloc.argtypes = [ctypes.c_size_t]
    libc.free.argtypes = [ctypes.c_void_p]
    p = libc.malloc(35 << 20)
    try:
        # glibc marks a chunk that is its own mapping in the size word
        # that precedes it (IS_MMAPPED, bit 1)
        size_word = ctypes.c_size_t.from_address(
            p - ctypes.sizeof(ctypes.c_size_t)).value
        assert not size_word & 2
    finally:
        libc.free(p)
