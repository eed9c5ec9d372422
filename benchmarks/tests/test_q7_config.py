"""The NEXMark Q7 configuration's host allocator setting: the builder sets
the malloc thresholds its file states, and leaves the process alone where
the environment has set a ``MALLOC_*`` variable itself."""

import ctypes
import platform

import pytest

from conftest import load


def _config():
    import importlib
    return importlib.import_module("configs.q7_highest_bid")


def test_a_malloc_variable_in_the_environment_wins():
    cfg = load("configs", "q7_highest_bid.json")
    assert _config().set_host_allocator(
        cfg, environ={"MALLOC_ARENA_MAX": "2"}) is False


def test_the_thresholds_are_the_files_and_batches_come_from_the_heap():
    cfg = load("configs", "q7_highest_bid.json")
    alloc = cfg["host_allocator"]
    assert alloc["mmap_threshold"] > 26 << 20     # one 262,144 x 100 B chunk
    assert alloc["trim_threshold"] >= alloc["mmap_threshold"]
    if platform.libc_ver()[0] != "glibc":
        pytest.skip("mallopt is glibc's")
    assert _config().set_host_allocator(cfg, environ={}) is True
    libc = ctypes.CDLL(None)
    libc.malloc.restype = ctypes.c_void_p
    libc.malloc.argtypes = [ctypes.c_size_t]
    libc.free.argtypes = [ctypes.c_void_p]
    p = libc.malloc(24 << 20)
    try:
        # glibc marks a chunk that is its own mapping in the size word
        # that precedes it (IS_MMAPPED, bit 1)
        size_word = ctypes.c_size_t.from_address(
            p - ctypes.sizeof(ctypes.c_size_t)).value
        assert not size_word & 2
    finally:
        libc.free(p)
