"""ctypes bindings for the C++ native host runtime (native/wf_native.cpp).

The shared library is built on demand with ``make -C native`` (g++ only, no
third-party dependencies).  ``WF_NO_NATIVE=1`` is the explicit opt-out to
the pure-Python cores; a checkout that ships no native source runs on them
too.  A build or bind *failure* with the source present is an error
(:class:`NativeBuildError`, carrying make's stderr), never a quiet switch
of cores.  Every call into the library releases the GIL, so farm workers
running native cores get true multicore host parallelism — the
FastFlow-pinned-threads property the reference gets for free from being a
C++ library.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SO = os.path.join(_DIR, "libwfnative.so")

_lock = threading.Lock()
_lib = None
_tried = False
_error = None

i64 = ctypes.c_longlong
p_i64 = ctypes.POINTER(i64)
p_i32 = ctypes.POINTER(ctypes.c_int32)
p_int = ctypes.POINTER(ctypes.c_int)


class NativeBuildError(RuntimeError):
    """The native source is present but did not build or bind."""


def _build() -> bool:
    """Run make; False when the checkout ships no native source."""
    if not os.path.exists(os.path.join(_DIR, "wf_native.cpp")):
        return False
    # always invoke make: it no-ops when up to date and rebuilds when the
    # host fingerprint changed (host.tag — a -march=native .so cached on
    # another CPU would SIGILL; mtime alone cannot see that)
    try:
        proc = subprocess.run(["make", "-C", _DIR], capture_output=True,
                              text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(
            f"make -C {_DIR} could not run: {e} (set WF_NO_NATIVE=1 to "
            "run the pure-Python cores on purpose)") from e
    if proc.returncode != 0 or not os.path.exists(_SO):
        raise NativeBuildError(
            f"make -C {_DIR} failed (exit {proc.returncode}); set "
            "WF_NO_NATIVE=1 to run the pure-Python cores on purpose\n"
            f"{proc.stderr.strip()}")
    return True


def load():
    """Load (building if needed) the native library; None when the checkout
    ships no native source.  Raises :class:`NativeBuildError` — on every
    call, the first failure is kept — when the build or the bind fails."""
    global _tried, _error
    with _lock:
        if _error is not None:
            raise _error
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            if not _build():
                return None
            try:
                return _bind(ctypes.CDLL(_SO))
            except (OSError, AttributeError) as e:
                # dlopen failure or a missing required symbol
                raise NativeBuildError(
                    f"{_SO} built but does not bind: {e}") from e
        except NativeBuildError as e:
            _error = e
            raise


def _bind(lib):
    global _lib
    lib.wf_core_new.restype = ctypes.c_void_p
    lib.wf_core_new.argtypes = ([i64] * 2 + [ctypes.c_int] * 2
                                + [i64] * 11 + [ctypes.c_int])
    lib.wf_core_free.argtypes = [ctypes.c_void_p]
    lib.wf_core_process.restype = i64
    lib.wf_core_process.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                    i64, i64, i64, i64, i64, i64, i64]
    lib.wf_core_eos.restype = i64
    lib.wf_core_eos.argtypes = [ctypes.c_void_p]
    lib.wf_core_force_flush.restype = i64
    lib.wf_core_force_flush.argtypes = [ctypes.c_void_p]
    lib.wf_core_barrier_flush.restype = i64
    lib.wf_core_barrier_flush.argtypes = [ctypes.c_void_p]
    lib.wf_renum_new.restype = ctypes.c_void_p
    lib.wf_renum_new.argtypes = []
    lib.wf_renum_free.argtypes = [ctypes.c_void_p]
    lib.wf_renum_run.restype = None
    lib.wf_renum_run.argtypes = [ctypes.c_void_p, p_i64, i64, p_i64]
    lib.wf_renum_next.restype = i64
    lib.wf_renum_next.argtypes = [ctypes.c_void_p, i64]
    lib.wf_keymap_new.restype = ctypes.c_void_p
    lib.wf_keymap_new.argtypes = []
    lib.wf_keymap_free.argtypes = [ctypes.c_void_p]
    lib.wf_keymap_lookup.restype = i64
    lib.wf_keymap_lookup.argtypes = [ctypes.c_void_p, p_i64, i64, p_i64]
    lib.wf_keyscan_ordered.restype = i64
    lib.wf_keyscan_ordered.argtypes = [p_i64, p_i64, i64, p_i64, p_i64,
                                       p_i64, p_i64]
    lib.wf_cores_process_mt.restype = i64
    lib.wf_cores_process_mt.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), i64, ctypes.c_void_p,
        i64, i64, i64, i64, i64, i64, i64]
    lib.wf_max_fields.restype = i64
    lib.wf_max_fields.argtypes = []
    lib.wf_core_set_fields.restype = i64
    lib.wf_core_set_fields.argtypes = [ctypes.c_void_p, i64, p_int]
    lib.wf_cores_process_mt_f.restype = i64
    lib.wf_cores_process_mt_f.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), i64, ctypes.c_void_p,
        i64, i64, i64, i64, i64, i64, p_i64]
    lib.wf_launch_peek_wires.restype = ctypes.c_int
    lib.wf_launch_peek_wires.argtypes = [ctypes.c_void_p, p_int]
    lib.wf_launch_take_padded_f.restype = None
    lib.wf_launch_take_padded_f.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p), i64, i64,
        p_i64, p_i32, p_i32, p_i32, p_i64, p_i64, p_i64, p_i64, p_i64,
        p_i64]
    lib.wf_core_release.restype = None
    lib.wf_core_release.argtypes = [ctypes.c_void_p]
    lib.wf_core_set_arg.restype = i64
    lib.wf_core_set_arg.argtypes = [ctypes.c_void_p, i64, i64, i64]
    lib.wf_core_arg_gather.restype = i64
    lib.wf_core_arg_gather.argtypes = [
        ctypes.c_void_p, i64, p_i64, p_i64, p_i64, p_i64, p_i32, p_i32,
        p_i64, p_i64]
    lib.wf_launch_peek_arg.restype = ctypes.c_int
    lib.wf_launch_peek_arg.argtypes = [ctypes.c_void_p, p_i64, p_i64]
    lib.wf_launch_peek_cut.restype = ctypes.c_int
    lib.wf_launch_peek_cut.argtypes = [ctypes.c_void_p, p_int, p_i64]
    lib.wf_core_fast_rows.restype = i64
    lib.wf_core_fast_rows.argtypes = [ctypes.c_void_p]
    lib.wf_core_archive_row_bytes.restype = i64
    lib.wf_core_archive_row_bytes.argtypes = [ctypes.c_void_p]
    lib.wf_core_set_stream.restype = ctypes.c_int
    lib.wf_core_set_stream.argtypes = [ctypes.c_void_p, i64]
    lib.wf_core_stream_stats.restype = None
    lib.wf_core_stream_stats.argtypes = [ctypes.c_void_p, p_i64]
    lib.wf_launch_peek_progress.restype = ctypes.c_int
    lib.wf_launch_peek_progress.argtypes = [ctypes.c_void_p, p_i64]
    lib.wf_core_fired_pending.restype = i64
    lib.wf_core_fired_pending.argtypes = [ctypes.c_void_p]
    lib.wf_core_flush_early.restype = i64
    lib.wf_core_flush_early.argtypes = [ctypes.c_void_p, p_i64]
    lib.wf_launch_pending.restype = i64
    lib.wf_launch_pending.argtypes = [ctypes.c_void_p]
    lib.wf_launch_live_rows.restype = i64
    lib.wf_launch_live_rows.argtypes = [ctypes.c_void_p]
    lib.wf_launch_peek.restype = ctypes.c_int
    lib.wf_launch_peek.argtypes = [ctypes.c_void_p, p_i64, p_i64, p_i64,
                                   p_int, p_int, p_i64, p_i64]
    lib.wf_launch_take.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                   p_i64, p_i32, p_i32, p_i32,
                                   p_i64, p_i64, p_i64, p_i64]
    lib.wf_launch_take_padded.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, i64, i64,
        p_i64, p_i32, p_i32, p_i32, p_i64, p_i64, p_i64, p_i64, p_i64,
        p_i64]
    lib.wf_launch_peek_regular.restype = ctypes.c_int
    lib.wf_launch_peek_regular.argtypes = [ctypes.c_void_p, p_i64]
    lib.wf_launch_coalesce.restype = i64
    lib.wf_launch_coalesce.argtypes = [ctypes.c_void_p, i64, i64, i64]
    lib.wf_launch_take_regular.argtypes = [ctypes.c_void_p, p_i32,
                                           p_i32, p_i32, p_i32]
    # the stream-time host core's fold (core/vecinc.VecStreamCore): every
    # array is the caller's, passed as an address
    lib.wf_sfold_max_parts.restype = i64
    lib.wf_sfold_max_parts.argtypes = []
    lib.wf_sfold_index.restype = None
    lib.wf_sfold_index.argtypes = [ctypes.c_void_p, i64, ctypes.c_void_p,
                                   i64]
    lib.wf_sfold.restype = i64
    lib.wf_sfold.argtypes = ([ctypes.c_void_p] + [i64] * 7
                             + [ctypes.c_void_p] * 2 + [i64]
                             + [ctypes.c_void_p, i64, ctypes.c_void_p, i64]
                             + [ctypes.c_void_p] * 3)
    lib.wf_queue_new.restype = ctypes.c_void_p
    lib.wf_queue_new.argtypes = [i64]
    lib.wf_queue_free.argtypes = [ctypes.c_void_p]
    lib.wf_queue_push.restype = ctypes.c_int
    lib.wf_queue_push.argtypes = [ctypes.c_void_p, i64, i64]
    lib.wf_queue_pop.restype = ctypes.c_int
    lib.wf_queue_pop.argtypes = [ctypes.c_void_p, p_i64, p_i64]
    lib.wf_queue_close.argtypes = [ctypes.c_void_p]
    # overload-policy entry points (runtime/overload.py) — absent from a
    # pre-robustness .so; bind tolerantly so an old library still serves
    # every default path and only the opt-in shed/deadline knobs fall back
    # to the Python queue (engine._make_inbox gates on this flag)
    try:
        lib.wf_queue_try_push.restype = ctypes.c_int
        lib.wf_queue_try_push.argtypes = [ctypes.c_void_p, i64, i64]
        lib.wf_queue_push_timed.restype = ctypes.c_int
        lib.wf_queue_push_timed.argtypes = [ctypes.c_void_p, i64, i64, i64]
        lib.wf_queue_try_pop.restype = ctypes.c_int
        lib.wf_queue_try_pop.argtypes = [ctypes.c_void_p, p_i64, p_i64]
        lib.wf_has_overload_queue = True
    except AttributeError:
        lib.wf_has_overload_queue = False
    # state-ABI entry points (checkpoints + keyed live rescale for the
    # native core, docs/ROBUSTNESS.md "Native state ABI") — absent from a
    # pre-ABI .so; bind tolerantly so an old library still serves every
    # default execution path while snapshot/migration requests decline
    # loudly (SnapshotUnsupported / check WF215 gate on this flag)
    try:
        lib.wf_abi_version.restype = i64
        lib.wf_abi_version.argtypes = []
        lib.wf_core_state_size.restype = i64
        lib.wf_core_state_size.argtypes = [ctypes.c_void_p]
        lib.wf_core_state_export.restype = i64
        lib.wf_core_state_export.argtypes = [ctypes.c_void_p,
                                             ctypes.c_void_p, i64]
        lib.wf_core_state_import.restype = i64
        lib.wf_core_state_import.argtypes = [ctypes.c_void_p,
                                             ctypes.c_void_p, i64]
        lib.wf_core_key_count.restype = i64
        lib.wf_core_key_count.argtypes = [ctypes.c_void_p]
        lib.wf_core_key_list.restype = i64
        lib.wf_core_key_list.argtypes = [ctypes.c_void_p, p_i64, i64]
        lib.wf_core_key_state_size.restype = i64
        lib.wf_core_key_state_size.argtypes = [ctypes.c_void_p, i64]
        lib.wf_core_key_export.restype = i64
        lib.wf_core_key_export.argtypes = [ctypes.c_void_p, i64,
                                           ctypes.c_void_p, i64]
        lib.wf_core_key_import.restype = i64
        lib.wf_core_key_import.argtypes = [ctypes.c_void_p,
                                           ctypes.c_void_p, i64]
        lib.wf_core_key_neutralize.restype = i64
        lib.wf_core_key_neutralize.argtypes = [ctypes.c_void_p, i64]
        lib.wf_has_state_abi = True
    except AttributeError:
        lib.wf_has_state_abi = False
    _lib = lib
    return _lib


def available() -> bool:
    return load() is not None


def enabled():
    """The native library, or None when the source is absent or opted out
    via WF_NO_NATIVE=1 — the single selection gate for every
    native-vs-Python choice (cores, engine channels)."""
    if os.environ.get("WF_NO_NATIVE", "") == "1":
        return None
    return load()
