"""A deployment's malloc thresholds, set for the benchmark's process.

A configuration file may state ``host_allocator``: glibc's ``mmap_threshold``,
``trim_threshold`` and ``top_pad`` in bytes, as a deployment's launch script
would set them with ``MALLOC_MMAP_THRESHOLD_``, ``MALLOC_TRIM_THRESHOLD_`` and
``MALLOC_TOP_PAD_``.  Under glibc's default the mmap threshold follows the
largest mapping freed (up to 32 MiB), so a run serves the batches its threads
hand one another from the heap or maps and unmaps every one, by what happened
to be freed first: two steady rates for one program.  The harness sets what
the file states before it builds the first pipeline, and nothing where the
file states nothing.  The program sets no allocator policy of its own.

Imports the standard library only.
"""

from __future__ import annotations

import ctypes
import os

#: glibc's mallopt parameters (malloc.h)
MALLOPT = {"trim_threshold": -1, "top_pad": -2, "mmap_threshold": -3}


def apply(cfg, environ=os.environ, libc=None):
    """Set the thresholds ``cfg["host_allocator"]`` states.  Returns what was
    done: ``"set"``; ``"none stated"``; ``"left to the environment"`` where
    it sets a ``MALLOC_*`` variable itself; ``"no mallopt"`` where the C
    library has none or refuses a value."""
    alloc = cfg.get("host_allocator")
    if not alloc:
        return "none stated"
    if any(k.startswith("MALLOC_") for k in environ):
        return "left to the environment"
    mallopt = getattr(libc or ctypes.CDLL(None), "mallopt", None)
    if mallopt is None:
        return "no mallopt"
    ok = all(mallopt(MALLOPT[k], int(alloc[k])) for k in MALLOPT)
    return "set" if ok else "no mallopt"
