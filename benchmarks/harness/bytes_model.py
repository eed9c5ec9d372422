"""Bytes a window step needs per launch, from the shapes it was launched with.

What is counted, and nothing else:

* ``in``  the blocks of rows appended since the last launch, as they were
  placed on the device (``bytes_shipped``: one block per shipped field, in the
  dtype it is shipped in).  The step has to read each of them from HBM once
  to fold them into the ring.
* ``out`` one accumulator per window result and statistic
  (``windows x n_stats x acc_itemsize``), which the step has to write once.

Not counted: the ring itself (the regular step cumsums the whole ring, which
is the implementation's choice, not the algorithm's need), the window
descriptors (a few bytes per window, less than the results), padding that a
step adds on the device, and any re-read.  So the count is a lower bound on
the bytes the executables moved, the time is the device time of all of them,
and the share ``bytes / time / peak`` cannot pass 100% on a device that moves
at most ``peak`` bytes a second: a reading above it means the counters and the
traced slice do not cover the same launches.
"""

from __future__ import annotations


def step_bytes(bytes_shipped, windows, n_stats, acc_itemsize=4):
    """Lower bound of bytes moved through HBM by the launches that shipped
    ``bytes_shipped`` and evaluated ``windows`` window results."""
    return float(bytes_shipped) + float(windows) * n_stats * acc_itemsize


def hbm_share_pct(n_bytes, device_seconds, hbm_bytes_per_s):
    """Share of the HBM roofline, in percent; None where nothing ran."""
    if device_seconds <= 0:
        return None
    return 100.0 * n_bytes / device_seconds / hbm_bytes_per_s
