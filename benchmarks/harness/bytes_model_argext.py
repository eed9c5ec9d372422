"""Bytes the launches of the arg-extremum step family need, from what they
were launched with.

What is counted, and nothing else:

* ``in``    the blocks of rows appended since the last launch, as they were
  placed on the device (``bytes_shipped``): the step reads each from HBM once
  and writes its rows into the ring once, so they count twice;
* ``eval``  the ring cells the evaluated windows span (``eval_rows``), in the
  ring's dtype: to name a window's extremum the step has to read every cell
  of the window once;
* ``out``   three accumulators per evaluated window (extremum, index of its
  first occurrence, count of cells at the extremum).

Not counted: the rest of the ring (the blockwise walk reads whole blocks, so
up to one block before and one after the window come along), a compaction's
copy of the ring, the window descriptors, any re-read.  So the count is a
lower bound on the bytes the family's executables moved, the time is the
device time of exactly those executables, and the share
``bytes / time / peak`` cannot pass 100% on a device that moves at most
``peak`` bytes a second: a reading above it means the counters and the traced
slice do not cover the same launches.
"""

from __future__ import annotations


def argext_bytes(bytes_shipped, eval_rows, eval_windows, cell_itemsize=4,
                 outs_per_window=3, out_itemsize=4):
    """Lower bound of bytes moved through HBM by the family's launches."""
    return (2.0 * float(bytes_shipped)
            + float(eval_rows) * cell_itemsize
            + float(eval_windows) * outs_per_window * out_itemsize)
