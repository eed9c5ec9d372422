"""Bytes the join step needs for the windows it joined, from their row
counts.

The step (``windflow_tpu/ops/join.py``, ``jit_wf_step_multi`` bound to it) is
made of passes over int32 columns in HBM.  What is counted, each once, and
nothing else -- ``n`` the rows of both sides the joined windows hold,
``m`` their matches, 4 bytes a cell:

* ``slice``    the window out of the rings: every ring column read and
  written once (``ring_cols`` x 2 x n);
* ``sort``     the sort by (key, side): its operands -- the ring columns and
  the rows' places -- read and written once ((``ring_cols`` + 1) x 2 x n); a
  comparison sort makes log n passes, which are not counted;
* ``search``   the two running maxima (the newest left row's key, where it
  stands) read and written once each (2 x 2 x n) and the left row's time
  gathered (2 x n);
* ``compact``  the second sort's operands -- the order and the output
  columns -- read and written once ((1 + ``out_cols``) x 2 x n);
* ``out``      per match and output column one slot read and written
  (``out_cols`` x 2 x m).

Not counted: the append of the launch's own rectangle, the sorts' further
passes, padding up to the step's fixed length and to the result's slots,
masks and selects that fuse into a pass, a carried left field's gather.  So the count is a lower bound on
the bytes the executable moved, the time is that executable's device time,
and the share ``bytes / time / peak`` cannot pass 100% on a device that moves
at most ``peak`` bytes a second: a reading above it means the counters and
the traced slice do not cover the same launches.
"""

from __future__ import annotations

CELL = 4


def join_bytes(rows, matches, ring_cols=5, out_cols=4):
    """Lower bound of bytes moved through HBM by the step for windows of
    ``rows`` rows (both sides) and ``matches`` results in all."""
    n, m = float(rows), float(matches)
    parts = {
        "slice": ring_cols * 2 * n,
        "sort": (ring_cols + 1) * 2 * n,
        "search": (2 * 2 + 2) * n,
        "compact": (1 + out_cols) * 2 * n,
        "out": out_cols * 2 * m,
    }
    return {name: CELL * cells for name, cells in parts.items()}
