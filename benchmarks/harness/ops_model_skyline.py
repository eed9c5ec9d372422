"""Vector operations an all-pairs skyline needs, from the windows' lengths.

A window of ``n`` points tests each point against each: ``n^2`` pair tests.
One pair test is 9 elementwise operations on 32-bit lanes -- four compares
(``x_j <= x_i``, ``y_j <= y_i``, ``x_j < x_i``, ``y_j < y_i``), an ``or`` of
the two strict ones, three ``and`` (the two weak ones, the strict pair, the
cell's mask) and the ``or`` that folds the row's verdicts -- which is also
what XLA's cost analysis counts for the compiled step (9.0 a pair of the
padded shape).

Counted: the pair tests of the windows' REAL lengths, nothing else.  Not
counted: the cells the library pads a window with (a window of 51,200 points
runs as 65,536), the windows it pads a batch with, the append and the gathers
around the function, the two sums of the result.  So the count is a lower
bound on what the device executed, the time is the device time of the whole
step, and the share ``operations / time / peak`` of an upper-bound peak can
only read low.
"""

from __future__ import annotations

OPS_PER_PAIR = 9


def skyline_ops(window_lengths):
    """Operations for windows of these lengths."""
    return float(OPS_PER_PAIR * sum(int(n) * int(n) for n in window_lengths))


def skyline_ops_at_least(rows, windows):
    """The least the sum of squares can be for ``windows`` windows holding
    ``rows`` points between them (all of one length: ``rows^2 / windows``),
    where only the two totals are known.  In an open loop every window holds
    rate x window points, give or take one, and the bound is tight."""
    if windows <= 0:
        return 0.0
    return float(OPS_PER_PAIR) * float(rows) * float(rows) / float(windows)


def vector_share_pct(n_ops, device_seconds, vector_op_per_s):
    """Share of the vector peak, in percent; None where nothing ran."""
    if device_seconds <= 0:
        return None
    return 100.0 * n_ops / device_seconds / vector_op_per_s
