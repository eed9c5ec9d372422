"""Plain reference of the ``ysb_kf_eo`` deployment: ``ysb_kf``'s, under
exactly-once.

numpy only; nothing of the program is imported.  The deployment is the Yahoo
Streaming Benchmark of ``ysb_kf`` run with a checkpoint every second and one
window worker killed and restored in mid-stream.  A crash changes no expected
result -- that is the guarantee -- so the stream and its answers are
``ysb_kf_oracle``'s own functions, taken over and not copied.

What the guarantee adds is in how a run is read against them
(``harness/check.compare``, every limit 0), by name:

``duplicates``  a (campaign, window id) that reached the sink twice: the
                replayed prefix of the restored worker's output was not
                dropped (at-least-once);
``missing``     one that never reached it: state or journalled input lost
                across the crash (at-most-once);
``wrong.*``     one whose value differs: the restored state, or the replay,
                is not what the uncrashed run held.

``delivery_faults`` names the first two for the tests and the control.
"""

from __future__ import annotations

from .ysb_kf_oracle import (NEVER, brute_force, columns,  # noqa: F401
                            events_of_missing, expected, id_shift,
                            period_events)

#: the numbers of the comparison that a broken delivery guarantee moves
DELIVERY = ("duplicates", "missing")


def delivery_faults(numbers):
    """Of the comparison's numbers, those by which the run was not
    exactly-once: ``{"duplicates": n}`` delivered twice (at-least-once),
    ``{"missing": n}`` never delivered (at-most-once); empty when every
    (campaign, window id) reached the sink exactly once."""
    return {name: int(numbers[name]) for name in DELIVERY
            if numbers.get(name, 0) > 0}
