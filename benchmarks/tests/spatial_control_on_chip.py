"""The precision control of a float-ring cell, read on the chip.

    chiprun -- python3 benchmarks/tests/spatial_control_on_chip.py \\
        --workload <cell> --seeds 31,32,33 --seconds 8

``control_on_chip.py`` is the reading (a short window of the cell through
``run.measure``, the comparison's numbers for the program and for the plain
reference one precision narrower, over the same chunk log), but it knows
integer accumulators and float32 only and is a file the benchmark had.  This
is the same script with the one control it lacks: a configuration whose rings
are float32 and whose ``precision.control`` is float16.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import control_on_chip  # noqa: E402

control_on_chip.NARROWER["float16"] = np.float16

if __name__ == "__main__":
    sys.exit(control_on_chip.main())
