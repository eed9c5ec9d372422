"""The table of device peaks, keyed by ``device_kind``.  A device that is
not in the table is an error, not a default."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind):
    with open(_PATH) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} has no entry in {_PATH}: add its "
            f"published peaks with their source before reporting a share")
    return table[device_kind]
