"""The table of peaks, keyed by ``device_kind``; see peaks.json."""

from __future__ import annotations

import json
import os


def chip_peaks(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"peaks.json has no device_kind {device_kind!r}: add it with "
            "its source, do not default"
        )
    return table[device_kind]
