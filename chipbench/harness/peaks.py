"""The table of peaks, keyed by ``device_kind``."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(Exception):
    """The run's device has no row in the peaks table."""


def peaks_for(device_kind):
    with open(_PATH) as f:
        table = json.load(f)
    row = table.get(device_kind)
    if not isinstance(row, dict):
        known = sorted(k for k in table if not k.startswith("_"))
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} (known: {known})")
    return row
