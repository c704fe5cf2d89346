"""Published per-chip peaks, keyed by JAX's ``device_kind``.

The table is the yardstick of every share of a peak or a roofline; it is
not the simulator's parameter file.  A device missing from it is an error,
never a default.
"""
from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(_TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to {_TABLE.name} with its source")
    return table[device_kind]
