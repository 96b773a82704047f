"""The chip's published peaks, keyed by JAX's ``device_kind`` (peaks.json).
A device that is not in the table is an error, not a default."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {TABLE.name} "
                       f"({sorted(table)}): add its published peaks first")
    return table[device_kind]
