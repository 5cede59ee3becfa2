"""Published peaks per device kind (``peaks.json``); a device that is not in
the table is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    with open(TABLE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {TABLE.name}; have {sorted(table)}")
    return table[device_kind]
