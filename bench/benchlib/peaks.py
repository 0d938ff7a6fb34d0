"""Per-chip peaks, keyed by JAX's ``device_kind``.  A device that is not in
``peaks.json`` is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


def lookup(device_kind: str, table: Path = TABLE) -> dict:
    devices = json.loads(table.read_text())["devices"]
    if device_kind not in devices:
        raise LookupError(f"no peaks for device kind {device_kind!r}; "
                          f"known: {sorted(devices)}")
    return devices[device_kind]
