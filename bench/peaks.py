"""The chips' published peaks (`peaks.json`), keyed by JAX's
`device_kind`.  A kind that is not in the table is an error, never a
default."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def lookup(device_kind: str) -> dict:
    with open(TABLE) as f:
        kinds = json.load(f)["kinds"]
    if device_kind not in kinds:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(kinds)}")
    return kinds[device_kind]
