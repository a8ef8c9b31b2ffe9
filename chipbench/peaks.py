"""Published peaks of one chip, keyed by JAX's ``device_kind``
(``peaks.json``).  A kind that is not in the table is an error: a
share of an unknown peak is not a number."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

TABLE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(TABLE.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {TABLE.name}; known: "
                       f"{sorted(k for k in table if not k.startswith('_'))}")
    return table[device_kind]
