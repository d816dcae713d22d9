"""The published peaks of each device kind (``peaks.json``)."""
from __future__ import annotations

import json
import os
from typing import Dict


def peak_for(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in benchmark/peaks.json")
    return table[device_kind]
