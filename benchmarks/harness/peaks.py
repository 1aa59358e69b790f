"""The table of published peaks, keyed by `device_kind`; a device that is
not in the table is an error, not a default."""

import json
import os
from typing import Dict


def peaks_for(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in benchmarks/harness/peaks.json")
    return table[device_kind]
