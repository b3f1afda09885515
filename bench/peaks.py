"""Published peaks per ``device_kind`` (table in ``peaks.json``)."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
#: Which peak a tier's operations count against (see peaks.json on f32).
PEAK_KEY = {"int8": "int8_ops_per_s", "f32": "bf16_flops_per_s"}


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The row of ``device_kind``; a kind the table lacks is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def compute_peak(row: dict, dtype: str) -> float:
    """Operations per second the tier ``dtype`` can reach at most."""
    return float(row[PEAK_KEY[dtype]])
