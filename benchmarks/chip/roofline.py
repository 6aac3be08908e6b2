"""Peaks of the chip and the bytes a scan needs, counted from its shapes.

``peaks.json`` holds the published peaks of each chip, keyed by the
``device_kind`` JAX reports; a chip that is not there is an error, not a
default.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}")
    return table[device_kind]


def scan_bytes(leaves_scanned: int, candidates: int, *, leaf_size: int,
               segments: int, series_len: int, rows: int) -> int:
    """Bytes a k-NN scan has to read from device memory at the least:
    the one-byte SAX code of every segment of every row of the leaves it
    scanned, and the float32 raw series of every row it verified."""
    code_rows = min(leaves_scanned * leaf_size, rows)
    return code_rows * segments + candidates * series_len * 4
