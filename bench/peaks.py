"""The chip's published peaks, keyed by JAX's ``device_kind``.

A kind that is not in ``peaks.json`` is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    with open(path) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(table)}")
    return table[device_kind]
