"""Published peaks of each chip, keyed by JAX's ``device_kind``, for the
roofline shares of the kernel cells to come.  A chip that is not in
``bench/peaks.json`` is an error, never a default."""
from __future__ import annotations

import json
import pathlib

_PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str) -> dict:
    table = json.loads(_PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]
