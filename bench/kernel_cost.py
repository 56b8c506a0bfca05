"""Peaks of each chip (``peaks.json``, keyed by JAX's ``device_kind``) and
the bytes each kernel of the program must move per call, from its shapes."""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind: str, key: str) -> float:
    """One published peak of a chip; a chip not in the table is an error."""
    with open(PEAKS) as f:
        devices = json.load(f)["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return float(devices[device_kind][key])


def pattern_cmp_bytes(rows: int, k: int) -> int:
    """``kernels/pattern_cmp``: per row it reads a ``k``-token int32 suffix
    window, a ``k``-token int32 pattern slice and two int32 bounds, and
    writes two int32 results (``rows`` as the host passes them, before the
    kernel pads them to its bucket)."""
    return rows * (2 * k + 2 + 2) * 4
