"""The chip a run is on: its check, its published peaks, its memory."""
from __future__ import annotations

import json
import os
from typing import Dict

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class DeviceError(Exception):
    """No accelerator, too few chips, or a chip of an unknown kind."""


def peaks_for(kind: str, path: str = PEAKS_FILE) -> Dict:
    """Published peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise DeviceError(f"no published peaks for device kind {kind!r}; "
                          f"known: {sorted(table)}")
    return table[kind]


def require_tpu(chips: int):
    """The first ``chips`` TPU devices, or DeviceError."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise DeviceError(f"needs {chips} TPU chip(s); JAX found "
                          f"{len(devices)} {devices[0].platform} device(s)")
    peaks_for(devices[0].device_kind)
    return devices[:chips]


def describe(devices) -> Dict:
    """The result line's ``device``, as JAX reports it."""
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": memory_peak(devices)}


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest chip, where the backend says."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
