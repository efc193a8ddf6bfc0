"""The device a run measures, and the peaks table.

Every result names its device as JAX reports it. A run that finds no
TPU, or fewer chips than its cell asks for, stops before it measures:
there is no fall-back to the CPU. The peaks table (``peaks.json``) is
keyed by ``device_kind``; a kind it does not list is an error.
"""
from __future__ import annotations

import os
from typing import Dict, List

from .registry import BENCH_DIR, load_json


class DeviceError(RuntimeError):
    """The machine cannot run the cell as asked."""


def describe(devices: List) -> Dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def require(devices: List, chips: int) -> Dict:
    """The cell's device record, as JAX reports it; raises unless
    ``devices`` are at least ``chips`` TPUs of a kind in the peaks
    table. A cell uses its first ``chips`` devices: nothing of a
    one-chip cell shards, and its trace is read from those chips
    alone."""
    info = describe(devices)
    if info["platform"] != "tpu":
        raise DeviceError(f"JAX's first device is {info['platform']!r}, "
                          "not a TPU")
    if info["count"] < chips:
        raise DeviceError(f"the cell needs {chips} chips, JAX sees "
                          f"{info['count']}")
    peaks(info["kind"])
    return info


def peaks(kind: str, path: str = os.path.join(BENCH_DIR, "peaks.json")
          ) -> Dict:
    """Published peaks of one chip of ``kind``."""
    table = load_json(path)
    try:
        return table["devices"][kind]
    except KeyError:
        raise DeviceError(f"device kind {kind!r} is not in the peaks table "
                          f"({sorted(table['devices'])})") from None


def memory_peak_bytes(devices: List) -> int:
    """Peak bytes in use on the fullest chip, where the backend says."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
