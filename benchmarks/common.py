"""Shared benchmark utilities: timing, CSV emission, result persistence."""
from __future__ import annotations

import json
import os
import time
from typing import Any, Callable

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")


def timed(fn: Callable[[], Any]) -> tuple:
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e6


def emit(name: str, us_per_call: float, derived: Any) -> str:
    line = f"{name},{us_per_call:.1f},{derived}"
    print(line, flush=True)
    return line


def save_json(name: str, payload: Any) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=str)
    return path
