"""Find cells, configurations, traffic mixes, generators and per-layer
metric readers by the names ``BENCHMARK.json`` gives them.

Layout under the benchmark directory (``benchmarks/chip``):

- ``configs/<file>.json``: a configuration, at the ``file`` its entry
  in ``BENCHMARK.json`` names;
- ``traffic/<traffic>.json``: a traffic mix; its ``generator`` key
  names the generator that reads it, ``generators/<generator>.py``;
- ``metrics/<metric name>.py``: the reader of one per-layer metric, a
  ``read(readings)`` function returning a number or None. A metric
  split by cell (``device_idle.search``) falls back to the reader of
  its stem (``metrics/device_idle.py``), so cells share one reader.

A new cell, configuration, mix or metric is new files and new entries;
no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def repo_root(bench_dir: str = BENCH_DIR) -> str:
    """The checkout that holds ``BENCHMARK.json`` (two levels up)."""
    return os.path.dirname(os.path.dirname(bench_dir))


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Optional[str] = None) -> Dict:
    return load_json(os.path.join(root or repo_root(), "BENCHMARK.json"))


def _by_name(entries, name: str, what: str) -> Dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(bench: Dict, name: str) -> Dict:
    """The ``workloads`` entry of a cell."""
    return _by_name(bench["workloads"], name, "workload")


def config(bench: Dict, name: str, root: Optional[str] = None) -> Dict:
    """The configuration file of ``configs`` entry ``name``."""
    entry = _by_name(bench["configs"], name, "configuration")
    return load_json(os.path.join(root or repo_root(), entry["file"]))


def traffic(name: str, bench_dir: str = BENCH_DIR) -> Dict:
    return load_json(os.path.join(bench_dir, "traffic", f"{name}.json"))


def _load_module(path: str, modname: str):
    if not os.path.isfile(path):
        raise KeyError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(name: str, bench_dir: str = BENCH_DIR):
    """The traffic generator module ``generators/<name>.py``: it has
    ``run(ctx) -> dict``."""
    path = os.path.join(bench_dir, "generators", f"{name}.py")
    return _load_module(path, f"canalbench_generator_{name}")


def metric_reader(name: str, bench_dir: str = BENCH_DIR
                  ) -> Callable[[Dict], Optional[float]]:
    """``read`` of ``metrics/<name>.py``, else of ``metrics/<stem>.py``
    where ``stem`` is the name up to its first ``.``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        path = os.path.join(bench_dir, "metrics",
                            name.split(".", 1)[0] + ".py")
    mod = _load_module(path, "canalbench_metric_" + name.replace(".", "_"))
    return mod.read


def metrics_of_cell(bench: Dict, cell_name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
    that list the cell under ``workloads``, or that list no cells."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]
