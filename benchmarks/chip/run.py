#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip it finds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell (``workloads`` of ``BENCHMARK.json``) names a configuration
and a traffic mix; the mix names its generator
(``generators/<name>.py``).
The run names its device and stops, with no result, unless JAX's
devices are TPUs of a kind in the peaks table, as many as the cell
asks for. It sets up, measures for ``--seconds``, then checks what the
window produced against the benchmark's own reference.

Standard output ends with one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
with ``--trace 1`` a ``breakdown`` of device time, and last
``checks``: every number compared with its limit. The checks are also
the last lines of standard error. Everything runs in this one process:
a child could not reach a chip this process holds.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from canalbench import registry  # noqa: E402
from canalbench.cell import Ctx, passed  # noqa: E402
from canalbench.device import (DeviceError, memory_peak_bytes,  # noqa: E402
                               require)


def enable_compile_cache(root: str) -> str:
    """JAX's persistent cache at the program's fixed place in the
    checkout (``$JAX_COMPILATION_CACHE_DIR`` when set), keeping every
    program however fast it compiled."""
    sys.path.insert(0, os.path.join(root, "src"))
    from repro.core import compile_cache

    import jax
    path = compile_cache.enable(root)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def per_layer(bench, cell_name, ctx, out):
    readings = dict(out["readings"], trace=ctx.trace_summary)
    metrics = {}
    for m in registry.metrics_of_cell(bench, cell_name, "per_layer"):
        value = registry.metric_reader(m["name"])(readings)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def measure(args, root: str = None) -> dict:
    """Set up, measure and check one run; returns the result line."""
    root = root or registry.repo_root()
    bench = registry.load_benchmark(root)
    cell = registry.cell(bench, args.workload)
    config = registry.config(bench, cell["config"], root)
    traffic = registry.traffic(cell["traffic"])
    gen = registry.generator(traffic["generator"])

    import jax
    devices = jax.devices()
    info = require(devices, cell["chips"])
    cache = enable_compile_cache(root)
    print(f"device platform={info['platform']} kind={info['kind']} "
          f"count={info['count']} jax={jax.__version__} "
          f"compile_cache={cache}", file=sys.stderr, flush=True)
    ctx = Ctx(cell=cell, config=config, traffic=traffic, seed=args.seed,
              seconds=args.seconds, trace=bool(args.trace),
              bench_dir=BENCH_DIR, repo_root=root)
    try:
        out = gen.run(ctx)
    finally:
        ctx.rec.close()
    info["memory_peak_bytes"] = memory_peak_bytes(devices)
    if ctx.trace:
        metrics = per_layer(bench, cell["name"], ctx, out)
        t = ctx.trace_summary
        info["busy_s"] = t["busy_s"]
        info["window_s"] = t["window_s"]
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        values = dict(out["e2e"], setup_s=out["t_window_start"] - T_PROCESS)
        metrics = {name: {"value": values[name], "unit": units[name]}
                   for name in (m["name"] for m in registry.metrics_of_cell(
                       bench, cell["name"], "end_to_end"))}
    table = out["check"]()
    result = {"correct": passed(table) and out["failed"] == 0,
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": info}
    if ctx.trace:
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    result["checks"] = table
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    try:
        result = measure(args)
    except DeviceError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    for name, row in result["checks"].items():
        print(f"check {name}: {row['value']} (limit {row['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
