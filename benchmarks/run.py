"""Benchmark harness — one module per paper table/figure.

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig08]
        [--store PATH | --no-store]

Prints ``name,us_per_call,derived`` CSV per benchmark and saves JSON
records under benchmarks/results/ (consumed by EXPERIMENTS.md). Sweep
benchmarks run store-backed: design-point records persist in the
spec-addressed result store (``--store``, default ``.canal_store`` /
``$CANAL_RESULT_STORE``), so an incremental re-run only recomputes
design points whose spec digest is new — everything else is served from
disk. ``--no-store`` forces every point cold.

The digest addresses the *design point*, not the producing code: stored
records survive source edits, so after changing the router/emulator run
with ``--no-store`` (or delete the store root) to re-measure — CI gets
this for free by salting its store cache key with ``src/**``.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced app/track sets")
    ap.add_argument("--only", type=str, default=None,
                    help="substring filter on benchmark module name")
    ap.add_argument("--store", type=str, default=None,
                    help="result-store root (default $CANAL_RESULT_STORE "
                         "or .canal_store)")
    ap.add_argument("--no-store", action="store_true",
                    help="run every design point cold (no persistence)")
    args = ap.parse_args()

    from repro.core import compile_cache
    compile_cache.enable(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    # the sweep executors attach the store via the env default; setting it
    # here makes every figure benchmark store-backed without threading a
    # store object through each module
    from repro.core.store import STORE_ENV, default_store_root
    if args.no_store:
        os.environ.pop(STORE_ENV, None)
    else:
        os.environ[STORE_ENV] = args.store or default_store_root()
        # per-record PnR timings (gen_pnr_seconds) always reflect the
        # original cold computation; only the module-level wall clocks
        # shrink on a warm store
        print(f"# result store: {os.environ[STORE_ENV]} (warm sweeps "
              "measure serve latency; records survive source edits — "
              "--no-store after changing the engines)", flush=True)

    from . import (dse_speed, fig08_fifo_area, fig09_topology_routability,
                   fig10_track_area, fig11_track_runtime, fig13_port_area,
                   fig14_15_port_runtime, pnr_speed)
    try:
        from . import kernels_bench
    except Exception:                                  # pragma: no cover
        kernels_bench = None
    try:
        from . import roofline_table
    except Exception:                                  # pragma: no cover
        roofline_table = None

    mods = [fig08_fifo_area, fig10_track_area, fig13_port_area, dse_speed,
            pnr_speed, fig09_topology_routability, fig11_track_runtime,
            fig14_15_port_runtime]
    if kernels_bench is not None:
        mods.append(kernels_bench)
    if roofline_table is not None:
        mods.append(roofline_table)

    print("name,us_per_call,derived")
    failures = []
    for mod in mods:
        name = mod.__name__.split(".")[-1]
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        try:
            mod.run(quick=args.quick)
            print(f"# {name}: ok in {time.perf_counter() - t0:.1f}s",
                  flush=True)
        except Exception as e:                        # pragma: no cover
            failures.append(name)
            traceback.print_exc()
            print(f"# {name}: FAILED ({e})", flush=True)
    if failures:
        sys.exit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()
