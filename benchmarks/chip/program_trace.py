#!/usr/bin/env python3
"""Run one cell as ``run.py`` does, with the program's own spans on.

    python3 benchmarks/chip/program_trace.py --workload <cell> \\
        --seed <n> --seconds <s> --trace <0|1> --record <0|1>

``--record 1`` keeps a ``repro.core.trace`` recording on for the whole
run; ``--record 0`` is ``run.py`` itself. The same seeds with both
values measure what the recording costs the end-to-end metrics
(``--trace 0``). With ``--record 1`` the result line gains
``program``: per completed point of the window, the JAX trace, lower
and compile-or-load seconds under the point's spans (``jit_s``), the
thread CPU seconds of the ``place.*`` and ``route.*`` spans
(``place_cpu_s``, ``route_cpu_s``) and the wall seconds of the
``device.wait`` spans (``device_wait_s``), and ``spans``, each span
name's figures per point. With ``--trace 1`` as well, standard error
gets the window's device-idle gaps named by the innermost ``canal:``
span (``canalbench.stage_gaps``), and the line gains ``stage_gaps``.
The cell's checks decide ``correct`` as in ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import run as bench  # noqa: E402
from canalbench import registry, stage_gaps, tracing  # noqa: E402
from canalbench.device import DeviceError  # noqa: E402

sys.path.insert(0, os.path.join(registry.repo_root(), "src"))
from repro.core import trace  # noqa: E402


def per_point(rows_by_tag, points: int) -> dict:
    """The four per-point figures from ``Recording.per_tag``."""
    def total(field, keep):
        return sum(row[field] for rows in rows_by_tag.values()
                   for name, row in rows.items() if keep(name)) / points

    return {
        "jit_s": total("jit_s", lambda n: True),
        "place_cpu_s": total("cpu_s", lambda n: n.startswith("place.")),
        "route_cpu_s": total("cpu_s", lambda n: n.startswith("route.")),
        "device_wait_s": total("wall_s", lambda n: n == "device.wait"),
    }


def measure(args) -> dict:
    """``run.measure`` with the window's readings and, when tracing,
    the stage gaps kept."""
    kept = {}
    load_generator = registry.generator
    reduce_dir = tracing.reduce_dir

    def generator(name, *a, **kw):
        gen = load_generator(name, *a, **kw)
        run = gen.run

        def keeping(ctx):
            out = run(ctx)
            kept["readings"] = out["readings"]
            return out

        gen.run = keeping
        return gen

    def reducing(trace_dir, chips):
        kept["stage_gaps"] = stage_gaps.reduce_dir(trace_dir, chips)
        return reduce_dir(trace_dir, chips)

    registry.generator = generator
    tracing.reduce_dir = reducing
    try:
        if not args.record:
            return bench.measure(args)
        with trace.recording() as rec:
            result = bench.measure(args)
    finally:
        registry.generator = load_generator
        tracing.reduce_dir = reduce_dir
    readings = kept["readings"]
    points = readings["points"]
    tags = set(readings["place_s"])
    rows = rec.per_tag(tags)
    if points:
        result["program"] = per_point(rows, points)
        result["spans"] = {
            name: {k: v / points for k, v in row.items()}
            for name, row in rec.summary(tags).items()}
    if "stage_gaps" in kept:
        result["stage_gaps"] = kept["stage_gaps"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--record", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    try:
        result = measure(args)
    except DeviceError as e:
        print(f"program_trace.py: {e}", file=sys.stderr)
        return 2
    if "stage_gaps" in result:
        print(stage_gaps.table(result["stage_gaps"]), file=sys.stderr)
    for name, row in result["checks"].items():
        print(f"check {name}: {row['value']} (limit {row['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
