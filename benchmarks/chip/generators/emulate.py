"""Routed applications emulated on one design point, closed loop.

Reads a traffic file with:

- ``pnr_seed``: the PnR seed of the one design point (the
  configuration's ``spec``);
- ``cycles``: T, the cycles of every emulated batch.

Set-up: the executor is asked for a stimulus-driven emulation of no
apps, so a program that cannot take a stimulus fails here, in seconds.
Then the configuration's spec is compiled, and every app is placed and
routed once through ``SweepExecutor.run_point`` (``emulate_cycles=0``),
or loaded from ``cache/`` where a run in the same checkout left it,
keyed by a hash of ``src/``, the configuration and the seed. One batch
warms the emulation program.

Window: one batch in flight. A batch is ``SweepExecutor.emulate_routed``
of every routed app together (B = the apps, T = ``cycles``) with fresh
random 16-bit words on every app input, drawn from ``--seed``. A
"point" is one batch: ``points_per_s`` counts batches completed in the
window, ``point_p90_s`` is the 90th percentile from the call to outputs
on the host.

``correct``: ``check_pnr`` rows for every routed app, and
``check_emulate``'s ``wrong_words`` (output words of the window's
batches that differ from the apps' semantics, limit 0),
``apps_missing`` (limit 0) and ``unbalanced_paths`` (route registers
that change an app's function, limit 0).

The per-layer readings come from the program's own spans
(``repro.core.trace``), recorded over the window: ``emulate.bind``,
``emulate.run`` and its ``sweeps``.
"""
from __future__ import annotations

import contextlib
import copy
import hashlib
import json
import os
import pickle
import time

import numpy as np

from canalbench import check_emulate, check_pnr, design, netlist
from canalbench.cell import checks

LIMITS = {"bad_packing": 0, "bad_placements": 0, "bad_routes": 0,
          "overused_nodes": 0, "wirelength_gap": 0,
          "critical_path_gap": 1e-9, "wrong_words": 0, "apps_missing": 0,
          "unbalanced_paths": 0}


def cache_key(root: str, config, seed: int) -> str:
    """Hash of every file under ``src/``, the configuration and the
    PnR seed: a routed design is reused only by the code that made it."""
    h = hashlib.sha256()
    src = os.path.join(root, "src")
    for d, dirs, files in os.walk(src):
        dirs[:] = sorted(x for x in dirs if x != "__pycache__")
        for f in sorted(files):
            if f.endswith(".pyc"):
                continue
            path = os.path.join(d, f)
            h.update(os.path.relpath(path, src).encode())
            with open(path, "rb") as fh:
                h.update(fh.read())
    h.update(json.dumps(config, sort_keys=True).encode())
    h.update(str(seed).encode())
    return h.hexdigest()[:24]


def route_point(rec, ex, spec):
    """Every app of ``spec`` through ``run_point``: ``{app: (record
    entry, PnRResult or None)}``."""
    import repro.core.dse as dse

    results = {}
    inner = dse.place_and_route

    def kept(ic, app, *args, **kwargs):
        r = inner(ic, app, *args, **kwargs)
        results[app.bench_app] = r
        return r

    rec.patch(dse, "place_and_route", kept)
    try:
        record = ex.run_point(spec)
    finally:
        rec.restore()
    return {name: (entry, results.get(name) if entry["success"] else None)
            for name, entry in record["apps"].items()}


def routed_designs(ctx, ex, spec):
    """The design point's routed apps, from ``cache/`` when there."""
    from repro.core.pnr import RoutingResources

    seed = int(ctx.traffic["pnr_seed"])
    cache = os.path.join(ctx.bench_dir, "cache")
    path = os.path.join(
        cache, f"emulate-{cache_key(ctx.repo_root, ctx.config, seed)}.pkl")
    ic = ex.interconnect(spec)
    if os.path.isfile(path):
        with open(path, "rb") as f:
            designs = pickle.load(f)
        res = RoutingResources(ic)
        for _, r in designs.values():
            if r is not None:
                r.routing.resources = res
        return ic, designs
    designs = route_point(ctx.rec, ex, spec)
    kept = {}
    for name, (entry, r) in designs.items():
        if r is not None:
            r = copy.copy(r)
            r.routing = copy.copy(r.routing)
            r.routing.resources = None
        kept[name] = (entry, r)
    os.makedirs(cache, exist_ok=True)
    tmp = f"{path}.{os.getpid()}"
    with open(tmp, "wb") as f:
        pickle.dump(kept, f)
    os.replace(tmp, path)
    return ic, designs


def program_readings(prog, t0: float, t1: float):
    """Seconds in ``emulate.bind`` and ``emulate.run`` and the sweeps of
    ``emulate.run`` over the window, or None where no span was
    recorded."""
    spans = [s for s in prog.spans if t0 <= s.t0 and s.t1 <= t1]

    def total(name, of=lambda s: s.t1 - s.t0):
        found = [of(s) for s in spans if s.name == name]
        return sum(found) if found else None

    return {"emulate_bind_s": total("emulate.bind"),
            "emulate_run_s": total("emulate.run"),
            "sweeps": total("emulate.run", lambda s: s.attrs["sweeps"])}


def run(ctx):
    from repro.core import trace
    from repro.core.dse import SweepExecutor
    from repro.core.lowering import compile_interconnect
    from repro.core.spec import InterconnectSpec

    cfg, tr, rec = ctx.config, ctx.traffic, ctx.rec
    cycles = int(tr["cycles"])
    rec.count_compiles()
    ex = SweepExecutor(apps=netlist.builders(cfg), emulate_cycles=0,
                       shard=False, store=False)
    # a program without the stimulus signature stops here
    ex.emulate_routed(None, [], stimulus={"probe": {"in0": np.zeros(1)}})
    spec = ex.resolve(InterconnectSpec(**cfg["spec"]).replace(
        seed=int(tr["pnr_seed"])))
    ic, designs = routed_designs(ctx, ex, spec)
    fab = compile_interconnect(ic)
    routed = [(name, r.packed, r) for name, (_, r) in designs.items()
              if r is not None]
    inputs = {name: [n for n, kind, _, _ in cfg["apps"][name]["instances"]
                     if kind == "io_in"] for name, _, _ in routed}
    outputs = {name: {n: r.placement[n]
                      for n, kind, _, _ in cfg["apps"][name]["instances"]
                      if kind == "io_out"} for name, _, r in routed}
    rng = np.random.default_rng(np.random.SeedSequence([ctx.seed]))

    def draw():
        return {name: {n: rng.integers(0, 1 << 16, cycles, dtype=np.int32)
                       for n in names} for name, names in inputs.items()}

    def batch(stimulus):
        outs = ex.emulate_routed(fab, routed, stimulus=stimulus)
        return {name: {n: outs[name][1][xy] for n, xy in where.items()}
                for name, where in outputs.items()}

    batch(draw())                                # warm the program

    active = trace.active()
    batches, latencies = [], []
    with (contextlib.nullcontext(active) if active is not None
          else trace.recording()) as prog:
        t0 = time.perf_counter()
        t_end = t0 + ctx.seconds
        with ctx.window():
            while True:
                stimulus = draw()
                t = time.perf_counter()
                got = batch(stimulus)
                latencies.append(time.perf_counter() - t)
                batches.append((stimulus, got))
                if time.perf_counter() >= t_end:
                    break
        t_last = time.perf_counter()
    readings = program_readings(prog, t0, t_last)

    def check():
        g = design.graph(ic)
        sd = {"width": spec.width, "height": spec.height,
              "mem_columns": list(spec.mem_columns),
              "io_ring": spec.io_ring, "track_width": spec.track_width}
        rows, delays = [], {}
        for name, (entry, r) in designs.items():
            if r is None:
                continue
            d = design.app_result(r)
            app = cfg["apps"][name]
            routes = design.routes_of(d)
            rows.append(check_pnr.check_app(
                g, sd, app, d["placement"], routes, d["const_ports"],
                d["reg_ports"], entry))
            try:
                delays[name] = check_emulate.connection_delays(
                    g, sd, app, d["placement"], routes)
            except (KeyError, ValueError):
                pass                 # no sound route: the app is missing
        out = check_pnr.worst(rows)
        out.update(check_emulate.compare(cfg["apps"], delays, batches,
                                         cycles))
        out["unbalanced_paths"] = sum(
            check_emulate.unbalanced_paths(cfg["apps"][name], d)
            for name, d in delays.items())
        return out

    return {
        "t_window_start": t0,
        "attempted": len(batches),
        "failed": 0,
        "e2e": {"points_per_s": len(batches) / (t_last - t0),
                "point_p90_s": float(np.percentile(latencies, 90))},
        "readings": dict(readings, points=len(batches),
                         compiles=rec.compiles_between(t0, t_last)),
        "check": lambda: checks(check(), LIMITS),
    }
