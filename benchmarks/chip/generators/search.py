"""Cold design points through the serving path, closed loop.

Reads a traffic file with:

- ``in_flight``: points kept in flight (a closed loop: the next point is
  sent when one returns, and none once the window has closed; points in
  flight then finish and count);
- ``block_axes`` and ``rotate_axes``: grid axes of the configuration's
  ``grid``. The points are the whole grid, each once, in blocks: a
  block holds every combination of ``block_axes``, each with one
  combination of ``rotate_axes``, rotated from block to block so the
  blocks together cover the grid. ``--seed`` orders the blocks and the
  points inside each, so every seed sends the same mix of sizes;
- ``warm_seed``: the PnR seed of the throwaway point of set-up.

Each point is ``DSEService.submit`` of one spec (``emulate_cycles=0``,
so PnR only) over a fresh, empty result store; its PnR seed comes from
``--seed`` and the point's index. ``correct`` checks every routed app
of every point against the configuration's own netlist with
``canalbench.check_pnr`` (packing, placement, routes, wirelength and
critical path), and holds the apps that the records call unroutable,
summed over the window, to a limit.
"""
from __future__ import annotations

import itertools
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import FIRST_COMPLETED, wait

import numpy as np

from canalbench import check_pnr, design, netlist
from canalbench.cell import checks

#: limits of the numbers compared (PERF.md gives the readings they sit
#: between); every count but ``unrouted_apps`` is exact
LIMITS = {"wrong_records": 0, "apps_missing": 0, "bad_packing": 0,
          "bad_placements": 0, "bad_routes": 0, "overused_nodes": 0,
          "wirelength_gap": 0, "critical_path_gap": 1e-9,
          "unrouted_apps": 1}


def point_seed(seed: int, index: int) -> int:
    return int(np.random.SeedSequence([seed, index]).generate_state(1)[0]
               % (1 << 31))


def plan(config, traffic, seed: int):
    """The grid as a list of field overrides, in the seeded block
    order (see the module docstring)."""
    grid = config["grid"]
    cells = list(itertools.product(*(grid[a] for a in traffic["block_axes"])))
    combos = list(itertools.product(
        *(grid[a] for a in traffic["rotate_axes"])))
    rng = np.random.default_rng(np.random.SeedSequence([seed]))
    points = []
    for j in rng.permutation(len(combos)):
        block = []
        for c, cell in enumerate(cells):
            combo = combos[(c + int(j)) % len(combos)]
            block.append({**dict(zip(traffic["block_axes"], cell)),
                          **dict(zip(traffic["rotate_axes"], combo))})
        points.extend(block[i] for i in rng.permutation(len(block)))
    return points


def instrument(rec, svc, captures, starts):
    """Spans around the program's layers for the life of the run:
    ``point`` (and the thread's tag) around each executor point,
    ``hwgen`` around the pass pipeline and static analysis, ``place``
    around packing and placement, ``route`` around routing and its
    resources, ``sta`` around timing. Every PnR result is kept, with
    the interconnect it was routed on, for the checks."""
    import repro.core.analysis as analysis
    import repro.core.dse as dse
    import repro.core.passes as passes
    import repro.core.pnr.driver as pnr

    ex = svc.executor
    run_point = ex.run_point

    def spanned_point(point, *args, **kwargs):
        digest = ex.resolve(point).digest()
        starts[digest] = time.perf_counter()
        rec.tag = digest
        try:
            with rec.span("point"):
                return run_point(point, *args, **kwargs)
        finally:
            rec.tag = None

    rec.patch(ex, "run_point", spanned_point)
    rec.wrap(ex, "resources", "route")
    rec.wrap(passes.PassManager, "run", "hwgen")
    rec.wrap(analysis, "analyze", "hwgen")
    for name in ("pack", "assign_ios", "global_place", "legalize",
                 "detailed_place"):
        rec.wrap(pnr, name, "place")
    rec.wrap(pnr, "route_app", "route")
    rec.wrap(pnr, "sta_critical_path", "sta")
    place_and_route = dse.place_and_route

    def kept(ic, app, *args, **kwargs):
        r = place_and_route(ic, app, *args, **kwargs)
        captures.setdefault(rec.tag, {})[app.bench_app] = (ic, r)
        return r

    rec.patch(dse, "place_and_route", kept)


def warm_minplus(n_tiles: int) -> None:
    """Compile the router's cost-field program at every seed bucket it
    can use on this fabric (powers of two up to the tile count)."""
    import jax.numpy as jnp
    from repro.kernels import ops

    w = jnp.zeros((n_tiles, n_tiles), jnp.float32)
    bucket = 1
    while True:
        ops.minplus_wavefront(jnp.zeros((bucket, n_tiles), jnp.float32),
                              w).block_until_ready()
        if bucket >= n_tiles:
            break
        bucket *= 2


def check(config, results, captures):
    """Counts over every point of the window (``check_pnr``), plus the
    apps that a point's record says it could not route."""
    rows = []
    wrong = missing = unrouted = 0
    graphs = {}
    for digest, spec, record in results:
        if record.get("spec_digest") != digest:
            wrong += 1
            continue
        sd = {"width": spec.width, "height": spec.height,
              "mem_columns": list(spec.mem_columns),
              "io_ring": spec.io_ring, "track_width": spec.track_width}
        for app, data in config["apps"].items():
            entry = record.get("apps", {}).get(app)
            if entry is None:
                missing += 1
                continue
            if not entry["success"]:
                unrouted += 1
                continue
            got = captures.get(digest, {}).get(app)
            if got is None:
                missing += 1
                continue
            ic, r = got
            if id(ic) not in graphs:
                graphs[id(ic)] = design.graph(ic)
            d = design.app_result(r)
            rows.append(check_pnr.check_app(
                graphs[id(ic)], sd, data, d["placement"],
                design.routes_of(d), d["const_ports"], d["reg_ports"],
                entry))
    out = check_pnr.worst(rows)
    out.update(wrong_records=wrong, apps_missing=missing,
               unrouted_apps=unrouted)
    return out


def run(ctx):
    import canal
    from repro.core.spec import InterconnectSpec

    cfg, tr, rec = ctx.config, ctx.traffic, ctx.rec
    apps = netlist.builders(cfg)
    base = InterconnectSpec(**cfg["spec"])
    overrides = plan(cfg, tr, ctx.seed)
    specs = [base.replace(**o, seed=point_seed(ctx.seed, i))
             for i, o in enumerate(overrides)]
    rec.count_compiles()
    captures, starts = {}, {}
    stores = [tempfile.mkdtemp(prefix="canalbench-store-")
              for _ in range(2)]
    try:
        # set-up: one throwaway point on a throwaway store warms JAX
        with canal.serve(store=stores[0], apps=apps,
                         emulate_cycles=0) as svc:
            instrument(rec, svc, {}, {})
            svc.query(base.replace(seed=int(tr["warm_seed"])))
            rec.restore()
        warm_minplus(base.width * base.height)

        done_at = {}
        lock = threading.Lock()
        with canal.serve(store=stores[1], apps=apps,
                         emulate_cycles=0) as svc:
            instrument(rec, svc, captures, starts)
            ex = svc.executor
            in_flight, submitted, results = {}, [], []
            submit_t = {}

            def stamp(fut):
                with lock:
                    done_at[fut] = time.perf_counter()

            def submit():
                i = len(submitted)
                spec = specs[i]
                digest = ex.resolve(spec).digest()
                t = time.perf_counter()
                fut = svc.submit(spec)
                fut.add_done_callback(stamp)
                in_flight[fut] = (digest, spec, t)
                submitted.append(digest)
                submit_t[digest] = t

            t0 = time.perf_counter()
            t_end = t0 + ctx.seconds
            latencies = []
            with ctx.window():
                for _ in range(min(int(tr["in_flight"]), len(specs))):
                    submit()
                while in_flight:
                    done, _ = wait(list(in_flight),
                                   return_when=FIRST_COMPLETED)
                    for fut in done:
                        digest, spec, t_sub = in_flight.pop(fut)
                        # waiters wake before done-callbacks run
                        while fut not in done_at:
                            time.sleep(1e-4)
                        with lock:
                            latencies.append(done_at[fut] - t_sub)
                        try:
                            record = fut.result()
                        except Exception as e:  # a failed query counts
                            print(f"search: point {digest} failed: {e!r}",
                                  file=sys.stderr)
                            record = None
                        if record is not None:
                            results.append((digest, spec, record))
                        if (time.perf_counter() < t_end
                                and len(submitted) < len(specs)):
                            submit()
            t_last = max(done_at.values())
            rec.restore()
    finally:
        for d in stores:
            shutil.rmtree(d, ignore_errors=True)
    t_window = (t0, t_last)
    tags = {d for d, _, _ in results}
    return {
        "t_window_start": t0,
        "attempted": len(submitted),
        "failed": len(submitted) - len(results),
        "e2e": {"points_per_s": len(results) / (t_last - t0),
                "point_p90_s": float(np.percentile(latencies, 90))},
        "readings": {
            "points": len(results),
            "serve_wait_s": [starts[d] - submit_t[d] for d in tags
                             if d in starts],
            "hwgen_s": rec.per_tag("hwgen", tags),
            "place_s": rec.per_tag("place", tags),
            "route_s": rec.per_tag("route", tags),
            "compiles": rec.compiles_between(*t_window),
        },
        "check": lambda: checks(check(cfg, results, captures), LIMITS),
    }
