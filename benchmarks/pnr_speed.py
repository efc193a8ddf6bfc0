"""PnR speed: the device-accelerated PathFinder vs the Python A* oracle.

Three measurements, saved as ``benchmarks/results/pnr_speed.json``:

* ``routing`` — routed nets/sec on a shared placement of the benchmark
  apps over a >=8x8 mesh with >=5 tracks: ``strategy="python"``
  (Manhattan-bounded A*) vs ``strategy="minplus"`` (batched tropical
  Bellman-Ford coarse cost fields as A* lower bounds). Both run on the
  same cached ``RoutingResources``; the headline number is the speedup
  of the tile-coarsened batched path (acceptance: >=2x).
* ``placement`` — annealing steps/sec at an equal step budget:
  ``strategy="python"`` (host SA, one chain, one device round-trip per
  step) vs ``strategy="batched"`` (K parallel-tempering chains as one
  jitted ``lax.scan``). Same chain/batch population; also records the
  final Eq. 2 cost ratio (acceptance: >=3x faster, ratio <= 1).
* ``sweep`` — end-to-end ``SweepExecutor`` wall time for a small track
  sweep (PnR + batched emulation) per strategy, with the async
  PnR/emulation pipeline on, so router gains survive to the sweep level.
"""
from __future__ import annotations

import time
from typing import Dict, List

from .common import emit, save_json


def _route_workload(width: int, height: int, num_tracks: int,
                    app_names: List[str]):
    """Shared fixture: interconnect, resources, and packed+placed apps
    (placement runs once — the benchmark times *routing* only)."""
    from repro.core.passes import PassManager
    from repro.core.pnr.app import BENCH_APPS
    from repro.core.pnr.detailed_place import detailed_place
    from repro.core.pnr.global_place import assign_ios, global_place, legalize
    from repro.core.pnr.packing import pack
    from repro.core.pnr.route import RoutingResources
    from repro.core.spec import InterconnectSpec, SwitchBoxType

    ic = PassManager().run(InterconnectSpec(
        width=width, height=height, num_tracks=num_tracks, io_ring=True,
        sb_type=SwitchBoxType.WILTON, reg_density=1.0))
    res = RoutingResources(ic)
    placed = []
    for name in app_names:
        packed = pack(BENCH_APPS[name]())
        fixed = assign_ios(packed, width, height)
        cont = global_place(packed, width, height, fixed=fixed, seed=0)
        base = legalize(packed, cont, width, height, io_ring=True,
                        fixed=fixed)
        pl = detailed_place(packed, base, width, height, io_ring=True,
                            gamma=0.3, alpha=2.0, n_steps=40, batch=8,
                            seed=0)
        placed.append((name, packed, pl))
    return ic, res, placed


def _route_all(ic, res, placed, strategy: str) -> int:
    from repro.core.pnr.route import route_app

    nets = 0
    for _, packed, pl in placed:
        result = route_app(ic, packed, pl, res=res, strategy=strategy)
        nets += len(result.nets)
    return nets


def routing_speed(width: int = 8, height: int = 8, num_tracks: int = 5,
                  repeats: int = 3) -> Dict:
    """python-A* vs minplus-batched routed nets/sec (shared placement,
    shared resources, best-of-N wall clocks)."""
    apps = ["pointwise", "tree_reduce", "fir", "butterfly"]
    ic, res, placed = _route_workload(width, height, num_tracks, apps)
    rec: Dict = {"width": width, "height": height,
                 "num_tracks": num_tracks, "apps": apps,
                 "nodes": len(res.nodes)}
    for strategy in ("python", "minplus"):
        nets = _route_all(ic, res, placed, strategy)   # warm (jit, fields)
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            nets = _route_all(ic, res, placed, strategy)
            best = min(best, time.perf_counter() - t0)
        rec[strategy] = {"nets": nets, "seconds": best,
                         "nets_per_sec": nets / max(best, 1e-9)}
    rec["speedup"] = (rec["minplus"]["nets_per_sec"]
                      / max(rec["python"]["nets_per_sec"], 1e-9))
    return rec


def place_speed(width: int = 8, height: int = 8,
                quick: bool = False) -> Dict:
    """Host SA vs device-resident parallel-tempering chains: annealing
    steps/sec at an equal step budget and chain population, plus the
    final Eq. 2 cost ratio (batched / host, lower is better)."""
    from repro.core.pnr.app import BENCH_APPS
    from repro.core.pnr.batched_anneal import batched_place, eq2_cost
    from repro.core.pnr.detailed_place import detailed_place
    from repro.core.pnr.global_place import assign_ios, global_place, legalize
    from repro.core.pnr.packing import pack

    app_name = "butterfly"
    steps = 60 if quick else 120
    chains = 16
    packed = pack(BENCH_APPS[app_name]())
    fixed = assign_ios(packed, width, height)
    cont = global_place(packed, width, height, fixed=fixed, seed=0)
    base = legalize(packed, cont, width, height, io_ring=True, fixed=fixed)

    # warm both engines so neither pays jit compilation in the timed run
    batched_place(packed, base, width, height, io_ring=True,
                  n_steps=steps, n_chains=chains, seed=0)
    detailed_place(packed, base, width, height, io_ring=True, n_steps=2,
                   batch=chains, seed=0)

    t0 = time.perf_counter()
    pl_b, cost_b = batched_place(packed, base, width, height,
                                 io_ring=True, n_steps=steps,
                                 n_chains=chains, seed=0,
                                 return_cost=True)
    t_batched = time.perf_counter() - t0

    t0 = time.perf_counter()
    pl_h = detailed_place(packed, base, width, height, io_ring=True,
                          n_steps=steps, batch=chains, seed=0)
    t_host = time.perf_counter() - t0
    cost_h = float(eq2_cost(packed, pl_h, width, height))

    return {"width": width, "height": height, "app": app_name,
            "steps": steps, "chains": chains,
            "python": {"seconds": t_host,
                       "steps_per_sec": steps / max(t_host, 1e-9),
                       "final_cost": cost_h},
            "batched": {"seconds": t_batched,
                        "steps_per_sec": steps / max(t_batched, 1e-9),
                        "final_cost": float(cost_b)},
            "speedup": t_host / max(t_batched, 1e-9),
            "cost_ratio": float(cost_b) / max(cost_h, 1e-9)}


def sweep_speed(quick: bool = False) -> Dict:
    """End-to-end SweepExecutor wall time per router strategy (async
    emulation pipeline on): the router win at the DSE-sweep level."""
    from repro.core.dse import SweepExecutor
    from repro.core.pnr.app import BENCH_APPS
    from repro.core.spec import InterconnectSpec, spec_grid

    apps = {k: BENCH_APPS[k] for k in
            (("fir",) if quick else ("fir", "tree_reduce"))}
    tracks = (5,) if quick else (4, 5)
    # the annealing budget lives on the spec now (folded PnR knobs): the
    # design point fully describes how it is placed and routed
    base = InterconnectSpec(width=8, height=8, io_ring=True,
                            reg_density=1.0, sa_steps=30, sa_batch=8)
    points = spec_grid(base, {"num_tracks": tracks})
    rec: Dict = {"tracks": list(tracks), "apps": list(apps)}
    for strategy in ("python", "minplus"):
        # store=False: this benchmark times the router — serving records
        # from a warm store would measure the cache, not the engine
        ex = SweepExecutor(apps=apps, emulate_cycles=8,
                           route_strategy=strategy, max_workers=2,
                           store=False)
        t0 = time.perf_counter()
        recs = ex.run_points(points)
        rec[strategy] = {"seconds": time.perf_counter() - t0,
                         "n_routed": sum(
                             1 for r in recs for a in r["apps"].values()
                             if a["success"])}
    rec["speedup"] = (rec["python"]["seconds"]
                      / max(rec["minplus"]["seconds"], 1e-9))
    return rec


def run(quick: bool = False):
    lines = []
    route_rec = routing_speed(repeats=2 if quick else 3)
    lines.append(emit(
        f"pnr_speed/route_{route_rec['width']}x{route_rec['height']}"
        f"_t{route_rec['num_tracks']}",
        route_rec["minplus"]["seconds"] * 1e6,
        f"python={route_rec['python']['nets_per_sec']:.1f}n/s "
        f"minplus={route_rec['minplus']['nets_per_sec']:.1f}n/s "
        f"speedup={route_rec['speedup']:.2f}x"))
    # the acceptance margin (>=2x) holds with ~2x headroom on a warm run;
    # assert a floor low enough to only flag real regressions on noisy
    # shared runners
    assert route_rec["speedup"] >= 1.2, \
        "batched min-plus router must beat the Python A* baseline"

    place_rec = place_speed(quick=quick)
    lines.append(emit(
        f"pnr_speed/place_{place_rec['width']}x{place_rec['height']}"
        f"_k{place_rec['chains']}",
        place_rec["batched"]["seconds"] * 1e6,
        f"python={place_rec['python']['steps_per_sec']:.0f}st/s "
        f"batched={place_rec['batched']['steps_per_sec']:.0f}st/s "
        f"speedup={place_rec['speedup']:.1f}x "
        f"cost_ratio={place_rec['cost_ratio']:.3f}"))
    # acceptance is >=3x with equal-or-better final cost; the asserted
    # floors leave noise headroom on shared runners
    assert place_rec["speedup"] >= 1.5, \
        "batched annealing chains must beat the host SA loop"
    assert place_rec["cost_ratio"] <= 1.05, \
        "batched annealing must not regress final Eq. 2 cost"

    sweep_rec = sweep_speed(quick=quick)
    lines.append(emit(
        "pnr_speed/sweep_8x8",
        sweep_rec["minplus"]["seconds"] * 1e6,
        f"python={sweep_rec['python']['seconds']:.2f}s "
        f"minplus={sweep_rec['minplus']['seconds']:.2f}s "
        f"speedup={sweep_rec['speedup']:.2f}x"))
    save_json("pnr_speed", {"routing": route_rec, "placement": place_rec,
                             "sweep": sweep_rec})
    return lines
