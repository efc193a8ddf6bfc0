"""Abstract claim — "Canal enables fast design space exploration": IR
generation + hardware lowering speed vs array size, the spec-addressed
persistent result store (the same track sweep cold, computing and
persisting, vs warm, served from the store with zero PnR) and
search-driven DSE vs the exhaustive grid. Host wall-clocks.
Results go to ``benchmarks/results/dse_speed.json``."""
from __future__ import annotations

import os
import time
from typing import Dict

from repro.core.dse import generation_speed

from .common import emit, save_json, timed


def store_warm_vs_cold(quick: bool = False,
                       store_root: str = None) -> Dict:
    """The persistent-store payoff: one ``sweep_num_tracks`` grid run
    against an empty (or pre-warmed) store, then re-run on a fresh
    executor over the same store. The second pass must do zero PnR —
    every record is served by digest. ``store_root`` defaults to
    ``$CANAL_RESULT_STORE`` when set (so incremental benchmark re-runs
    start warm), else a throwaway temp store — under ``run.py
    --no-store`` the first pass is therefore genuinely cold."""
    import tempfile

    from repro.core.dse import SweepExecutor, sweep_num_tracks
    from repro.core.pnr.app import BENCH_APPS
    from repro.core.store import STORE_ENV, ResultStore

    root = store_root or os.environ.get(STORE_ENV) or \
        tempfile.mkdtemp(prefix="canal-store-bench-")

    apps = {k: BENCH_APPS[k] for k in
            (("fir",) if quick else ("fir", "tree_reduce"))}
    tracks = (3, 4) if quick else (3, 4, 5)
    width = 6 if quick else 8

    def one_pass() -> Dict:
        ex = SweepExecutor(apps=apps, emulate_cycles=8, max_workers=2,
                           store=ResultStore(root))
        t0 = time.perf_counter()
        sweep_num_tracks(tracks, width=width, height=width, executor=ex)
        return {"seconds": time.perf_counter() - t0,
                "store_hits": ex.store_hits,
                "store_misses": ex.store_misses,
                "pnr_computations": ex.pnr_computations}

    cold = one_pass()     # cold only on a truly fresh store; hit counts
    warm = one_pass()     # tell the two cases apart in the record
    assert warm["pnr_computations"] == 0, \
        "warm store must serve the whole sweep without recomputing PnR"
    assert warm["store_hits"] == len(tracks)
    return {"tracks": list(tracks), "width": width, "apps": list(apps),
            "first_pass": cold, "second_pass": warm,
            "cold_seconds": cold["seconds"],
            "warm_seconds": warm["seconds"],
            "speedup": cold["seconds"] / max(warm["seconds"], 1e-9),
            "first_pass_was_warm": cold["pnr_computations"] == 0}


def search_vs_grid(quick: bool = False) -> Dict:
    """The optimizer payoff: greedy ``canal.search`` vs the exhaustive
    grid on the ``sweep_num_tracks`` axis. Asserts the search lands on
    the grid's best fully-routed point while evaluating fewer
    candidates, and that an identical re-run against the warm store
    performs zero new PnR (pure store hits)."""
    import tempfile

    from repro.core.dse import SweepExecutor, sweep_num_tracks
    from repro.core.pnr.app import BENCH_APPS
    from repro.core.search import search
    from repro.core.spec import InterconnectSpec, SwitchBoxType
    from repro.core.store import ResultStore

    apps = {"fir": BENCH_APPS["fir"]}
    tracks = (2, 3, 4) if quick else (2, 3, 4, 5, 6)
    width = 6
    budget = 2 if quick else 4
    base = InterconnectSpec(width=width, height=width, num_tracks=3,
                            io_ring=True, sb_type=SwitchBoxType.WILTON,
                            reg_density=1.0, cb_track_fc=1.0,
                            sb_track_fc=1.0)
    grid_root = tempfile.mkdtemp(prefix="canal-grid-bench-")
    search_root = tempfile.mkdtemp(prefix="canal-search-bench-")

    grid_ex = SweepExecutor(apps=apps, max_workers=2,
                            store=ResultStore(grid_root))
    t0 = time.perf_counter()
    grid = sweep_num_tracks(tracks, width=width, height=width,
                            executor=grid_ex)
    grid_seconds = time.perf_counter() - t0
    routed = [r for r in grid
              if all(a["success"] for a in r["apps"].values())]
    best_grid = min(routed, key=lambda r: r["sb_area"] + r["cb_area"])

    t0 = time.perf_counter()
    res = search(base, {"num_tracks": tracks}, selector="greedy",
                 objective="area",
                 constraints={"min_routability": 1.0},
                 budget=budget, batch_size=2, seed=0, store=search_root,
                 apps=apps, max_workers=2)
    search_seconds = time.perf_counter() - t0
    best = res.best("area", {"min_routability": 1.0})
    assert best is not None, "search found no feasible point"
    assert best.digest == best_grid["spec_digest"], \
        "greedy search must land on the grid's best design point"
    assert len(res.evaluated) < len(tracks), \
        "search must evaluate fewer candidates than the full grid"

    rerun = search(base, {"num_tracks": tracks}, selector="greedy",
                   objective="area",
                   constraints={"min_routability": 1.0},
                   budget=budget, batch_size=2, seed=0,
                   store=search_root, apps=apps, max_workers=2)
    assert rerun.stats["executor"]["pnr_computations"] == 0, \
        "repeated identical search must be pure store hits"

    return {"tracks": list(tracks), "width": width, "budget": budget,
            "grid_size": len(tracks),
            "grid_seconds": grid_seconds,
            "search_seconds": search_seconds,
            "search_evaluations": len(res.evaluated),
            "search_matched_best": True,
            "best_num_tracks": best.spec.num_tracks,
            "best_area": best.metrics["area"],
            "rerun_executor": rerun.stats["executor"]}


def run(quick: bool = False):
    sizes = (4, 8, 16) if quick else (4, 8, 16, 32)
    recs, us = timed(lambda: generation_speed(sizes))
    lines = []
    for r in recs:
        lines.append(emit(
            f"dse_speed/array={r['size']}x{r['size']}", us / len(recs),
            f"nodes={r['nodes']} gen={r['gen_seconds'] * 1e3:.0f}ms "
            f"lower={r['lower_seconds'] * 1e3:.0f}ms"))

    # persistent result store: cold (compute + persist) vs warm (served
    # by digest, zero PnR asserted inside)
    wc = store_warm_vs_cold(quick=quick)
    lines.append(emit(
        f"dse_speed/store_warm_sweep_t{len(wc['tracks'])}",
        wc["warm_seconds"] * 1e6,
        f"cold={wc['cold_seconds']:.2f}s warm={wc['warm_seconds']:.2f}s "
        f"speedup={wc['speedup']:.1f}x "
        f"warm_hits={wc['second_pass']['store_hits']}"))

    # search-driven DSE vs the exhaustive grid (matched-best, fewer
    # evaluations, and zero-PnR re-run all asserted inside)
    sg = search_vs_grid(quick=quick)
    lines.append(emit(
        f"dse_speed/search_vs_grid_t{sg['grid_size']}",
        sg["search_seconds"] * 1e6,
        f"grid={sg['grid_seconds']:.2f}s "
        f"search={sg['search_seconds']:.2f}s "
        f"evals={sg['search_evaluations']}/{sg['grid_size']} "
        f"best_tracks={sg['best_num_tracks']}"))

    save_json("dse_speed", {"generation": recs,
                            "store_warm_vs_cold": wc,
                            "search_vs_grid": sg})
    return lines
