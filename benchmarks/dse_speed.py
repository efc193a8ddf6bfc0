"""Abstract claim — "Canal enables fast design space exploration": IR
generation + hardware lowering speed vs array size, plus the batched DSE
engine: B fabric configurations emulated as one ``run_batch`` scan vs the
serial per-config baseline, the fused engine (whole fixpoint + PE eval
per cycle) vs the sweep-at-a-time PR-1 path, batch-axis sharding across
the devices of this process, and the
spec-addressed persistent result store: the same track sweep cold
(computing + persisting) vs warm (served from the store, zero PnR).
Results go to ``benchmarks/results/dse_speed.json``."""
from __future__ import annotations

import os
import time
from typing import Dict

import jax

from repro.core.dse import (batched_vs_serial_emulation,
                            fused_vs_unfused_emulation, generation_speed,
                            sharded_vs_single_emulation)

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

    # batched configuration emulation: the production run_batch path
    # (the fused XLA engine) vs looping run per config
    batch = 4 if quick else 8
    cycles = 8 if quick else 16
    width = 4 if quick else 6
    tracks = 2 if quick else 4
    emu = batched_vs_serial_emulation(width=width, height=width,
                                      num_tracks=tracks,
                                      batch=batch, cycles=cycles)
    lines.append(emit(
        f"dse_speed/batched_emulation_b={emu['batch']}",
        emu["batched_seconds"] * 1e6,
        f"serial={emu['serial_seconds'] * 1e3:.0f}ms "
        f"batched={emu['batched_seconds'] * 1e3:.0f}ms "
        f"speedup={emu['speedup']:.2f}x depth={emu['depth']}"))
    # both paths are pre-warmed; the measured margin is ~2.5-4x, so a 1.5x
    # tolerance only absorbs shared-runner timing noise, not a regression
    assert emu["batched_seconds"] <= emu["serial_seconds"] * 1.5, \
        "batched DSE emulation must not be slower than the serial baseline"

    # fused engine (one fused step per cycle, PE cores inside it,
    # per-config depth masking) vs the sweep-at-a-time PR-1 baseline
    fus = fused_vs_unfused_emulation(width=width, height=width,
                                     num_tracks=tracks, batch=batch,
                                     cycles=cycles)
    lines.append(emit(
        f"dse_speed/fused_emulation_b={fus['batch']}",
        fus["fused_seconds"] * 1e6,
        f"unfused={fus['unfused_seconds'] * 1e3:.0f}ms "
        f"fused={fus['fused_seconds'] * 1e3:.0f}ms "
        f"speedup={fus['speedup']:.2f}x "
        f"depths={fus['min_depth']}..{fus['max_depth']}"))
    # measured margin ~1.3x in favour of the fused engine; the tolerance
    # absorbs runner noise while still catching a real regression
    assert fus["fused_seconds"] <= fus["unfused_seconds"] * 1.2, \
        "fused DSE engine must not regress the sweep-at-a-time baseline"

    # batch-axis sharding across this process's devices (1 device ->
    # fallback parity check)
    shd = sharded_vs_single_emulation(width=4, height=4, num_tracks=2,
                                      batch=batch, cycles=cycles)
    lines.append(emit(
        f"dse_speed/sharded_emulation_dev={shd['devices']}",
        shd["sharded_seconds"] * 1e6,
        f"single={shd['single_seconds'] * 1e3:.0f}ms "
        f"sharded={shd['sharded_seconds'] * 1e3:.0f}ms "
        f"speedup={shd['speedup']:.2f}x"))
    if len(jax.devices()) == 1:
        # same code path either way; anything beyond noise is a bug in
        # the single-device fallback
        assert shd["sharded_seconds"] <= shd["single_seconds"] * 1.5, \
            "single-device shard fallback must not add overhead"
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

    save_json("dse_speed", {"generation": recs, "batched_emulation": emu,
                            "fused_emulation": fus,
                            "sharded_emulation": shd,
                            "store_warm_vs_cold": wc,
                            "search_vs_grid": sg})
    return lines
