"""Search-driven DSE: the SearchSpace axes, Pareto machinery, selector
policies, the search() driver (acceptance: greedy matches the best
grid-sweep point with fewer evaluations, repeats are zero-PnR), the
DSEService.recommend verb, and the canal.search CLI."""
import json
import random

import pytest
from _hypothesis_compat import given, settings, st

import canal
from repro.core.dse import SweepExecutor, sweep_num_tracks
from repro.core.pnr.app import app_pointwise
from repro.core.search import (SearchSpace, SelectorKind, dominates,
                               make_selector, pareto_frontier, search)
from repro.core.search.pareto import (Evaluated, best_point,
                                      objective_value, point_metrics,
                                      satisfies)
from repro.core.spec import (InterconnectSpec, SwitchBoxType,
                             mutate_spec, neighbor_specs, spec_axes)
from repro.core.store import ResultStore, record_metrics

BASE = InterconnectSpec(width=4, height=4, num_tracks=4, io_ring=True,
                        sb_type=SwitchBoxType.WILTON, reg_density=1.0,
                        cb_track_fc=1.0, sb_track_fc=1.0)


def _ev(digest, area, delay, routability, valid=True):
    return Evaluated(spec=BASE, digest=str(digest), record={},
                     metrics={"area": area, "critical_path_ns": delay,
                              "routability": routability}, valid=valid)


# ---------------------------------------------------------------------------
# Axis helpers (spec.py)
# ---------------------------------------------------------------------------

def test_spec_axes_validates_and_canonicalizes():
    axes = spec_axes(BASE, {"num_tracks": [2, 3, 3, 2],
                            "sb_type": ["wilton", "disjoint"]})
    assert axes["num_tracks"] == (2, 3)           # deduped, ordered
    assert axes["sb_type"] == (SwitchBoxType.WILTON,
                               SwitchBoxType.DISJOINT)
    with pytest.raises(TypeError, match="unknown spec axis"):
        spec_axes(BASE, {"num_trax": [2]})
    with pytest.raises(ValueError, match="num_tracks"):
        spec_axes(BASE, {"num_tracks": ["nope"]})
    with pytest.raises(ValueError, match="no values"):
        spec_axes(BASE, {"num_tracks": []})


def test_mutate_spec_moves_one_axis():
    axes = spec_axes(BASE, {"num_tracks": (2, 3, 4)})
    rng = random.Random(0)
    for _ in range(10):
        m = mutate_spec(BASE, axes, rng)
        assert m.num_tracks in (2, 3) and m != BASE
    # one-point space: unchanged
    assert mutate_spec(BASE, {"num_tracks": (4,)}, rng) == BASE


def test_neighbor_specs_adjacent_and_deterministic():
    axes = spec_axes(BASE, {"num_tracks": (2, 3, 4, 5, 6),
                            "sb_type": ("wilton", "disjoint")})
    nbrs = neighbor_specs(BASE, axes)
    assert [(n.num_tracks, n.sb_type) for n in nbrs] == [
        (3, SwitchBoxType.WILTON), (5, SwitchBoxType.WILTON),
        (4, SwitchBoxType.DISJOINT)]
    # off-axis current value: every axis value is a neighbor
    off = BASE.replace(num_tracks=9)
    nbrs = neighbor_specs(off, {"num_tracks": (2, 3)})
    assert [n.num_tracks for n in nbrs] == [2, 3]


# ---------------------------------------------------------------------------
# SearchSpace
# ---------------------------------------------------------------------------

def test_search_space_geometry():
    sp = SearchSpace(BASE, {"num_tracks": (2, 3, 4),
                            "sb_type": ("wilton", "disjoint")})
    assert sp.size() == 6 and len(sp) == 6
    grid = sp.grid()
    assert len(set(grid)) == 6
    assert all(sp.contains(s) for s in grid)
    assert not sp.contains(BASE.replace(num_tracks=9))
    assert not sp.contains(BASE.replace(width=5, num_tracks=2))
    org = sp.origin()
    assert org.num_tracks == 4                    # base value on-axis
    assert org.sb_type == SwitchBoxType.WILTON
    # base value off-axis: snaps to the middle value
    sp2 = SearchSpace(BASE, {"num_tracks": (5, 6, 7)})
    assert sp2.origin().num_tracks == 6
    with pytest.raises(ValueError, match="at least one axis"):
        SearchSpace(BASE, {})


def test_search_space_sampling_stays_in_space():
    sp = SearchSpace(BASE, {"num_tracks": (2, 3, 4)})
    rng = random.Random(1)
    for _ in range(20):
        assert sp.contains(sp.sample(rng))
        assert sp.contains(sp.mutate(sp.sample(rng), rng))


# ---------------------------------------------------------------------------
# Pareto machinery
# ---------------------------------------------------------------------------

def test_dominates_partial_order():
    a = {"area": 1.0, "critical_path_ns": 1.0, "routability": 1.0}
    b = {"area": 2.0, "critical_path_ns": 1.0, "routability": 1.0}
    c = {"area": 1.0, "critical_path_ns": 2.0, "routability": 0.5}
    assert dominates(a, b) and not dominates(b, a)
    assert dominates(a, c) and not dominates(c, a)
    assert not dominates(b, c) and not dominates(c, b)  # incomparable
    assert not dominates(a, a)                    # ties dominate nothing


def test_pareto_frontier_invariants():
    pts = [_ev(0, 10, 5, 1.0), _ev(1, 20, 5, 1.0),   # 1 dominated by 0
           _ev(2, 5, 9, 1.0),                        # tradeoff: kept
           _ev(3, 1, 1, 1.0, valid=False),           # invalid: excluded
           _ev(4, 10, 5, 1.0)]                       # metric tie: kept
    front = pareto_frontier(pts)
    assert [p.digest for p in front] == ["0", "2", "4"]


def test_best_point_constraints_and_fallback():
    pts = [_ev(0, 10, 9, 1.0), _ev(1, 20, 2, 1.0), _ev(2, 5, 1, 0.5)]
    assert best_point(pts, "area").digest == "2"
    c = {"min_routability": 1.0}
    assert best_point(pts, "area", c).digest == "0"
    assert best_point(pts, "critical_path_ns", c).digest == "1"
    tight = {"max_critical_path_ns": 0.5}
    assert best_point(pts, "area", tight) is None          # strict
    assert best_point(pts, "area", tight, strict=False).digest == "2"
    with pytest.raises(ValueError, match="unknown constraint"):
        satisfies(pts[0].metrics, {"max_delay": 1})
    with pytest.raises(ValueError, match="unknown objective"):
        objective_value(pts[0].metrics, "speed")


def test_point_metrics_prefers_stamp_and_rederives():
    rec = {"apps": {"a": {"success": True, "critical_path_ns": 2.5}},
           "sb_area": 7.0, "cb_area": 3.0}
    m = point_metrics(rec)
    assert m == {"area": 10.0, "critical_path_ns": 2.5,
                 "routability": 1.0}
    assert m == record_metrics(rec)
    stamped = dict(rec, metrics={"area": 99.0, "critical_path_ns": 1.0,
                                 "routability": 0.5})
    assert point_metrics(stamped)["area"] == 99.0


# ---------------------------------------------------------------------------
# Selectors
# ---------------------------------------------------------------------------

def test_random_selector_enumerates_small_space_exactly():
    sp = SearchSpace(BASE, {"num_tracks": (2, 3), "io_ring": (True,),
                            "sb_type": ("wilton", "disjoint")})
    sel = make_selector("random", sp, random.Random(0))
    seen = []
    while True:
        batch = sel.propose(3)
        if not batch:
            break
        seen.extend(batch)
        sel.observe([_ev(i, 1, 1, 1) for i in range(len(batch))])
    assert len(seen) == sp.size() == 4            # no dup, no miss
    assert len(set(seen)) == 4


def test_greedy_selector_walks_toward_the_optimum():
    sp = SearchSpace(BASE, {"num_tracks": (2, 3, 4, 5, 6)})
    sel = make_selector("greedy", sp, random.Random(0),
                        objective="area")
    first = sel.propose(2)
    assert [s.num_tracks for s in first] == [4]   # the origin
    # area grows with tracks: feed back and expect descent toward 2
    def feed(batch):
        evs = [Evaluated(spec=s, digest=str(s.num_tracks), record={},
                         metrics={"area": float(s.num_tracks),
                                  "critical_path_ns": 1.0,
                                  "routability": 1.0}, valid=True)
               for s in batch]
        sel.observe(evs)
    feed(first)
    second = sel.propose(2)
    assert sorted(s.num_tracks for s in second) == [3, 5]
    feed(second)
    third = sel.propose(2)
    assert [s.num_tracks for s in third] == [2]   # neighbor of 3
    feed(third)
    fourth = sel.propose(2)                       # local optimum: restart
    assert [s.num_tracks for s in fourth] == [6]  # the only unseen point
    feed(fourth)
    assert sel.propose(2) == []                   # space exhausted


def test_make_selector_rejects_unknown_kind():
    sp = SearchSpace(BASE, {"num_tracks": (2, 3)})
    with pytest.raises(ValueError, match="unknown selector"):
        make_selector("simulated-annealing", sp, random.Random(0))
    for kind in SelectorKind:
        assert make_selector(kind, sp, random.Random(0)) is not None


# ---------------------------------------------------------------------------
# search() driver on a fake executor (fast, deterministic)
# ---------------------------------------------------------------------------

class FakeExecutor:
    """Deterministic synthetic evaluator: metrics derived from the spec
    digest, ~1 in 5 points statically invalid. Counts evaluations."""

    def __init__(self):
        self.evals = 0

    def stats(self):
        return {"evaluations": self.evals}

    def run_specs(self, specs, record=False, assume_cold=False):
        recs = []
        for s in specs:
            self.evals += 1
            h = int(s.digest()[:8], 16)
            clean = h % 5 != 0
            success = clean and h % 3 != 0
            rec = {"spec_digest": s.digest(),
                   "sb_area": 10.0 + h % 7, "cb_area": float(h % 5),
                   "analysis": {"clean": clean},
                   "apps": {"a": {"success": success,
                                  "critical_path_ns":
                                      1.0 + h % 9 if success
                                      else float("inf")}}}
            if not clean:
                rec["apps"]["a"]["skipped"] = "static-analysis"
            rec["metrics"] = record_metrics(rec)
            recs.append(rec)
        return recs


@given(st.integers(0, 10 ** 6),
       st.sampled_from(["random", "greedy", "evolutionary"]),
       st.integers(1, 12))
@settings(max_examples=25, deadline=None)
def test_search_frontier_properties(seed, kind, budget):
    """The property the optimizer stands on: the returned frontier is
    mutually non-dominated, and every evaluated valid non-frontier
    point is strictly dominated by some frontier point; invalid points
    never surface; the budget is respected."""
    ex = FakeExecutor()
    res = search(BASE, {"num_tracks": (2, 3, 4, 5, 6),
                        "sb_type": ("wilton", "disjoint", "imran")},
                 selector=kind, budget=budget, batch_size=3, seed=seed,
                 executor=ex)
    assert len(res.evaluated) <= budget
    assert ex.evals == len(res.evaluated)         # driver never re-evals
    digests = [p.digest for p in res.evaluated]
    assert len(set(digests)) == len(digests)      # dedup held
    front = res.frontier
    assert all(p.valid for p in front)
    for p in front:
        assert not any(dominates(q.metrics, p.metrics) for q in front)
    in_front = {id(p) for p in front}
    for p in res.evaluated:
        if p.valid and id(p) not in in_front:
            assert any(dominates(q.metrics, p.metrics) for q in front)
    assert res.stats["evaluated"] == len(res.evaluated)
    assert res.stats["statically_invalid"] == \
        sum(1 for p in res.evaluated if not p.valid)


def test_search_same_seed_reproduces():
    runs = [search(BASE, {"num_tracks": (2, 3, 4, 5, 6)},
                   selector="evolutionary", budget=5, batch_size=2,
                   seed=7, executor=FakeExecutor())
            for _ in range(2)]
    assert [p.digest for p in runs[0].evaluated] == \
        [p.digest for p in runs[1].evaluated]


def test_search_argument_validation():
    with pytest.raises(TypeError, match="base \\+ axes"):
        search(selector="random", executor=FakeExecutor())
    sp = SearchSpace(BASE, {"num_tracks": (2, 3)})
    with pytest.raises(TypeError, match="not both"):
        search(BASE, {"num_tracks": (2,)}, space=sp,
               executor=FakeExecutor())
    with pytest.raises(ValueError, match="budget"):
        search(space=sp, budget=0, executor=FakeExecutor())
    with pytest.raises(TypeError, match="prebuilt executor"):
        search(space=sp, executor=FakeExecutor(), store="x")


# ---------------------------------------------------------------------------
# Acceptance: search vs the sweep_num_tracks grid, store-backed
# ---------------------------------------------------------------------------

def _grid_best(recs):
    routed = [r for r in recs
              if all(a["success"] for a in r["apps"].values())]
    return min(routed, key=lambda r: r["sb_area"] + r["cb_area"])


def test_greedy_search_matches_grid_best_with_fewer_evals(tmp_path):
    """THE acceptance criterion: greedy search over the
    sweep_num_tracks axis lands on the same best design point as the
    exhaustive grid while evaluating fewer candidates, and an identical
    re-run against the warm store performs zero new PnR."""
    apps = {"pw": lambda: app_pointwise(1)}
    tracks = (2, 3, 4, 5, 6)
    grid_ex = SweepExecutor(apps=apps, store=ResultStore(
        str(tmp_path / "grid")), emulate_cycles=0, max_workers=1)
    grid = sweep_num_tracks(tracks, width=4, height=4, executor=grid_ex)
    best_grid = _grid_best(grid)
    assert grid_ex.pnr_computations == len(tracks)

    store = str(tmp_path / "search")
    res = search(BASE, {"num_tracks": tracks}, selector="greedy",
                 objective="area",
                 constraints={"min_routability": 1.0},
                 budget=4, batch_size=2, seed=0, store=store,
                 apps=apps, max_workers=1)
    best = res.best("area", {"min_routability": 1.0})
    assert best is not None
    assert best.digest == best_grid["spec_digest"]     # same optimum
    assert len(res.evaluated) < len(tracks)            # fewer evals
    assert res.stats["executor"]["pnr_computations"] == \
        len(res.evaluated)

    res2 = search(BASE, {"num_tracks": tracks}, selector="greedy",
                  objective="area",
                  constraints={"min_routability": 1.0},
                  budget=4, batch_size=2, seed=0, store=store,
                  apps=apps, max_workers=1)
    assert res2.stats["executor"]["pnr_computations"] == 0  # zero PnR
    assert res2.stats["executor"]["store_hits"] == len(res2.evaluated)
    assert res2.best("area", {"min_routability": 1.0}).digest == \
        best.digest


def test_evolutionary_search_finds_grid_best(tmp_path):
    """The evolutionary selector also lands on the grid optimum on the
    single-axis space (random first generation covers it; the Pareto
    archive keeps it)."""
    apps = {"pw": lambda: app_pointwise(1)}
    res = search(BASE, {"num_tracks": (2, 3, 4)}, selector="evolutionary",
                 objective="area",
                 constraints={"min_routability": 1.0},
                 budget=3, batch_size=3, seed=0,
                 store=str(tmp_path / "s"), apps=apps,
                 max_workers=1)
    best = res.best("area", {"min_routability": 1.0})
    assert best is not None and best.spec.num_tracks == 2


def test_recommend_serving_verb(tmp_path):
    """DSEService.recommend: the cache is a recommendation engine —
    and its second recommendation is pure store hits."""
    svc = canal.serve(store=str(tmp_path / "s"),
                      apps={"pw": lambda: app_pointwise(1)},
                      emulate_cycles=0, max_workers=1)
    out = svc.recommend(BASE, {"num_tracks": [2, 3]},
                        objective="area",
                        constraints={"min_routability": 1.0},
                        budget=2, batch_size=2, selector="random")
    assert out["best"] is not None
    assert out["best"]["spec"]["num_tracks"] == 2
    assert out["frontier"] and out["stats"]["evaluated"] == 2
    again = svc.recommend(BASE, {"num_tracks": [2, 3]},
                          objective="area",
                          constraints={"min_routability": 1.0},
                          budget=2, batch_size=2, selector="random")
    assert again["stats"]["executor"]["pnr_computations"] == 0
    assert again["best"]["digest"] == out["best"]["digest"]
    svc.close()


def test_cli_emits_frontier_json(tmp_path):
    from repro.core.search.cli import run
    out = tmp_path / "frontier.json"
    code = run(["--width", "5", "--axes", '{"num_tracks": [2, 3]}',
                "--selector", "random", "--budget", "2", "--batch", "2",
                "--apps", "pointwise", "--seed", "0",
                "--store", str(tmp_path / "store"), "-o", str(out)])
    assert code == 0
    doc = json.loads(out.read_text())
    assert len(doc["frontier"]) >= 1
    assert doc["stats"]["evaluated"] == 2
    assert doc["best"] is not None
    assert doc["space"]["size"] == 2
    # warm re-run: zero PnR, still a frontier
    code = run(["--width", "5", "--axes", '{"num_tracks": [2, 3]}',
                "--selector", "greedy", "--budget", "2", "--batch", "2",
                "--apps", "pointwise", "--seed", "0",
                "--store", str(tmp_path / "store"), "-o", str(out)])
    assert code == 0
    doc = json.loads(out.read_text())
    assert doc["stats"]["executor"]["pnr_computations"] == 0


def test_cli_usage_errors(tmp_path):
    from repro.core.search.cli import run
    with pytest.raises(SystemExit) as e:
        run(["--axes", "not json"])
    assert e.value.code == 2
    with pytest.raises(SystemExit) as e:
        run(["--axes", '{"num_trax": [1]}', "--no-store"])
    assert e.value.code == 2
    with pytest.raises(SystemExit) as e:
        run(["--axes", '{"num_tracks": [2]}', "--apps", "nope"])
    assert e.value.code == 2


def test_canal_front_door_exports():
    assert canal.search is not None and canal.SearchSpace is not None
    assert "search" in canal.__all__ and "SearchSpace" in canal.__all__
    sp = canal.SearchSpace(BASE, {"num_tracks": (2, 3)})
    assert sp.size() == 2
