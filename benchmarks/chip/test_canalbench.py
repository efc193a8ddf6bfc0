"""CPU tests of the chip benchmark's yardstick, at small sizes.

They cover the lookup of cells, configurations, mixes and metrics by
name; the peaks table and the refusal to run off a TPU; the trace
reduction; the independent check that decides ``correct`` (the
program's results pass, perturbed ones fail); and whole generator runs
with the timed path broken underneath, each of which must come out not
correct. Nothing here touches a TPU.
"""
from __future__ import annotations

import copy
import importlib.util
import json
import os
import shutil
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from canalbench import (cell, check_pnr, design, device, faults,  # noqa: E402
                        netlist, registry, tracing)

REPO = registry.repo_root()


def small_config(name: str, apps=("pointwise", "butterfly")) -> dict:
    """A configuration file cut to a 6x6 fabric and two apps."""
    cfg = copy.deepcopy(registry.config(registry.load_benchmark(), name))
    cfg["spec"].update(width=6, height=6, num_tracks=4)
    cfg["apps"] = {a: cfg["apps"][a] for a in apps}
    return cfg


# ----------------------------------------------------------- registry
def test_benchmark_names_resolve():
    bench = registry.load_benchmark()
    for w in bench["workloads"]:
        assert registry.config(bench, w["config"])["name"] == w["config"]
        tr = registry.traffic(w["traffic"])
        assert hasattr(registry.generator(tr["generator"]), "run")
    for m in bench["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def test_added_files_are_found_without_editing(tmp_path):
    """A new configuration, mix, generator and metric are new files plus
    new entries in BENCHMARK.json; no file already there changes."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*")
              if p.is_file()}
    bench = registry.load_benchmark()
    bench["configs"].append({"name": "dummy_cfg", "source": "x",
                             "file": "benchmarks/chip/configs/dummy.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "dummy.cell", "config": "dummy_cfg",
                               "traffic": "dummy", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "dummy_metric.cell", "unit": "s",
                               "better": "lower", "source": "program_span",
                               "layer": "device", "moves": "setup_s",
                               "workloads": ["dummy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (bench_dir / "configs" / "dummy.json").write_text('{"name": "d"}')
    (bench_dir / "traffic" / "dummy.json").write_text(
        '{"generator": "dummy"}')
    (bench_dir / "generators" / "dummy.py").write_text(
        "def run(ctx):\n    return 'dummy ran'\n")
    (bench_dir / "metrics" / "dummy_metric.cell.py").write_text(
        "def read(r):\n    return r['x'] * 2\n")
    (bench_dir / "metrics" / "shared.py").write_text(
        "def read(r):\n    return r['x'] + 1\n")

    root = str(tmp_path)
    loaded = registry.load_benchmark(root)
    w = registry.cell(loaded, "dummy.cell")
    assert registry.config(loaded, w["config"], root) == {"name": "d"}
    tr = registry.traffic(w["traffic"], str(bench_dir))
    assert registry.generator(tr["generator"], str(bench_dir)).run(None) \
        == "dummy ran"
    assert registry.metric_reader("dummy_metric.cell",
                                  str(bench_dir))({"x": 3}) == 6
    # a metric split by cell falls back to the reader of its stem
    assert registry.metric_reader("shared.dummy",
                                  str(bench_dir))({"x": 3}) == 4
    with pytest.raises(KeyError):
        registry.metric_reader("nothing.dummy", str(bench_dir))
    names = [m["name"] for m in registry.metrics_of_cell(
        loaded, "dummy.cell", "per_layer")]
    assert names == ["dummy_metric.cell"]
    assert all(p.read_bytes() == data for p, data in before.items())
    with pytest.raises(KeyError):
        registry.cell(loaded, "no.such.cell")


# ------------------------------------------------------------- device
def test_peaks_keyed_by_device_kind():
    v5e = device.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(device.DeviceError):
        device.peaks("TPU v99")


def test_run_refuses_the_cpu(capsys):
    import jax

    with pytest.raises(device.DeviceError):
        device.require(jax.devices(), 1)
    spec = importlib.util.spec_from_file_location(
        "canalbench_run_main", os.path.join(BENCH_DIR, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    rc = run.main(["--workload", "dse8.search", "--seed", "3",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


# -------------------------------------------------------------- trace
def test_trace_reduction_synthetic():
    ms = 1_000_000
    devices = {"/device:TPU:0": [("fusion.1", 10 * ms, 30 * ms),
                                 ("fusion.2", 20 * ms, 40 * ms),
                                 ("copy", 70 * ms, 80 * ms),
                                 ("fusion.1", 95 * ms, 120 * ms)]}
    spans = [("window", 0, 100 * ms), ("route", 0, 10 * ms),
             ("place", 40 * ms, 70 * ms), ("point", 40 * ms, 100 * ms)]
    out = tracing.reduce(devices, spans)
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx(0.045)       # 10-40, 70-80, 95-100
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.025)]
    assert dict(map(tuple, out["idle_gaps"])) == {
        "route": pytest.approx(0.010), "place": pytest.approx(0.030),
        "point": pytest.approx(0.015)}
    with pytest.raises(ValueError):
        tracing.reduce({}, spans)
    with pytest.raises(ValueError):
        tracing.reduce(devices, spans[1:])


def test_trace_reads_only_the_cells_chips():
    ms = 1_000_000
    devices = {"/device:TPU:0": [("a", 0, 50 * ms)],
               "/device:TPU:1": [], "/device:TPU:3": []}
    spans = [("window", 0, 100 * ms)]
    used = tracing.used_planes(devices, 1)
    assert list(used) == ["/device:TPU:0"]
    assert tracing.reduce(used, spans)["busy_s"] == pytest.approx(0.05)
    assert tracing.reduce(devices, spans)["busy_s"] == pytest.approx(
        0.05 / 3)


def test_device_ops_named_by_program():
    ops = [("%fusion.3 = f32[8]{0} fusion(f32[8] %p), kind=kLoop", 5, 2),
           ("%while.6 = (s32[]) while((s32[]) %t), condition=%c", 12, 1),
           ("%copy = f32[8]{0} copy(f32[8] %x)", 30, 1)]
    modules = [("jit_step(123)", 4, 6), ("jit_while(77)", 11, 5)]
    assert tracing.named_ops(ops, modules) == [
        ("jit_step/fusion.3", 5, 7), ("jit_while/while.6", 12, 13),
        ("?/copy", 30, 31)]


def test_trace_spans_recorded_on_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    from canalbench.spans import Recorder

    rec = Recorder(trace=True)
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with rec.span("window"):
        with rec.span("batch"):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    devices, spans = tracing.read_xplane(tracing.find_xplane(str(tmp_path)))
    assert {"window", "batch"} <= {name for name, _, _ in spans}
    assert devices == {}            # the CPU has no TPU plane
    assert [n for n, _, _, _ in rec.spans] == ["batch", "window"]


# ------------------------------------------------- PnR check, 6x6 fabric
@pytest.fixture(scope="module")
def routed():
    """The program's PnR of two apps on a 6x6 fabric, as the checks read
    it."""
    from repro.core.passes import PassManager
    from repro.core.pnr import place_and_route
    from repro.core.spec import InterconnectSpec

    cfg = small_config("canal_dse_8x8")
    spec = InterconnectSpec(**cfg["spec"])
    ic = PassManager().run(spec)
    apps = {}
    for name, data in cfg["apps"].items():
        r = place_and_route(ic, netlist.build(name, data), alphas=(2.0,),
                            sa_steps=40, sa_batch=8, seed=5)
        assert r.success, r.error
        apps[name] = (design.app_result(r),
                      {"wirelength": r.wirelength,
                       "critical_path_ns": r.timing["critical_path_ns"]})
    return {"cfg": cfg, "ic": ic, "graph": design.graph(ic), "apps": apps,
            "spec": dict(cfg["spec"], track_width=16)}


def _check(routed, name, d=None, record=None, app=None):
    d0, rec0 = routed["apps"][name]
    d = d0 if d is None else d
    return check_pnr.check_app(
        routed["graph"], routed["spec"],
        app or routed["cfg"]["apps"][name], d["placement"],
        design.routes_of(d), d["const_ports"], d["reg_ports"],
        record or rec0)


def test_program_pnr_passes_the_check(routed):
    for name in routed["apps"]:
        assert _check(routed, name) == {
            "bad_packing": 0, "bad_placements": 0, "bad_routes": 0,
            "overused_nodes": 0, "wirelength_gap": 0,
            "critical_path_gap": 0.0}


def _broken_edge(d, g):
    src, sinks, tree = d["routes"][0]
    child, _ = tree[-1]
    stranger = next(i for i in range(len(g.kind))
                    if i not in g.wire_of[child] and i != child)
    tree[-1] = [child, stranger]


def _shared_node(d, g):
    t1, t2 = d["routes"][0][2], d["routes"][1][2]
    t2.append(list(t1[0]))


def _pe_on_io_ring(d, g):
    pe = next(n for n in d["placement"] if n.startswith("b"))
    d["placement"][pe] = [0, 2]


def _stacked(d, g):
    a, b = [n for n in d["placement"] if n.startswith("b")][:2]
    d["placement"][b] = list(d["placement"][a])


def _route_left_out(d, g):
    d["routes"].pop()


def _extra_instance(d, g):
    d["placement"]["ghost"] = [2, 2]


@pytest.mark.parametrize("edit,count", [
    (_broken_edge, "bad_routes"), (_shared_node, "overused_nodes"),
    (_pe_on_io_ring, "bad_placements"), (_stacked, "bad_placements"),
    (_route_left_out, "bad_routes"), (_extra_instance, "bad_placements")])
def test_perturbed_design_fails_the_check(routed, edit, count):
    d = copy.deepcopy(routed["apps"]["butterfly"][0])
    edit(d, routed["graph"])
    assert _check(routed, "butterfly", d)[count] > 0


def test_check_reads_the_configurations_netlist(routed):
    """A net the program never routed, because the netlist it was given
    lacked it, fails: the connections come from the configuration."""
    app = copy.deepcopy(routed["cfg"]["apps"]["butterfly"])
    app["nets"].append(["extra", ["in0", "io_out"], [["out1", "io_in"]]])
    assert _check(routed, "butterfly", app=app)["bad_routes"] > 0


def test_folded_constant_is_checked(routed):
    d = copy.deepcopy(routed["apps"]["pointwise"][0])
    pe, ports = next(iter(d["const_ports"].items()))
    port = next(iter(ports))
    ports[port] += 1
    assert _check(routed, "pointwise", d)["bad_packing"] == 1


@pytest.mark.parametrize("field,gap", [("wirelength", "wirelength_gap"),
                                       ("critical_path_ns",
                                        "critical_path_gap")])
def test_altered_record_fails_the_check(routed, field, gap):
    record = dict(routed["apps"]["pointwise"][1])
    record[field] += 1e-3 if field == "critical_path_ns" else 1
    got = _check(routed, "pointwise", record=record)[gap]
    assert got > search_limits()[gap]


def search_limits():
    return registry.generator("search").LIMITS


# ------------------------------------- packing and timing rules, no PnR
REG_APP = {"instances": [["i", "io_in", "add", 0], ["o", "io_out", "add", 0],
                         ["r1", "reg", "add", 0], ["r2", "reg", "add", 0],
                         ["k", "const", "const", 7],
                         ["p", "pe", "add", 0], ["q", "pe", "mul", 0]],
           "nets": [["n0", ["i", "io_out"], [["r1", "in"], ["q", "data1"]]],
                    ["n1", ["r1", "out"], [["r2", "in"], ["q", "data0"]]],
                    ["n2", ["r2", "out"], [["p", "data0"]]],
                    ["n3", ["k", "out"], [["p", "data1"]]],
                    ["n4", ["p", "res0"], [["o", "io_in"]]],
                    ["n5", ["q", "res0"], [["o", "io_in"]]]]}


def test_packing_rules():
    """r2 feeds one PE input, so it may be absorbed; r1 feeds two sinks,
    so it must be placed; the constant folds into its one PE input."""
    pack = check_pnr.Packing(REG_APP, placed=["i", "o", "r1", "p", "q"])
    assert pack.absorbed == {"r2": ("p", "data0")}
    assert pack.folded == {"k": ("p", "data1")}
    assert sorted(pack.to_place) == ["i", "o", "p", "q", "r1"]
    assert ("r1", "out", "p", "data0", True) in pack.connections(REG_APP)
    assert pack.bad({"p": {"data1": 7}}, {"p": ["data0"]}) == 0
    assert pack.bad({"p": {"data1": 7}}, {}) == 1          # absorption lost
    assert pack.bad({}, {"p": ["data0"]}) == 1             # fold lost
    assert pack.bad({"p": {"data1": 7}, "q": {"data1": 1}},
                    {"p": ["data0"]}) == 1                 # fold invented
    # r1 not placed, but it feeds two sinks: nothing may absorb it
    assert check_pnr.Packing(REG_APP, placed=["i", "o", "p", "q"]
                             ).bad({"p": {"data1": 7}}, {"p": ["data0"]}) \
        == 1


def test_registers_end_timing_paths():
    """i -> r1 -> q: the register cuts the path; r1 -> (absorbed r2) ->
    p: the path ends at p's input and p's output starts at the clock."""
    pack = check_pnr.Packing(REG_APP, placed=["i", "o", "r1", "p", "q"])
    conns = pack.connections(REG_APP)
    segments = {c: (1.0, 0) for c in conns}
    # i -> q.data1 at 1.0; r1 launches at 0.8 -> q.data0 at 1.8, so q's
    # output is at 2.6 and o's input at 3.6; p's output starts at the
    # clock (0.8), so p -> o arrives at 1.8
    assert check_pnr.critical_path(pack, conns, segments) == \
        pytest.approx(3.6)
    # a register crossed on the route r1 -> q restarts that segment
    segments[("r1", "out", "q", "data0", False)] = (1.0, 1)
    assert check_pnr.critical_path(pack, conns, segments) == \
        pytest.approx(2.8)


# --------------------------------------- whole generator runs on the CPU
def _drive(tmp_path, generator, cfg, traffic, fault, seconds=0.1):
    ctx = cell.Ctx(cell={"name": "test", "chips": 1}, config=cfg,
                   traffic=traffic, seed=2 ** 31 + 11, seconds=seconds,
                   trace=False, bench_dir=str(tmp_path), repo_root=REPO)
    try:
        with faults.planted(generator, fault):
            out = registry.generator(generator).run(ctx)
            table = out["check"]()
    finally:
        ctx.rec.close()
    return out, cell.passed(table) and out["failed"] == 0


@pytest.fixture(scope="module")
def search_case():
    cfg = small_config("canal_dse_8x8")
    cfg["grid"] = {"num_tracks": [3, 4], "sb_type": ["wilton"],
                   "sb_sides": [4], "cb_sides": [4]}
    return cfg, dict(registry.traffic("search"), in_flight=2)


@pytest.mark.parametrize("fault", ("none",) + tuple(
    f for f in faults.SEARCH if f not in ("give_up_early", "give_up")))
def test_search_run_with_fault(tmp_path, search_case, fault):
    cfg, traffic = search_case
    out, ok = _drive(tmp_path, "search", cfg, traffic, fault)
    assert out["attempted"] == 2
    assert out["e2e"]["points_per_s"] > 0
    assert ok == (fault == "none")


@pytest.mark.parametrize("fault", ("none", "give_up_early", "give_up"))
def test_giving_up_fails(tmp_path, fault):
    """At two tracks, three SB sides and two CB sides the butterfly
    needs further negotiation rounds on the configuration's 8x8 fabric:
    a router capped at one round calls it unroutable, one that never
    starts calls every app unroutable, and the window's count passes
    its limit either way."""
    cfg = copy.deepcopy(registry.config(registry.load_benchmark(),
                                        "canal_dse_8x8"))
    cfg["grid"] = {"num_tracks": [2], "sb_type": ["wilton", "imran"],
                   "sb_sides": [3], "cb_sides": [2]}
    traffic = dict(registry.traffic("search"), in_flight=2)
    out, ok = _drive(tmp_path, "search", cfg, traffic, fault)
    table = out["check"]()
    assert out["attempted"] == 2
    assert ok == (fault == "none"), table
    over = table["unrouted_apps"]["value"] > table["unrouted_apps"]["limit"]
    assert over == (fault != "none")


def test_search_plan_covers_the_grid_once_per_seed():
    cfg = registry.config(registry.load_benchmark(), "canal_dse_8x8")
    tr = registry.traffic("search")
    search = registry.generator("search")
    first = {}
    for seed in (0, 1, 2 ** 31 + 5):
        pts = search.plan(cfg, tr, seed)
        keys = [tuple(sorted(p.items())) for p in pts]
        assert len(keys) == len(set(keys)) == 135
        first[seed] = sorted((p["num_tracks"], p["sb_type"])
                             for p in pts[:15])
    # every seed's first block holds the same track/topology mix
    assert len({tuple(v) for v in first.values()}) == 1
