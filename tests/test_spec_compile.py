"""The declarative front door: InterconnectSpec serialization, the pass
pipeline's determinism and legacy equivalence, CompiledFabric end-to-end,
and the spec-digest cache keys of the DSE executor."""
import subprocess
import sys

import numpy as np
import pytest

import canal
from repro.core.compile import CompiledFabric
from repro.core.passes import (IRPass, PassManager, freeze, ir_digest,
                               materialize_tiles, prune_dead_muxes)
from repro.core.spec import (InterconnectSpec, SwitchBoxType,
                             spec_from_kwargs, spec_grid)

SMOKE = dict(width=4, height=4, num_tracks=2, io_ring=True, reg_density=1.0)


# ---------------------------------------------------------------------------
# Spec serialization
# ---------------------------------------------------------------------------

def test_spec_json_round_trip():
    spec = InterconnectSpec(width=6, height=5, num_tracks=3,
                            sb_type="imran", reg_density=0.5,
                            mem_columns=(2,), extra_layers={1: 4},
                            ready_valid=True, split_fifo=True,
                            route_strategy="minplus", auto_min_tiles=30)
    rt = InterconnectSpec.from_json(spec.to_json())
    assert rt == spec
    assert rt.digest() == spec.digest()
    assert hash(rt) == hash(spec)


def test_spec_digest_key_order_independent():
    spec = InterconnectSpec(**SMOKE)
    d = spec.to_dict()
    shuffled = {k: d[k] for k in sorted(d, reverse=True)}
    assert InterconnectSpec.from_dict(shuffled).digest() == spec.digest()


def test_spec_digest_stable_across_processes():
    import os

    import repro.core.spec as spec_mod

    spec = InterconnectSpec(**SMOKE)
    src_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(spec_mod.__file__))))
    env = dict(os.environ)
    env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
    code = ("from repro.core.spec import InterconnectSpec\n"
            f"print(InterconnectSpec(**{SMOKE!r}).digest())\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True, env=env)
    assert out.stdout.strip() == spec.digest()


def test_spec_is_frozen_and_canonicalized():
    spec = InterconnectSpec(**SMOKE)
    with pytest.raises(Exception):       # FrozenInstanceError
        spec.width = 99                  # type: ignore[misc]
    # str sb_type and dict extra_layers canonicalize to enum/sorted tuple
    a = InterconnectSpec(sb_type="wilton", extra_layers={1: 4, 32: 2})
    b = InterconnectSpec(sb_type=SwitchBoxType.WILTON,
                         extra_layers=((1, 4), (32, 2)))
    assert a == b and a.digest() == b.digest()
    assert {a: "hit"}[b] == "hit"        # usable as a dict key


def test_spec_validation():
    with pytest.raises(ValueError):
        InterconnectSpec(width=0)
    with pytest.raises(ValueError):
        InterconnectSpec(reg_density=1.5)
    with pytest.raises(ValueError):
        InterconnectSpec(route_strategy="warp")
    with pytest.raises(TypeError):
        InterconnectSpec.from_dict({"widht": 4})     # typo -> clear error


def test_spec_from_kwargs_rejects_callables():
    with pytest.raises(TypeError, match="core_fn.*not serializable"):
        spec_from_kwargs(width=4, core_fn=lambda x, y, w, h: None)


def test_spec_grid_product_and_labels():
    base = InterconnectSpec(**SMOKE)
    pts = spec_grid(base, {"num_tracks": (2, 3), "sb_type":
                           (SwitchBoxType.WILTON, SwitchBoxType.DISJOINT)})
    assert len(pts) == 4
    specs = [s for s, _ in pts]
    assert len(set(specs)) == 4
    assert pts[0][1] == {"num_tracks": 2, "sb_type": "wilton"}
    labelled = spec_grid(base, {"num_tracks": (2,)},
                         label=lambda s: {"t": s.num_tracks * 10})
    assert labelled[0][1] == {"t": 20}


# ---------------------------------------------------------------------------
# Pass pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic():
    """Compiling the same spec twice yields isomorphic IR: identical
    node/edge multisets down to mux input order (= config semantics)."""
    spec = InterconnectSpec(width=5, height=4, num_tracks=3, io_ring=True,
                            reg_density=0.5, cb_track_fc=0.5,
                            mem_columns=(2,))
    a, b = canal.compile(spec), canal.compile(spec)
    assert a.ir_digest() == b.ir_digest()
    assert a.interconnect.num_nodes() == b.interconnect.num_nodes()
    assert a.interconnect.num_edges() == b.interconnect.num_edges()
    assert a.interconnect.connectivity() == b.interconnect.connectivity()


def test_shim_emits_deprecation_and_matches_pipeline():
    """`create_uniform_interconnect` still works, warns, and produces an
    interconnect isomorphic to PassManager.compile(InterconnectSpec())."""
    from repro.core.edsl import create_uniform_interconnect

    with pytest.warns(DeprecationWarning, match="canal.compile"):
        legacy = create_uniform_interconnect(**SMOKE)
    compiled = PassManager().compile(InterconnectSpec(**SMOKE))
    assert ir_digest(legacy) == compiled.ir_digest()
    assert legacy.connectivity() == compiled.interconnect.connectivity()


def test_pipeline_for_gates_optional_passes():
    pm = PassManager()
    static = pm.pipeline_for(InterconnectSpec(**SMOKE))
    rv = pm.pipeline_for(InterconnectSpec(ready_valid=True, **SMOKE))
    assert "readyvalid_transform" not in static
    assert "readyvalid_transform" in rv
    assert static == ["materialize_tiles", "apply_sb_topology",
                      "insert_pipeline_registers", "connect_core_ports",
                      "prune_dead_muxes", "freeze"]


def test_readyvalid_transform_annotates_ir():
    fab = canal.compile(InterconnectSpec(ready_valid=True, split_fifo=True,
                                         **SMOKE))
    ic = fab.interconnect
    regs = [r for g in ic.graphs.values() for r in g.registers]
    assert regs and all(r.attributes.get("rv_fifo") == "split"
                        for r in regs)
    assert ic.params["rv_fifo_mode"] == "split"
    from repro.fabric import RVFabric
    assert isinstance(fab.fabric(), RVFabric)


def test_prune_removes_only_isolated_nodes():
    """A pipeline that never wires the switch boxes leaves every SB node
    isolated: prune drops them all but keeps core ports (interface)."""
    spec = InterconnectSpec(**SMOKE)
    pm = PassManager((IRPass("materialize_tiles", materialize_tiles),
                      IRPass("prune_dead_muxes", prune_dead_muxes),
                      IRPass("freeze", freeze)))
    ic = pm.run(spec)
    from repro.core.graph import NodeKind
    kinds = {n.kind for n in ic.nodes()}
    assert NodeKind.SWITCH_BOX not in kinds          # all isolated -> gone
    assert NodeKind.PORT in kinds                    # interface kept
    # full pipeline on the stock uniform topology: nothing is isolated
    full = canal.compile(spec)
    log = [e for e in full.pass_log if e["pass"] == "prune_dead_muxes"]
    assert log and log[0]["removed"] == 0


def test_prune_refuses_connected_nodes():
    from repro.core.graph import InterconnectGraph, PortNode

    g = InterconnectGraph(16)
    a, b = PortNode("a", 0, 0, 16), PortNode("b", 0, 0, 16)
    a.add_edge(b)
    with pytest.raises(ValueError, match="connected"):
        g.prune([a])


def test_prune_accepts_generator_input():
    """A one-shot iterable must not drain during validation and then
    silently prune nothing."""
    from repro.core.graph import InterconnectGraph, RegisterNode

    g = InterconnectGraph(16)
    reg = RegisterNode("r", 0, 0, 0, 16)
    g.add_register(reg)
    g.prune(n for n in [reg])
    assert reg not in list(g.nodes())


def test_readyvalid_rejects_unsupported_fifo_depth():
    spec = InterconnectSpec(ready_valid=True, fifo_depth=8, **SMOKE)
    with pytest.raises(ValueError, match="depth-2"):
        canal.compile(spec)


def test_prune_never_removes_routed_nodes():
    """No node used by any routed example app is pruned."""
    from repro.core.pnr.app import BENCH_APPS

    spec = InterconnectSpec(width=6, height=6, num_tracks=4, io_ring=True,
                            reg_density=1.0)
    fab = canal.compile(spec)
    pruned = set()
    for g in fab.interconnect.graphs.values():
        pruned |= g._pruned
    for name in ("pointwise", "tree_reduce"):
        r = fab.place_and_route(BENCH_APPS[name](), alphas=(2.0,),
                                sa_steps=30, sa_batch=8)
        assert r.success, f"{name}: {r.error}"
        used = {n for e in r.route_edges() for n in e}
        assert not (used & pruned)


# ---------------------------------------------------------------------------
# CompiledFabric end to end
# ---------------------------------------------------------------------------

def test_compiled_fabric_end_to_end():
    """spec -> compile -> place_and_route -> bitstream -> emulate, the
    quickstart flow, asserted."""
    from repro.core.pnr.app import app_pointwise

    spec = InterconnectSpec(width=6, height=6, num_tracks=4, io_ring=True,
                            reg_density=1.0)
    fab = canal.compile(spec)
    area = fab.area()
    assert area["sb_area"] > 0 and area["cb_area"] > 0

    result = fab.place_and_route(app_pointwise(2), alphas=(2.0,),
                                 sa_steps=40, sa_batch=8)
    assert result.success, result.error
    assert result.route_strategy in ("python", "minplus")

    words = fab.bitstream(result)
    assert len(words) > 0
    # all three accepted cfg forms agree: PnRResult, edge list, vector
    assert fab.bitstream(result.route_edges()) == words
    cfg = fab.fabric().route_to_config(result.route_edges())
    assert fab.bitstream(cfg) == words

    T = 10
    x = np.arange(7, 7 + T, dtype=np.int32)
    outs = fab.emulate(result, {"in0": x}, cycles=T)
    y = np.asarray(outs[result.placement["out0"]])
    lat = int(np.nonzero(y)[0][0])
    assert list(y[lat:lat + 4]) == list(x[:4] + 3)


def test_compiled_fabric_backend_memoized():
    fab = canal.compile(InterconnectSpec(**SMOKE))
    assert fab.fabric() is fab.fabric()
    assert fab.resources() is fab.resources()
    assert fab.resources(2.0) is not fab.resources(4.0)


def test_custom_core_fn_marks_uncacheable():
    fab = canal.compile(InterconnectSpec(**SMOKE),
                        core_fn=lambda x, y, w, h: None)
    assert not fab.cacheable
    assert canal.compile(InterconnectSpec(**SMOKE)).cacheable


# ---------------------------------------------------------------------------
# Executor spec-digest caching
# ---------------------------------------------------------------------------

def test_executor_key_canonicalization():
    from repro.core.dse import SweepExecutor

    kw = dict(SMOKE)
    spec = InterconnectSpec(**kw)
    assert SweepExecutor._key(kw) == SweepExecutor._key(spec)
    assert SweepExecutor._key(kw) == ("spec", spec.digest())
    # spellings that used to produce distinct raw-kwargs keys now collapse
    assert SweepExecutor._key(dict(kw, sb_type="wilton")) == \
        SweepExecutor._key(dict(kw, sb_type=SwitchBoxType.WILTON))


def test_executor_key_rejects_callables_with_clear_error():
    from repro.core.dse import SweepExecutor

    with pytest.raises(TypeError, match="callable"):
        SweepExecutor._key(dict(width=4, core_fn=lambda *a: None))


def test_executor_caches_hit_across_spellings():
    from repro.core.dse import SweepExecutor

    ex = SweepExecutor(apps={}, emulate_cycles=0)
    ic1 = ex.interconnect(**SMOKE)
    ic2 = ex.interconnect(InterconnectSpec(**SMOKE))
    ic3 = ex.interconnect(**dict(SMOKE, sb_type="wilton"))
    assert ic1 is ic2 is ic3


def test_executor_caches_shared_across_execution_knobs():
    """Points differing only in execution knobs (router strategy etc.)
    compile to the same hardware: the IR cache must not split."""
    from repro.core.dse import SweepExecutor

    spec = InterconnectSpec(**SMOKE)
    py = spec.replace(route_strategy="python")
    mp = spec.replace(route_strategy="minplus", emulate_io_chunk=4)
    assert py.digest() != mp.digest()                # records distinguish
    assert py.hardware_digest() == mp.hardware_digest() == spec.digest()
    ex = SweepExecutor(apps={}, emulate_cycles=0)
    ic = ex.interconnect(py)
    assert ic is ex.interconnect(mp)
    # the shared IR's stamped identity is the hardware's, not whichever
    # knob variant happened to compile it first
    assert ic.params["spec_digest"] == spec.hardware_digest()
    assert ic.spec == spec.hardware_spec()


def test_run_point_spec_equals_kwargs():
    """One design point through the spec path and the legacy kwargs path:
    identical deterministic record fields and shared caches."""
    from repro.core.dse import SweepExecutor
    from repro.core.pnr.app import app_pointwise

    kw = dict(width=6, height=6, num_tracks=4, io_ring=True,
              reg_density=1.0)
    ex = SweepExecutor(apps={"pw": lambda: app_pointwise(1)}, sa_steps=20,
                       sa_batch=8, emulate_cycles=6, max_workers=1)
    rec_kw = ex.run_point(kw, {"tag": 1})
    rec_spec = ex.run_point(InterconnectSpec(**kw), {"tag": 1})
    assert len(ex._ic_cache) == 1                    # one shared entry
    assert rec_kw["spec_digest"] == rec_spec["spec_digest"]
    for f in ("success", "critical_path_ns", "wirelength",
              "route_iterations", "route_strategy"):
        assert rec_kw["apps"]["pw"][f] == rec_spec["apps"]["pw"][f]
    assert rec_kw["apps"]["pw"]["emulation"]["out_checksum"] == \
        rec_spec["apps"]["pw"]["emulation"]["out_checksum"]
    assert rec_kw["sb_area"] == rec_spec["sb_area"]


# ---------------------------------------------------------------------------
# Route-strategy knob (auto threshold)
# ---------------------------------------------------------------------------

def test_auto_min_tiles_env_and_spec_override(monkeypatch):
    from repro.core.pnr.route import auto_min_tiles_threshold

    monkeypatch.delenv("CANAL_AUTO_MIN_TILES", raising=False)
    assert auto_min_tiles_threshold() == 49
    monkeypatch.setenv("CANAL_AUTO_MIN_TILES", "12")
    assert auto_min_tiles_threshold() == 12
    assert auto_min_tiles_threshold(override=7) == 7
    monkeypatch.setenv("CANAL_AUTO_MIN_TILES", "not-a-number")
    assert auto_min_tiles_threshold() == 49


def test_auto_strategy_resolved_and_recorded():
    """With strategy "auto" the resolved engine lands on the PnR result:
    a 4x4 (16 tiles) resolves to python at the default threshold and to
    minplus when the spec lowers it below 16."""
    from repro.core.pnr.app import app_pointwise

    app = app_pointwise(1)
    fab = canal.compile(InterconnectSpec(route_strategy="auto", **SMOKE))
    r = fab.place_and_route(app, alphas=(2.0,), sa_steps=20, sa_batch=8)
    assert r.success and r.route_strategy == "python"

    low = canal.compile(InterconnectSpec(route_strategy="auto",
                                         auto_min_tiles=4, **SMOKE))
    r2 = low.place_and_route(app, alphas=(2.0,), sa_steps=20, sa_batch=8)
    assert r2.success and r2.route_strategy == "minplus"
    assert r2.timing["critical_path_ns"] == \
        pytest.approx(r.timing["critical_path_ns"], rel=0.10)
