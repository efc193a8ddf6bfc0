"""Batched DSE engine: run_batch ≡ looped run, computed depth ≡
conservative depth, batched app emulation, SweepExecutor caching, and the
long-stream AppEmulator regression."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.edsl import create_uniform_interconnect
from repro.core.graph import IO, NodeKind, Side
from repro.core.lowering import compile_interconnect


@pytest.fixture(scope="module")
def small_ic():
    return create_uniform_interconnect(width=4, height=4, num_tracks=2,
                                       sb_type="wilton", io_ring=True,
                                       reg_density=1.0)


@pytest.fixture(scope="module")
def fabric(small_ic):
    return compile_interconnect(small_ic)


def _random_cases(fab, b, t, seed=0):
    rng = np.random.default_rng(seed)
    cfgs = rng.integers(0, 4, (b, fab.num_config)).astype(np.int32)
    ext = rng.integers(0, 1000, (b, t, fab.num_io)).astype(np.int32)
    return cfgs, ext


@pytest.fixture(scope="module")
def mem_fabric():
    ic = create_uniform_interconnect(width=4, height=4, num_tracks=2,
                                     sb_type="wilton", io_ring=True,
                                     reg_density=1.0, mem_columns=(2,))
    fab = compile_interconnect(ic)
    assert fab.num_mem > 0
    return fab


@pytest.mark.parametrize("kind,t", [("plain", 1), ("plain", 4),
                                    ("plain", 7), ("plain", 16),
                                    ("memory", 9)])
def test_run_batch_matches_looped_run(request, kind, t):
    """B configurations through one run_batch == B serial run calls, for
    stimulus lengths from one cycle up, and on a fabric whose memory
    tiles carry state from cycle to cycle."""
    fab = request.getfixturevalue("fabric" if kind == "plain"
                                  else "mem_fabric")
    cfgs, ext = _random_cases(fab, b=4, t=t, seed=t)
    serial = np.stack([
        np.asarray(fab.run(jnp.asarray(cfgs[i]), jnp.asarray(ext[i]),
                           depth=8))
        for i in range(len(cfgs))])
    batched = np.asarray(fab.run_batch(jnp.asarray(cfgs),
                                       jnp.asarray(ext), depth=8))
    np.testing.assert_array_equal(serial, batched)


def test_run_batch_computed_depth(small_ic, fabric):
    """depth=None resolves the per-config combinational depth and matches
    the fixed conservative bound. Configs come from legal routes: only an
    acyclic active network has a fixpoint, so only there is output
    depth-independent (a random config may wire a combinational loop)."""
    routes = [_east_route(small_ic, y=1), _east_route(small_ic, y=2),
              _east_route(small_ic, y=1, track=1)]
    cfgs = np.stack([fabric.route_to_config(r) for r in routes])
    rng = np.random.default_rng(1)
    ext = rng.integers(0, 1000, (3, 4, fabric.num_io)).astype(np.int32)
    auto = np.asarray(fabric.run_batch(jnp.asarray(cfgs),
                                       jnp.asarray(ext)))
    fixed = np.asarray(fabric.run_batch(jnp.asarray(cfgs),
                                        jnp.asarray(ext), depth=64))
    np.testing.assert_array_equal(auto, fixed)


def _east_route(ic, y=1, track=0):
    # same manual registered east route as test_lowering_fabric
    g = ic.graph(16)
    edges = []
    port = g.get_port(0, y, "io_out")
    sb_out = g.get_sb(0, y, Side.EAST, track, IO.SB_OUT)
    edges.append((port, sb_out))
    cur = sb_out
    w = ic.dims()[0]
    for x in range(1, w):
        rmux = [n for n in cur.fan_out if n.kind == NodeKind.REG_MUX][0]
        reg = [n for n in cur.fan_out if n.kind == NodeKind.REGISTER][0]
        edges += [(cur, reg), (reg, rmux)]
        sb_in = rmux.fan_out[0]
        edges.append((rmux, sb_in))
        if x < w - 1:
            nxt = g.get_sb(x, y, Side.EAST, track, IO.SB_OUT)
            edges.append((sb_in, nxt))
            cur = nxt
        else:
            edges.append((sb_in, g.get_port(x, y, "io_in")))
    return edges


def test_depth_for_route_tighter_and_equivalent(small_ic, fabric):
    """Computed route depth is <= the conservative bound and produces
    bit-identical emulation."""
    from repro.fabric import AppEmulator

    edges = _east_route(small_ic)
    computed = fabric.depth_for_route(edges)
    conservative = len(edges) + 4
    assert 1 <= computed <= conservative

    emu_new = AppEmulator(fabric, edges, pe_ops={})
    emu_old = AppEmulator(fabric, edges, pe_ops={}, depth=conservative)
    assert emu_new.depth == computed
    T = 10
    ins = {(0, 1): np.arange(100, 100 + T, dtype=np.int32)}
    a, b = emu_new.run(ins, T), emu_old.run(ins, T)
    for coord in a:
        np.testing.assert_array_equal(a[coord], b[coord])


def test_app_emulator_truncates_long_stream(small_ic, fabric):
    """Regression: an input stream longer than the emulation window used
    to raise on broadcast; it must truncate to ``cycles``."""
    from repro.fabric import AppEmulator

    edges = _east_route(small_ic)
    emu = AppEmulator(fabric, edges, pe_ops={})
    T = 6
    out = emu.run({(0, 1): np.arange(100, dtype=np.int32)}, T)
    assert all(len(v) == T for v in out.values())
    short = emu.run({(0, 1): np.arange(100, 100 + T, dtype=np.int32)}, T)
    lng = emu.run({(0, 1): np.arange(100, 200, dtype=np.int32)}, T)
    for coord in short:
        np.testing.assert_array_equal(short[coord], lng[coord])


def test_run_apps_batch_matches_per_app(small_ic, fabric):
    """Several apps on one fabric as one batch == per-app emulation."""
    from repro.fabric import AppEmulator, run_apps_batch

    e1 = AppEmulator(fabric, _east_route(small_ic, y=1), pe_ops={})
    e2 = AppEmulator(fabric, _east_route(small_ic, y=2), pe_ops={})
    T = 8
    i1 = {(0, 1): np.arange(10, 10 + T, dtype=np.int32)}
    i2 = {(0, 2): np.arange(50, 50 + T, dtype=np.int32)}
    outs = run_apps_batch([e1, e2], [i1, i2], T)
    ref = [e1.run(i1, T), e2.run(i2, T)]
    for got, want in zip(outs, ref):
        for coord in want:
            np.testing.assert_array_equal(got[coord], want[coord])


def test_run_apps_batch_rejects_mixed_fabrics(small_ic, fabric):
    from repro.fabric import AppEmulator, run_apps_batch

    other = compile_interconnect(small_ic)
    e1 = AppEmulator(fabric, _east_route(small_ic, y=1), pe_ops={})
    e2 = AppEmulator(other, _east_route(small_ic, y=2), pe_ops={})
    with pytest.raises(ValueError, match="shared fabric"):
        run_apps_batch([e1, e2], [{}, {}], 4)


def test_pipelined_emulation_matches_inline():
    """The async PnR/emulation pipeline (deferred per-device dispatch,
    futures joined before records return) must produce the same records
    as inline emulation, emulation report included."""
    from repro.core.dse import SweepExecutor
    from repro.core.pnr.app import app_pointwise

    kw1 = dict(width=6, height=6, num_tracks=4, io_ring=True,
               reg_density=1.0)
    kw2 = dict(width=6, height=6, num_tracks=3, io_ring=True,
               reg_density=1.0)
    points = [(kw1, {"num_tracks": 4}), (kw2, {"num_tracks": 3})]
    recs = {}
    for pipelined in (False, True):
        ex = SweepExecutor(apps={"pw1": lambda: app_pointwise(1)},
                           sa_steps=20, sa_batch=8, emulate_cycles=8,
                           max_workers=2,
                           pipeline_emulation=pipelined)
        recs[pipelined] = ex.run_points(points)
        assert not ex._pending          # all futures joined
    for sync_rec, async_rec in zip(recs[False], recs[True]):
        a, b = sync_rec["apps"]["pw1"], async_rec["apps"]["pw1"]
        assert a["success"] and b["success"]
        assert "emulation" in a and "emulation" in b
        assert a["emulation"]["out_checksum"] == \
            b["emulation"]["out_checksum"]
        assert a["emulation"]["depth"] == b["emulation"]["depth"]


def test_sweep_executor_point_with_batched_emulation(tmp_path):
    """One design point end to end on the executor: PnR, shared caches,
    batched emulation report, JSON persistence."""
    from repro.core.dse import SweepExecutor
    from repro.core.pnr.app import app_pointwise

    ex = SweepExecutor(apps={"pw1": lambda: app_pointwise(1)},
                       sa_steps=20, sa_batch=8, emulate_cycles=8,
                       max_workers=1)
    kw = dict(width=6, height=6, num_tracks=4, io_ring=True,
              reg_density=1.0)
    recs = ex.run_points([(kw, {"num_tracks": 4})])
    assert len(recs) == 1
    rec = recs[0]
    assert rec["num_tracks"] == 4 and rec["sb_area"] > 0
    app_rec = rec["apps"]["pw1"]
    assert app_rec["success"], app_rec["error"]
    assert app_rec["emulation"]["cycles"] == 8
    assert app_rec["emulation"]["depth"] >= 1
    # caches are shared across points with identical interconnects
    ic1 = ex.interconnect(**kw)
    assert ex.interconnect(**kw) is ic1
    assert ex.resources(ic1, ex._key(kw)) is ex.resources(ic1, ex._key(kw))
    path = ex.save_json(str(tmp_path / "sweep.json"))
    import json
    with open(path) as f:
        assert json.load(f)[0]["num_tracks"] == 4
