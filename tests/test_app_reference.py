"""Sequential applications against the plain reference interpreter.

Every default app is placed, routed and emulated through the normal
path, and its outputs on seeded random stimulus must equal
``repro.core.pnr.reference.evaluate`` with each connection delayed by
the interconnect registers its route crosses. App registers (absorbed
into a PE input or placed as a ``pass`` PE) and memories are one cycle
on the fabric, and static timing cuts its paths at them as the chip
benchmark's independent check (``benchmarks/chip/canalbench``) does.
"""
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import canal
from repro.core.dse import SweepExecutor
from repro.core.lowering import compile_interconnect
from repro.core.pnr import RoutingResources, place_and_route, reference
from repro.core.pnr.app import BENCH_APPS, AppGraph, app_fir
from repro.core.pnr.driver import PnRResult
from repro.core.pnr.packing import pack
from repro.core.pnr.route import RoutedNet, RoutingResult
from repro.core.trace import recording
from repro.fabric import AppEmulator, run_apps_batch
from test_batched_dse import _east_route

BENCH_CHIP = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                          "chip")
T = 16
PNR = dict(alphas=(2.0,), sa_steps=40, sa_batch=8, seed=11)
FABRICS = {
    "8x8_mem4": dict(width=8, height=8, num_tracks=5, mem_columns=(4,)),
    "6x6": dict(width=6, height=6, num_tracks=4),
}
#: apps that place at 6x6: stencil needs a memory tile
CASES = ([("8x8_mem4", a) for a in BENCH_APPS]
         + [("6x6", a) for a in BENCH_APPS if a != "stencil"])


class _Routed:
    """One fabric, its lowered model and each app's PnR, made once."""

    def __init__(self, size):
        spec = canal.InterconnectSpec(sb_type="wilton", io_ring=True,
                                      reg_density=1.0, **FABRICS[size])
        self.spec = spec
        self.ic = canal.compile(spec, analyze="off").interconnect
        self.fab = compile_interconnect(self.ic)
        self.res = RoutingResources(self.ic)
        self._pnr = {}

    def pnr(self, name):
        if name not in self._pnr:
            r = place_and_route(self.ic, BENCH_APPS[name](),
                                resources=self.res, **PNR)
            assert r.success, r.error
            self._pnr[name] = r
        return self._pnr[name]


_FABS = {}


def routed(size) -> _Routed:
    if size not in _FABS:
        _FABS[size] = _Routed(size)
    return _FABS[size]


def _stimulus(app, seed, cycles=T, batch=()):
    rng = np.random.default_rng(seed)
    return {n: rng.integers(0, 1 << 16, batch + (cycles,))
            for n, i in app.instances.items() if i.kind == "io_in"}


def _on_fabric(r, stim):
    return {r.placement[n]: np.asarray(v, np.int32) for n, v in stim.items()}


def _expected(r, stim, cycles=T):
    return reference.evaluate(r.packed.app, stim, cycles,
                              reference.route_delays(r.packed, r))


def _app_data(app):
    return {"instances": [[n, i.kind, i.op, i.const]
                          for n, i in app.instances.items()],
            "nets": [[n.name, list(n.src), [list(s) for s in n.sinks]]
                     for n in app.nets]}


@pytest.mark.parametrize("size,name", CASES)
def test_app_emulates_as_reference(size, name):
    f = routed(size)
    r = f.pnr(name)
    stim = _stimulus(r.packed.app, seed=CASES.index((size, name)))
    outs = AppEmulator.from_pnr(f.fab, r.packed, r).run(
        _on_fabric(r, stim), T)
    want = _expected(r, stim)
    assert want
    for inst, words in want.items():
        np.testing.assert_array_equal(outs[r.placement[inst]], words,
                                      err_msg=f"{name} {inst}")


def test_fir_impulse_response():
    f = routed("8x8_mem4")
    r = f.pnr("fir")
    assert not any(reference.route_delays(r.packed, r).values())
    x = np.zeros(8, np.int32)
    x[0] = 1
    emu = AppEmulator.from_pnr(f.fab, r.packed, r)
    y = emu.run({r.placement["in0"]: x}, 8)[r.placement["out0"]]
    np.testing.assert_array_equal(y, [1, 2, 3, 4, 0, 0, 0, 0])
    np.testing.assert_array_equal(
        reference.evaluate(app_fir(4), {"in0": x}, 8)["out0"], y)


@pytest.mark.parametrize("use_pallas,fused", [(False, True), (False, False),
                                              (True, True)])
def test_run_apps_batch_matches_per_app_with_delays(use_pallas, fused):
    """A batch of fir, stencil and pointwise (delayed and plain ports in
    one batch) equals each app's own ``run``, on every engine."""
    f = routed("8x8_mem4")
    fab = compile_interconnect(f.ic, use_pallas=use_pallas)
    names = ("fir", "stencil", "pointwise")
    rs = [f.pnr(n) for n in names]
    emus = [AppEmulator.from_pnr(fab, r.packed, r) for r in rs]
    stims = [_stimulus(r.packed.app, seed=k) for k, r in enumerate(rs)]
    ins = [_on_fabric(r, s) for r, s in zip(rs, stims)]
    ext = np.stack([e.ext_stream(i, T) for e, i in zip(emus, ins)])
    obs = np.asarray(fab.run_batch(
        jnp.stack([e.config for e in emus]), jnp.asarray(ext),
        pe_cfgs={k: jnp.stack([e.pe_cfg[k] for e in emus])
                 for k in emus[0].pe_cfg},
        depth=np.array([e.depth for e in emus]), fused=fused))
    for b, (e, i, r, s) in enumerate(zip(emus, ins, rs, stims)):
        single = e.run(i, T)
        for coord, k in e.io_index.items():
            np.testing.assert_array_equal(obs[b, :, k], single[coord])
        for inst, words in _expected(r, s).items():
            np.testing.assert_array_equal(obs[b, :, e.io_index[
                r.placement[inst]]], words)
    batched = run_apps_batch(emus, ins, T)
    for e, i, got in zip(emus, ins, batched):
        for coord, words in e.run(i, T).items():
            np.testing.assert_array_equal(got[coord], words)


@pytest.mark.parametrize("name", ["fir", "pointwise"])
def test_streamed_engine_equal_or_refuses(name):
    """The streamed engine carries no PE input registers: it refuses an
    app with delayed ports and stays bit-identical on one without."""
    f = routed("8x8_mem4")
    fab = compile_interconnect(f.ic, use_pallas=True)
    r = f.pnr(name)
    emu = AppEmulator.from_pnr(fab, r.packed, r)
    ins = _on_fabric(r, _stimulus(r.packed.app, seed=3))
    if r.packed.delayed_ports():
        with pytest.raises(NotImplementedError):
            run_apps_batch([emu], [ins], T, io_chunk=4)
        return
    got, = run_apps_batch([emu], [ins], T, io_chunk=4)
    for coord, words in emu.run(ins, T).items():
        np.testing.assert_array_equal(got[coord], words)


@pytest.mark.parametrize("name,cut", [("fir", "absorbed register d3"),
                                      ("stencil", "memory lb")])
def test_sta_cuts_at_registers_and_memories(name, cut):
    """The record's critical path equals the benchmark check's sound
    model on the same routes: ``fir``'s absorbed register and
    ``stencil``'s memory each end a path and launch the next."""
    sys.path.insert(0, BENCH_CHIP)
    from canalbench import check_pnr, design

    f = routed("8x8_mem4")
    r = f.pnr(name)
    d = design.app_result(r)
    data = _app_data(BENCH_APPS[name]())
    pack_ = check_pnr.Packing(data, d["placement"])
    if name == "fir":
        assert "d3" in pack_.absorbed and r.packed.reg_ports
    else:
        assert any(i.kind == "mem" for i in r.packed.placeable.values())
    spec = {"width": f.spec.width, "height": f.spec.height,
            "mem_columns": list(f.spec.mem_columns), "io_ring": True,
            "track_width": 16}
    row = check_pnr.check_app(
        design.graph(f.ic), spec, data, d["placement"], design.routes_of(d),
        d["const_ports"], d["reg_ports"],
        {"wirelength": r.wirelength,
         "critical_path_ns": r.timing["critical_path_ns"]})
    assert row == {"bad_packing": 0, "bad_placements": 0, "bad_routes": 0,
                   "overused_nodes": 0, "wirelength_gap": 0,
                   "critical_path_gap": 0.0}, cut


def _pipe_app():
    """in -> reg -> mem -> (+5 on a PE) -> out, with an extra register
    chain (reg -> reg) feeding a second output."""
    g = AppGraph()
    for name, kind in (("in0", "io_in"), ("r0", "reg"), ("r1", "reg"),
                       ("lb", "mem"), ("out0", "io_out"),
                       ("out1", "io_out")):
        g.add(name, kind)
    g.add("k", "const", op="const", const=5)
    g.add("p", "pe", op="add")
    g.connect("in0", "io_out", ("r0", "in"), ("lb", "wdata"))
    g.connect("r0", "out", ("r1", "in"))
    g.connect("r1", "out", ("out1", "io_in"))
    g.connect("lb", "rdata", ("p", "data0"))
    g.connect("k", "out", ("p", "data1"))
    g.connect("p", "res0", ("out0", "io_in"))
    return g


def test_reference_semantics_and_connection_delays():
    x = np.arange(1, 9) * 1000
    out = reference.evaluate(_pipe_app(), {"in0": x}, 8)
    np.testing.assert_array_equal(out["out1"], [0, 0] + list(x[:6]))
    np.testing.assert_array_equal(out["out0"], [5] + list(x[:7] + 5))
    late = reference.evaluate(_pipe_app(), {"in0": x}, 8,
                              {(("p", "res0"), ("out0", "io_in")): 2})
    np.testing.assert_array_equal(late["out0"], [0, 0] + list(out["out0"][:6]))
    # independent runs on a leading axis, 16-bit words
    both = reference.evaluate(_pipe_app(), {"in0": np.stack([x, x + 65536])},
                              8)
    np.testing.assert_array_equal(both["out0"][0], both["out0"][1])


def test_route_registers_delay_the_connection():
    """A connection routed through track registers (a manual east route
    across the array through every one) is late by one cycle for each,
    on the fabric and in the reference."""
    f = routed("6x6")
    app = AppGraph()
    app.add("in0", "io_in")
    app.add("out0", "io_out")
    app.connect("in0", "io_out", ("out0", "io_in"))
    packed = pack(app)
    edges = _east_route(f.ic)
    ids = f.res.node_id
    tree = {ids[c]: ids[p] for p, c in edges}
    src, sink = ids[edges[0][0]], ids[edges[-1][1]]
    result = PnRResult(
        success=True, placement={"in0": (0, 1), "out0": (5, 1)},
        packed=packed, routing=RoutingResult(
            [RoutedNet(packed.nets[0].name, src, [sink], tree)], 1, [],
            f.res))
    delays = reference.route_delays(packed, result)
    assert delays == {(("in0", "io_out"), ("out0", "io_in")): 5}
    stim = _stimulus(app, seed=5)
    outs = AppEmulator.from_pnr(f.fab, packed, result).run(
        _on_fabric(result, stim), T)
    want = reference.evaluate(app, stim, T, delays)["out0"]
    np.testing.assert_array_equal(outs[(5, 1)], want)
    np.testing.assert_array_equal(want[5:], stim["in0"][:T - 5])


def test_emulate_routed_takes_stimulus_and_records_spans():
    """``SweepExecutor.emulate_routed`` drives a given stimulus (the
    counter stays the default) and records ``emulate.bind``,
    ``emulate.run`` with its sweep count and the slots the chain engine
    gathers a sweep (4P), and ``device.wait``."""
    f = routed("8x8_mem4")
    names = ("fir", "stencil")
    routed_apps = [(n, f.pnr(n).packed, f.pnr(n)) for n in names]
    ex = SweepExecutor(emulate_cycles=T, shard=False, store=False)
    stim = {n: _stimulus(f.pnr(n).packed.app, seed=9) for n in names}
    with recording() as rec:
        got = ex.emulate_routed(f.fab, routed_apps, stimulus=stim)
    counter = ex.emulate_routed(f.fab, routed_apps)
    for n in names:
        r = f.pnr(n)
        depth, outs = got[n]
        for inst, words in _expected(r, stim[n]).items():
            np.testing.assert_array_equal(outs[r.placement[inst]], words)
        count = {i: np.arange(1, T + 1) for i in stim[n]}
        for inst, words in _expected(r, count).items():
            np.testing.assert_array_equal(counter[n][1][r.placement[inst]],
                                          words)
    spans = {s.name: s for s in rec.spans}
    assert {"emulate.bind", "emulate.run", "device.wait"} <= set(spans)
    run = spans["emulate.run"].attrs
    depths = [got[n][0] for n in names]
    assert run == {"lanes": 2, "cycles": T, "app_cycles": 2 * T,
                   "sweeps": max(depths) * T,
                   "slots_per_sweep": 4 * f.fab.num_pe}
    assert spans["device.wait"].parent == spans["emulate.run"].id
