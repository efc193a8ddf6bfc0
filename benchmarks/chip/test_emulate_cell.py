"""CPU tests of the emulation cell (``amber_full.emulate``) at a small
stand-in size: an 8x8 fabric with one memory column, T = 8.

The registry finds the cell's configuration, mix, generator and
readers; the program's outputs pass ``check_emulate``; and the timed
path broken underneath in three ways (registers as wires, one output
word changed, the previous batch's outputs returned) comes out not
correct. A program without the stimulus signature stops before PnR.
Nothing here touches a TPU.
"""
from __future__ import annotations

import copy
import os
import sys
from unittest import mock

import numpy as np
import pytest

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from canalbench import cell, check_emulate, registry  # noqa: E402

CELL = "amber_full.emulate"
READERS = ("emulate_bind_s.emulate", "emulate_run_s.emulate",
           "sweeps.emulate", "device_idle.emulate", "compiles.emulate")


def test_registry_finds_the_cell():
    bench = registry.load_benchmark()
    w = registry.cell(bench, CELL)
    assert w["chips"] == 1
    cfg = registry.config(bench, w["config"])
    assert cfg["name"] == "canal_amber_full"
    spec = cfg["spec"]                 # Amber's 32x16 inside an IO ring
    assert (spec["width"], spec["height"], spec["io_ring"]) == (34, 18, True)
    core = [(x, y) for x in range(1, 33) for y in range(1, 17)]
    mems = sum(x in spec["mem_columns"] for x, _ in core)
    assert (len(core) - mems, mems) == (384, 128)
    assert set(cfg["apps"]) == {"pointwise", "tree_reduce", "fir",
                                "stencil", "butterfly"}
    tr = registry.traffic(w["traffic"])
    assert hasattr(registry.generator(tr["generator"]), "run")
    names = {m["name"] for m in registry.metrics_of_cell(bench, CELL,
                                                         "per_layer")}
    assert names == set(READERS)
    for name in READERS:
        assert callable(registry.metric_reader(name))
    e2e = {m["name"] for m in registry.metrics_of_cell(bench, CELL,
                                                       "end_to_end")}
    assert e2e == {"points_per_s", "point_p90_s", "setup_s"}


def small_ctx(cache_dir, seed=2 ** 31 + 41):
    bench = registry.load_benchmark()
    cfg = copy.deepcopy(registry.config(bench, "canal_amber_full"))
    cfg["spec"].update(width=8, height=8, mem_columns=[4])
    traffic = dict(registry.traffic("emulate"), cycles=8)
    return cell.Ctx(cell=registry.cell(bench, CELL), config=cfg,
                    traffic=traffic, seed=seed, seconds=0.2, trace=False,
                    bench_dir=str(cache_dir), repo_root=registry.repo_root())


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    """Routed designs shared by the runs of this module."""
    return tmp_path_factory.mktemp("emulate-cell")


def run_cell(cache_dir):
    ctx = small_ctx(cache_dir)
    try:
        out = registry.generator("emulate").run(ctx)
    finally:
        ctx.rec.close()
    return out, out["check"]()


def test_program_outputs_pass(cache_dir):
    out, table = run_cell(cache_dir)
    assert cell.passed(table), table
    assert table["wrong_words"]["value"] == 0
    assert table["apps_missing"]["value"] == 0
    assert table["unbalanced_paths"]["value"] == 0
    assert out["attempted"] >= 1 and out["failed"] == 0
    r = dict(out["readings"], trace=None)
    values = {n: registry.metric_reader(n)(r) for n in READERS}
    assert values["emulate_bind_s.emulate"] > 0
    assert values["emulate_run_s.emulate"] > 0
    assert values["sweeps.emulate"] > 0
    assert values["sweeps.emulate"] % 8 == 0          # depth x T
    assert values["compiles.emulate"] == 0            # warmed in set-up
    assert values["device_idle.emulate"] is None      # untraced
    assert set(out["e2e"]) == {"points_per_s", "point_p90_s"}
    assert os.listdir(cache_dir / "cache")


def _registers_as_wires():
    from repro.fabric import AppEmulator

    bind = AppEmulator.from_pnr.__func__

    def wires(cls, *args, **kwargs):
        emu = bind(cls, *args, **kwargs)
        emu.pe_cfg = dict(emu.pe_cfg,
                          reg_mask=emu.pe_cfg["reg_mask"] * 0)
        return emu

    return mock.patch.object(AppEmulator, "from_pnr", classmethod(wires))


def _emulate_edit(edit):
    from repro.core.dse import SweepExecutor

    inner = SweepExecutor.emulate_routed

    def edited(self, *args, **kwargs):
        return edit(inner(self, *args, **kwargs))

    return mock.patch.object(SweepExecutor, "emulate_routed", edited)


def _one_word_changed():
    """One word of ``fir``'s output in the first timed batch (the call
    after the set-up's warm batch), at every IO tile, so at its one
    output."""
    calls = []

    def edit(outs):
        if outs:
            calls.append(1)
        if len(calls) == 2 and outs:
            depth, words = outs["fir"]
            words = {xy: np.asarray(w).copy() for xy, w in words.items()}
            for w in words.values():      # fir's one output among them
                w[3] ^= 1
            outs = dict(outs, fir=(depth, words))
        return outs

    return _emulate_edit(edit)


def _previous_batch():
    last = []

    def edit(outs):
        if not outs:
            return outs
        last.append(outs)
        return last[-2] if len(last) > 1 else outs

    return _emulate_edit(edit)


@pytest.mark.parametrize("fault", ["registers_as_wires", "one_word_changed",
                                   "previous_batch"])
def test_planted_faults_fail(cache_dir, fault):
    patch = {"registers_as_wires": _registers_as_wires,
             "one_word_changed": _one_word_changed,
             "previous_batch": _previous_batch}[fault]()
    with patch:
        _, table = run_cell(cache_dir)
    assert not cell.passed(table), table
    assert table["wrong_words"]["value"] > 0
    if fault == "one_word_changed":
        assert table["wrong_words"]["value"] == 1


def test_program_without_stimulus_stops_before_pnr(tmp_path):
    from repro.core.dse import SweepExecutor

    def old(self, fab, routed, device=None, io_chunk=None):
        return {}

    pnr = mock.Mock(side_effect=AssertionError("PnR ran"))
    with mock.patch.object(SweepExecutor, "emulate_routed", old), \
            mock.patch("repro.core.dse.place_and_route", pnr):
        ctx = small_ctx(tmp_path)
        with pytest.raises(TypeError):
            registry.generator("emulate").run(ctx)
        ctx.rec.close()
    pnr.assert_not_called()


def test_check_counts_delays_and_state():
    """``interpret`` on a register, a memory and a delayed connection."""
    app = {"instances": [["in0", "io_in", "add", 0], ["r", "reg", "add", 0],
                         ["lb", "mem", "add", 0], ["p", "pe", "sub", 0],
                         ["out0", "io_out", "add", 0],
                         ["out1", "io_out", "add", 0]],
           "nets": [["n0", ["in0", "io_out"], [["r", "in"], ["lb", "wdata"],
                                               ["p", "data0"]]],
                    ["n1", ["r", "out"], [["p", "data1"]]],
                    ["n2", ["lb", "rdata"], [["out1", "io_in"]]],
                    ["n3", ["p", "res0"], [["out0", "io_in"]]]]}
    x = np.array([[5, 9, 2, 7]])
    plain = check_emulate.interpret(app, {"in0": x}, 4, {})
    np.testing.assert_array_equal(plain["out0"], [[5, 4, 0xFFF9, 5]])
    np.testing.assert_array_equal(plain["out1"], [[0, 5, 9, 2]])
    late = check_emulate.interpret(app, {"in0": x}, 4,
                                   {("in0", "io_out", "r", "in"): 1})
    np.testing.assert_array_equal(late["out0"], [[5, 9, 0xFFFD, 0xFFFE]])
    np.testing.assert_array_equal(late["out1"], plain["out1"])


def test_check_counts_unbalanced_paths():
    """A route register on one branch of a reconvergence, or on one of
    two outputs, changes the app's function; one on every path does
    not."""
    app = {"instances": [["in0", "io_in", "add", 0],
                         ["in1", "io_in", "add", 0],
                         ["p", "pe", "add", 0], ["q", "pe", "sub", 0],
                         ["out0", "io_out", "add", 0],
                         ["out1", "io_out", "add", 0]],
           "nets": [["n0", ["in0", "io_out"], [["p", "data0"],
                                               ["q", "data0"]]],
                    ["n1", ["in1", "io_out"], [["p", "data1"],
                                               ["q", "data1"]]],
                    ["n2", ["p", "res0"], [["out0", "io_in"]]],
                    ["n3", ["q", "res0"], [["out1", "io_in"]]]]}
    count = check_emulate.unbalanced_paths
    assert count(app, {}) == 0
    every = {("in0", "io_out", "p", "data0"): 1,
             ("in1", "io_out", "p", "data1"): 1,
             ("in0", "io_out", "q", "data0"): 1,
             ("in1", "io_out", "q", "data1"): 1}
    assert count(app, every) == 0
    assert count(app, {("in0", "io_out", "p", "data0"): 1}) == 2
    assert count(app, {("p", "res0", "out0", "io_in"): 2}) == 1
