"""CPU tests of the readings taken from the program's own spans: idle
gaps named by ``canal:`` spans on a synthetic trace, and the per-point
figures of ``program_trace.py`` from a small run with the device check
bypassed. Nothing here touches a TPU."""
from __future__ import annotations

import copy
import importlib.util
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

from canalbench import device, registry, stage_gaps  # noqa: E402

PROGRAM = ("jit_s", "place_cpu_s", "route_cpu_s", "device_wait_s")


def test_stage_gaps_synthetic():
    ms = 1_000_000
    devices = {"/device:TPU:0": [("fusion.1", 10 * ms, 30 * ms),
                                 ("copy", 70 * ms, 80 * ms)]}
    harness = [("window", 0, 100 * ms), ("place", 30 * ms, 70 * ms)]
    program = [("point", 0, 100 * ms), ("route.app", 0, 10 * ms),
               ("place.detailed", 30 * ms, 70 * ms),
               ("device.wait", 40 * ms, 70 * ms)]
    rows = stage_gaps.stage_gaps(devices, harness, program)
    # gaps 0-10 (route.app), 30-70 (midpoint 50: device.wait), 80-100
    assert dict(map(tuple, rows)) == {
        "route.app": pytest.approx(0.010),
        "device.wait": pytest.approx(0.040),
        "point": pytest.approx(0.020)}
    assert rows[0][0] == "device.wait"
    assert stage_gaps.stage_gaps(devices, harness, [])[0] == [
        "none", pytest.approx(0.070)]
    assert "device.wait" in stage_gaps.table(rows).splitlines()[0]
    with pytest.raises(ValueError):
        stage_gaps.stage_gaps(devices, harness[1:], program)


def test_program_spans_read_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.core import trace

    f = jax.jit(lambda x: x + 1)
    with trace.recording():
        jax.profiler.start_trace(str(tmp_path))
        with trace.span("point", tag="p"):
            with trace.span("device.wait"):
                f(jnp.ones(4)).block_until_ready()
        jax.profiler.stop_trace()
    spans = stage_gaps.read_program_spans(
        stage_gaps.tracing.find_xplane(str(tmp_path)))
    assert sorted(name for name, _, _ in spans) == ["device.wait", "point"]
    (_, a, b), = [s for s in spans if s[0] == "device.wait"]
    (_, pa, pb), = [s for s in spans if s[0] == "point"]
    assert pa <= a < b <= pb


def _load_script():
    spec = importlib.util.spec_from_file_location(
        "program_trace", os.path.join(BENCH_DIR, "program_trace.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_program_figures_from_a_small_run(monkeypatch):
    script = _load_script()
    cfg = copy.deepcopy(registry.config(registry.load_benchmark(),
                                        "canal_dse_8x8"))
    cfg["spec"].update(width=6, height=6, num_tracks=4,
                       place_strategy="batched", route_strategy="minplus")
    cfg["apps"] = {"pointwise": cfg["apps"]["pointwise"]}
    cfg["grid"] = {"num_tracks": [3, 4], "sb_type": ["wilton"],
                   "sb_sides": [4], "cb_sides": [4]}
    traffic = dict(registry.traffic("search"), in_flight=2)
    monkeypatch.setattr(registry, "config", lambda *a, **k: cfg)
    monkeypatch.setattr(registry, "traffic", lambda *a, **k: traffic)
    monkeypatch.setattr(script.bench, "require",
                        lambda devices, chips: device.describe(devices))
    monkeypatch.setattr(script.bench, "enable_compile_cache",
                        lambda root: "off")
    args = script.argparse.Namespace(workload="dse8.search",
                                     seed=2 ** 31 + 17, seconds=0.1,
                                     trace=0, record=1)
    result = script.measure(args)
    assert result["correct"], result["checks"]
    assert result["attempted"] == 2
    for name in PROGRAM:
        value = result["program"][name]
        assert isinstance(value, float) and value >= 0, (name, value)
    assert result["program"]["place_cpu_s"] > 0
    assert result["program"]["route_cpu_s"] > 0
    assert result["program"]["device_wait_s"] > 0
    assert result["spans"]["point"]["n"] == 1.0
    assert set(result["metrics"]) == {"points_per_s", "point_p90_s",
                                      "setup_s"}
    # the harness is left as it was found
    assert registry.generator.__module__ == registry.__name__
