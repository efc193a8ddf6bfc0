#!/usr/bin/env python3
"""Smoke test of the DSE main path on a TPU, at Amber scale.

    python chip_smoke.py              # one chip: serve two FULL points
    python chip_smoke.py --chips 4    # four chips: sharded emulation only

One chip: ``canal.serve`` over a fresh, empty result store queries
``cgra_amber.FULL`` (32x32, five 16-bit tracks, memory columns, IO ring)
and the same fabric with four tracks, each against the five
``BENCH_APPS`` with 32 emulated cycles, then repeats the query. It
checks that every app routes with the min-plus router and the batched
placer (the compiled Pallas kernels of ``repro.kernels.minplus`` and
``repro.kernels.hpwl``), that the IR and routed analyses find no error,
that the emulated outputs on the TPU equal the same emulation on the
host CPU bit for bit and match the stored ``out_checksum``, and that
the repeated query is served from the store alone.

Four chips: ``FabricModule.run_batch`` at FULL on 32 random
configurations (random PE programs, 8-16 sweeps per lane) x 32 cycles,
sharded over the four chips, against the same batch on one chip, bit
for bit.

The first line names the device; the script exits non-zero unless JAX's
first device is a TPU. Host wall times per phase follow, labelled with
the device. The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

CYCLES = 32          # emulated cycles per app (and per sharded config)
SHARD_BATCH = 32     # random configurations of the four-chip phase


def _say(label: str, phase: str, seconds: float, note: str = "") -> None:
    print(f"[{label}] {phase}: {seconds} s{' ' + note if note else ''}",
          flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def serve_phase(label: str) -> None:
    """Two cold FULL design points through ``canal.serve``, then the
    same query warm."""
    import jax
    import numpy as np

    import canal
    from repro.configs import cgra_amber
    from repro.core.dse import out_checksum
    from repro.core.pnr.app import BENCH_APPS

    specs = [cgra_amber.FULL, cgra_amber.FULL.replace(num_tracks=4)]
    with tempfile.TemporaryDirectory(prefix="canal-smoke-") as store, \
            canal.serve(store=store, apps=BENCH_APPS,
                        emulate_cycles=CYCLES) as svc:
        ex = svc.executor
        # keep what the served path emulated, so that it can be replayed
        # on the CPU below
        served_emulation = ex.emulate_routed
        emulated = []

        def emulate_and_keep(fab, routed, device=None):
            t0 = time.perf_counter()
            outs = served_emulation(fab, routed, device=device)
            emulated.append((fab, routed, outs, time.perf_counter() - t0))
            return outs

        ex.emulate_routed = emulate_and_keep

        for spec in specs:
            tag = f"num_tracks={spec.num_tracks}"
            t0 = time.perf_counter()
            ic = ex.interconnect(spec)
            _say(label, f"compile {tag}", time.perf_counter() - t0)
            t0 = time.perf_counter()
            report = ex.analysis_report(spec, ic)
            _say(label, f"analysis {tag}", time.perf_counter() - t0,
                 f"counts={report.counts()}")
            _check(report.ok(), f"IR analysis found errors at {tag}")

        t0 = time.perf_counter()
        recs = svc.query(specs)
        _say(label, "cold query (2 design points)", time.perf_counter() - t0)
        for spec, rec in zip(specs, recs):
            tag = f"num_tracks={spec.num_tracks}"
            for name, app in rec["apps"].items():
                where = f"{tag} {name}"
                _say(label, f"pnr {where}", app["seconds"],
                     f"route={app['route_strategy']} "
                     f"place={app['place_strategy']} "
                     f"critical_path_ns={app['critical_path_ns']}")
                _check(app["success"], f"{where} did not route: "
                       f"{app['error']}")
                _check(app["route_strategy"] == "minplus",
                       f"{where} routed with {app['route_strategy']}")
                _check(app["place_strategy"] == "batched",
                       f"{where} placed with {app['place_strategy']}")
                errors = app["routed_analysis"]["counts"]["error"]
                _check(errors == 0,
                       f"{where}: routed analysis found {errors} errors")
                _check("emulation" in app, f"{where} was not emulated")

        _check(len(emulated) == len(specs),
               f"{len(emulated)} emulation batches for {len(specs)} points")
        by_hw = {rec["hardware_digest"]: rec for rec in recs}
        cpu = jax.devices("cpu")[0]
        for fab, routed, outs, seconds in emulated:
            rec = by_hw[fab.ic.spec.hardware_digest()]
            tag = f"num_tracks={fab.ic.spec.num_tracks}"
            _say(label, f"emulation {tag} ({len(outs)} apps x {CYCLES} "
                 "cycles)", seconds)
            t0 = time.perf_counter()
            ref = served_emulation(fab, routed, device=cpu)
            _say(f"cpu {cpu.device_kind} x1", f"reference emulation {tag}",
                 time.perf_counter() - t0)
            for name, (_, out) in outs.items():
                where = f"{tag} {name}"
                ref_out = ref[name][1]
                _check(set(out) == set(ref_out), f"{where}: IO sets differ")
                for coord in out:
                    _check(np.array_equal(out[coord], ref_out[coord]),
                           f"{where}: TPU and CPU outputs differ at "
                           f"{coord}")
                stored = rec["apps"][name]["emulation"]["out_checksum"]
                _check(out_checksum(out) == stored,
                       f"{where}: stored out_checksum {stored} != "
                       f"{out_checksum(out)}")
            print(f"[{label}] emulation {tag}: outputs equal the CPU's "
                  "bit for bit and match the stored checksums", flush=True)

        before = svc.stats()
        t0 = time.perf_counter()
        warm = svc.query(specs)
        _say(label, "warm query (2 design points)", time.perf_counter() - t0)
        after = svc.stats()
        _check(after["hits"] - before["hits"] == len(specs),
               "warm query was not served from the store alone")
        _check(after["misses"] == before["misses"], "warm query missed")
        _check(after["executor"]["pnr_computations"]
               == before["executor"]["pnr_computations"],
               "warm query recomputed PnR")
        _check([r["spec_digest"] for r in warm]
               == [r["spec_digest"] for r in recs],
               "warm query returned other design points")


def sharded_phase(label: str, seed: int = 0) -> None:
    """``run_batch`` at FULL: the batch sharded over every device against
    the same batch on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import canal
    from repro.configs import cgra_amber
    from repro.kernels.fabric_chain import PE_OPS

    t0 = time.perf_counter()
    fab = canal.compile(cgra_amber.FULL, analyze="off").fabric()
    _say(label, "compile FULL", time.perf_counter() - t0,
         f"nodes={fab.arrays.num_nodes} config_slots={fab.num_config}")
    rng = np.random.default_rng(seed)
    b, p = SHARD_BATCH, max(fab.num_pe, 1)
    configs = rng.integers(0, 4, (b, fab.num_config), dtype=np.int32)
    ext = rng.integers(0, 1 << 16, (b, CYCLES, fab.num_io), dtype=np.int32)
    pe_cfgs = {"op": rng.integers(0, len(PE_OPS), (b, p), dtype=np.int32),
               "const": rng.integers(0, 1 << 16, (b, p), dtype=np.int32),
               "imm_mask": np.zeros((b, p, 4), np.int32),
               "imm_val": np.zeros((b, p, 4), np.int32)}
    # random per-lane sweep counts, not each config's fixpoint depth: the
    # check is sharded == one device lane for lane, masking included, and
    # random configs' fixpoints (up to ~290 sweeps at FULL) would only
    # make it slower
    depths = rng.integers(8, 17, b, dtype=np.int32)

    def run(shard: bool):
        out = fab.run_batch(jnp.asarray(configs), jnp.asarray(ext),
                            pe_cfgs={k: jnp.asarray(v)
                                     for k, v in pe_cfgs.items()},
                            depth=depths, shard=shard)
        return out.block_until_ready()

    n_dev = len(jax.devices())
    for shard in (True, False):
        what = f"run_batch shard={shard}"
        for attempt in ("first call", "second call"):
            t0 = time.perf_counter()
            out = run(shard)
            _say(label, f"{what} {attempt} (B={b}, T={CYCLES})",
                 time.perf_counter() - t0,
                 f"devices={len(out.sharding.device_set)}")
        if shard:
            sharded = out
        else:
            single = out
    _check(len(sharded.sharding.device_set) == n_dev,
           f"sharded output spans {len(sharded.sharding.device_set)} of "
           f"{n_dev} devices")
    _check(len(single.sharding.device_set) == 1,
           "shard=False output spans more than one device")
    _check(np.array_equal(np.asarray(sharded), np.asarray(single)),
           "sharded emulation differs from the one-device run")
    print(f"[{label}] sharded output over {n_dev} devices equals the "
          "one-device output bit for bit", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served DSE path (default); 4: sharded "
                         "emulation across four chips, nothing else")
    args = ap.parse_args(argv)

    # the CPU backend is the bit-exact reference of the one-chip phase
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    from repro.core import compile_cache
    cache = compile_cache.enable(ROOT)
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device platform={device['platform']} kind={device['kind']} "
          f"count={device['count']} jax={jax.__version__} "
          f"compile_cache={cache}", flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: JAX's first device is {device['platform']}, "
              "not a TPU", file=sys.stderr)
        return 1
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {device['count']}", file=sys.stderr)
        return 1
    label = f"{device['platform']} {device['kind']} x{device['count']}"
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(label)
    else:
        serve_phase(label)
    _say(label, "total", time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
