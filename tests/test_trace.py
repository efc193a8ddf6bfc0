"""The span-and-counter facility (``repro.core.trace``) and the spans the
DSE path records: nesting and self time, tags per thread, the off state,
the compile counter, one cold served point at 6x6, and the stable
``jax.named_scope`` names of the device programs. CPU only."""
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring

from repro.core import trace
from repro.core.pnr.app import BENCH_APPS
from repro.core.spec import InterconnectSpec

#: every span of a cold served point without emulation
POINT_SPANS = {
    "serve.queue", "serve.query", "serve.probe", "point", "hwgen.compile",
    "hwgen.analyze", "hwgen.routed", "route.resources", "pnr",
    "place.pack", "place.io", "place.global", "place.legalize",
    "place.detailed", "route.app", "sta", "store.put", "device.wait"}


def _listeners():
    return len(monitoring._event_duration_secs_listeners)


def test_span_nesting_parent_and_self_time():
    with trace.recording() as rec:
        with trace.span("outer", k=1) as outer:
            time.sleep(0.02)
            with trace.span("inner") as inner:
                time.sleep(0.03)
            outer.set(done=True)
    by_name = {s.name: s for s in rec.spans}
    assert [s.name for s in rec.spans] == ["inner", "outer"]
    assert by_name["outer"].parent is None
    assert by_name["inner"].parent == outer.id
    assert by_name["outer"].attrs == {"k": 1, "done": True}
    rows = rec.summary()
    assert rows["inner"]["n"] == rows["outer"]["n"] == 1
    assert rows["outer"]["self_s"] == pytest.approx(
        rows["outer"]["wall_s"] - (inner.t1 - inner.t0))
    assert rows["outer"]["self_s"] >= 0.02
    assert rows["inner"]["self_s"] == rows["inner"]["wall_s"] >= 0.03
    # sleeping takes wall time, not CPU time
    assert rows["outer"]["cpu_s"] < rows["outer"]["wall_s"]


def test_tags_kept_apart_across_threads():
    barrier = threading.Barrier(2, timeout=30)

    def work(tag):
        with trace.span("point", tag=tag):
            barrier.wait()
            with trace.span("work"):
                time.sleep(0.01)
            barrier.wait()

    with trace.recording() as rec:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in ("a", "b")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
    per = rec.per_tag()
    assert set(per) == {"a", "b"}
    for tag in ("a", "b"):
        assert per[tag]["point"]["n"] == per[tag]["work"]["n"] == 1
    spans = {(s.tag, s.name): s for s in rec.spans}
    for tag in ("a", "b"):
        assert spans[tag, "work"].parent == spans[tag, "point"].id
        assert spans[tag, "work"].thread == spans[tag, "point"].thread
    assert spans["a", "point"].thread != spans["b", "point"].thread
    assert rec.summary(tags={"a"})["work"]["n"] == 1


def test_off_records_nothing_and_listens_to_nothing():
    before = _listeners()
    assert trace.active() is None
    first = trace.span("place.pack")
    assert trace.span("route.app", alpha=2.0) is first
    with first as s:
        s.set(rounds=3)
    assert _listeners() == before
    fn = len
    assert trace.handoff("serve.queue", fn) is fn
    with trace.recording() as rec:
        assert _listeners() == before + 1
        assert trace.active() is rec
        with pytest.raises(RuntimeError):
            with trace.recording():
                pass
    assert _listeners() == before
    assert trace.active() is None
    with trace.span("after"):
        pass
    assert rec.spans == []


def test_import_touches_no_jax():
    code = ("import sys, repro.core.trace; "
            "sys.exit('jax' in sys.modules)")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"},
                   cwd=__file__.rsplit("/tests/", 1)[0], timeout=120)


def test_fresh_jit_counted_in_its_span():
    inner = jax.jit(lambda x: jnp.tanh(x) * 3)
    f = jax.jit(lambda x: inner(x) + 1)
    x = jnp.arange(7, dtype=jnp.float32)
    with trace.recording() as rec:
        with trace.span("cold"):
            f(x).block_until_ready()
        with trace.span("warm"):
            f(x).block_until_ready()
    rows = rec.summary()
    assert rows["cold"]["jit_n"] >= 1
    assert rows["cold"]["jit_s"] > 0
    # the inner program's trace is inside the outer one's: counted once
    assert rows["cold"]["jit_s"] <= rows["cold"]["wall_s"]
    assert rows["warm"]["jit_n"] == 0
    assert rows["warm"]["jit_s"] == 0


def test_compile_with_no_span_goes_to_none():
    f = jax.jit(lambda x: x * 5 - 2)
    with trace.recording() as rec:
        f(jnp.ones(3)).block_until_ready()
    assert rec.unattributed["jit_n"] >= 1
    assert rec.summary()[trace.UNATTRIBUTED]["jit_s"] > 0
    assert rec.per_tag(tags={"x"}) == {}


def test_cold_served_point_records_every_span(tmp_path):
    import canal

    spec = InterconnectSpec(width=6, height=6, num_tracks=4, io_ring=True,
                            alphas=(2.0,), sa_steps=20, sa_batch=4, seed=3)
    with canal.serve(store=str(tmp_path), emulate_cycles=0,
                     apps={"pointwise": BENCH_APPS["pointwise"]},
                     place_strategy="batched",
                     route_strategy="minplus") as svc:
        assert "spans" not in svc.stats()
        digest = svc.executor.resolve(spec).digest()
        with trace.recording() as rec:
            record = svc.submit(spec).result(timeout=600)
            stats = svc.stats()
        assert "spans" not in svc.stats()
    assert record["apps"]["pointwise"]["success"]
    names = {s.name for s in rec.spans}
    assert names == POINT_SPANS
    assert set(stats["spans"]) >= POINT_SPANS
    point_tags = {s.tag for s in rec.spans if not s.name.startswith("serve")}
    assert point_tags == {digest}
    by_id = {s.id: s for s in rec.spans}
    parents = {by_id[s.parent].name for s in rec.spans
               if s.name == "device.wait"}
    assert {"place.detailed", "route.app"} <= parents
    route = next(s for s in rec.spans if s.name == "route.app")
    assert route.attrs["engine"] == "minplus"
    assert route.attrs["rounds"] >= 1
    assert by_id[route.parent].name == "pnr"
    assert by_id[route.parent].attrs == {"app": "pointwise"}
    glob = next(s for s in rec.spans if s.name == "place.global")
    assert glob.attrs["programs"] >= 1
    detailed = next(s for s in rec.spans if s.name == "place.detailed")
    assert detailed.attrs == {"alpha": 2.0, "engine": "batched"}
    point = next(s for s in rec.spans if s.name == "point")
    assert by_id[point.parent].name == "serve.query"
    rows = rec.per_tag(tags={digest})[digest]
    assert rows["point"]["wall_s"] >= rows["pnr"]["wall_s"]
    assert sum(r["jit_s"] for r in rows.values()) > 0


# ------------------------------------------------- named device programs
def _lowered(fn, *args, **kwargs) -> str:
    return fn.lower(*args, **kwargs).as_text(debug_info=True)


def test_anneal_scope_in_lowered_text(monkeypatch):
    from repro.core.pnr import batched_anneal
    from repro.core.pnr.global_place import (assign_ios, global_place,
                                             legalize)
    from repro.core.pnr.packing import pack

    anneal = batched_anneal._anneal
    texts = []

    def lowering(*args, **kwargs):
        texts.append(_lowered(anneal, *args, **kwargs))
        return anneal(*args, **kwargs)

    monkeypatch.setattr(batched_anneal, "_anneal", lowering)
    packed = pack(BENCH_APPS["pointwise"]())
    fixed = assign_ios(packed, 6, 6)
    base = legalize(packed, global_place(packed, 6, 6, fixed=fixed),
                    6, 6, io_ring=True, fixed=fixed)
    batched_anneal.batched_place(packed, base, 6, 6, n_steps=4,
                                 n_chains=2)
    assert len(texts) == 1 and "canal.anneal" in texts[0]


def test_minplus_scope_in_lowered_text():
    from repro.kernels import minplus

    d = jnp.zeros((2, 16), jnp.float32)
    w = jnp.ones((16, 16), jnp.float32)
    assert "canal.minplus" in _lowered(minplus.minplus_step, d, w,
                                       interpret=True)
    assert "canal.minplus" in _lowered(minplus._ref_block, d, w, iters=2)


def test_emulate_run_records_slots_per_sweep():
    """``emulate.run`` says how much the engine gathers a sweep: the 4P
    PE input slots on the chain engine, beside the sweeps."""
    from repro.core.lowering import compile_interconnect
    from repro.core.passes import PassManager
    from repro.fabric import AppEmulator, run_apps_batch

    fab = compile_interconnect(
        PassManager().run(InterconnectSpec(width=4, height=4, num_tracks=2)))
    assert fab.num_pe > 1
    emus = [AppEmulator(fab, [], {}, depth=d) for d in (2, 3)]
    with trace.recording() as rec:
        run_apps_batch(emus, [{}, {}], cycles=4, shard=False)
    run = next(s for s in rec.spans if s.name == "emulate.run")
    assert run.attrs["slots_per_sweep"] == 4 * fab.num_pe
    assert run.attrs["slots_per_sweep"] < fab.arrays.num_nodes
    assert run.attrs["sweeps"] == 3 * 4


def test_emulate_scope_in_lowered_text():
    from repro.core.lowering import compile_interconnect
    from repro.core.passes import PassManager

    fab = compile_interconnect(
        PassManager().run(InterconnectSpec(width=2, height=2, num_tracks=2)))
    b, t = 2, 3
    configs = jnp.zeros((b, fab.num_config), jnp.int32)
    ext = jnp.zeros((b, t, max(fab.num_io, 1)), jnp.int32)
    text = _lowered(jax.jit(lambda c, e: fab.run_batch(
        c, e, depth=np.int32(2), shard=False)), configs, ext)
    assert "canal.emulate" in text
