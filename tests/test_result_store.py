"""The spec-addressed persistent result store and the DSE serving path:
round-trip, atomicity/corruption tolerance, the hardware-digest secondary
index, store-backed SweepExecutor (warm sweeps do zero PnR, concurrent
requests coalesce, save_json dedupes), digest forward-compatibility of
the folded PnR knobs, and DSEService hit/miss/coalescing accounting."""
import json
import os
import threading
import time

import pytest

import canal
from repro.core.dse import SweepExecutor, sweep_num_tracks
from repro.core.pnr.app import app_pointwise
from repro.core.spec import InterconnectSpec, spec_from_kwargs
from repro.core.store import SCHEMA_VERSION, ResultStore

SMOKE = dict(width=4, height=4, num_tracks=2, io_ring=True, reg_density=1.0)
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "spec_digests.json")


def _executor(store, **kw):
    kw.setdefault("apps", {"pw": lambda: app_pointwise(1)})
    kw.setdefault("emulate_cycles", 6)
    kw.setdefault("max_workers", 1)
    return SweepExecutor(store=store, **kw)


# ---------------------------------------------------------------------------
# ResultStore basics
# ---------------------------------------------------------------------------

def test_store_round_trip(tmp_path):
    store = ResultStore(str(tmp_path / "s"))
    spec = InterconnectSpec(**SMOKE)
    rec = {"apps": {"pw": {"success": True}}, "sb_area": 1.5,
           "spec_digest": spec.digest()}
    digest = store.put(spec, rec)
    assert digest == spec.digest()
    assert store.get(spec.digest()) == rec
    assert store.get(spec) == rec                 # spec keys work too
    assert spec.digest() in store and len(store) == 1
    assert list(store.digests()) == [spec.digest()]
    st = store.stats()
    assert st["hits"] == 2 and st["writes"] == 1


def test_store_miss_and_bad_digest(tmp_path):
    store = ResultStore(str(tmp_path / "s"))
    assert store.get("0" * 64) is None
    assert store.stats()["misses"] == 1
    with pytest.raises(ValueError, match="sha256"):
        store.get("not-a-digest")
    with pytest.raises(ValueError, match="sha256"):
        store.put("nope", {})


def test_store_ignores_partial_and_corrupt_files(tmp_path):
    """Atomicity contract from the read side: truncated JSON, foreign
    schema versions, and digest-mismatched envelopes are all misses —
    never exceptions, never served."""
    store = ResultStore(str(tmp_path / "s"))
    spec = InterconnectSpec(**SMOKE)
    store.put(spec, {"apps": {}})
    records = os.path.join(store.root, "records")

    # a crashed writer's partial file under another digest's final path
    bad = "1" * 64
    with open(os.path.join(records, f"{bad}.json"), "w") as f:
        f.write('{"schema": 1, "record": {"apps"')     # truncated
    assert store.get(bad) is None
    assert store.stats()["corrupt"] >= 1

    # unknown schema version
    worse = "2" * 64
    with open(os.path.join(records, f"{worse}.json"), "w") as f:
        json.dump({"schema": SCHEMA_VERSION + 99, "spec_digest": worse,
                   "record": {}}, f)
    assert store.get(worse) is None

    # envelope that misrecords its own digest (e.g. renamed file)
    liar = "3" * 64
    with open(os.path.join(records, f"{liar}.json"), "w") as f:
        json.dump({"schema": SCHEMA_VERSION, "spec_digest": "4" * 64,
                   "record": {}}, f)
    assert store.get(liar) is None

    # the good record still loads; tmp droppings aren't listed (the
    # digest-named corrupt files are — listing is by name, loading is
    # what validates)
    assert store.get(spec) is not None
    with open(os.path.join(records, ".tmp-zzz.json"), "w") as f:
        f.write("{")
    listed = set(store.digests())
    assert spec.digest() in listed and len(listed) == 4
    assert ".tmp-zzz" not in {d[:8] for d in listed}


def test_store_hardware_index_enumerates_knob_variants(tmp_path):
    """Execution-knob variants of one hardware share hardware_digest();
    the secondary index returns all of them."""
    store = ResultStore(str(tmp_path / "s"))
    base = InterconnectSpec(**SMOKE)
    variants = [base.replace(route_strategy="python"),
                base.replace(route_strategy="minplus"),
                base.replace(sa_steps=10, alphas=(1.0, 2.0))]
    digests = {v.digest() for v in variants}
    assert len(digests) == 3                     # distinct addresses
    for i, v in enumerate(variants):
        store.put(v, {"i": i, "apps": {}})
    hw = base.hardware_digest()
    assert all(v.hardware_digest() == hw for v in variants)
    recs = store.for_hardware(hw)
    assert sorted(r["i"] for r in recs) == [0, 1, 2]
    assert store.for_hardware(base) == recs      # spec key accepted
    other = base.replace(num_tracks=3)
    assert store.for_hardware(other.hardware_digest()) == []


def test_store_dangling_index_marker_skipped(tmp_path):
    """The index marker is written before the record (a crash between
    the two leaves a dangling marker, never an unenumerable record);
    for_hardware must skip markers whose record never landed."""
    store = ResultStore(str(tmp_path / "s"))
    spec = InterconnectSpec(**SMOKE)
    store.put(spec, {"i": 0, "apps": {}})
    hw = spec.hardware_digest()
    dangling = os.path.join(store.root, "by_hardware", hw, "5" * 64)
    with open(dangling, "w"):
        pass
    recs = store.for_hardware(hw)
    assert [r["i"] for r in recs] == [0]


# ---------------------------------------------------------------------------
# Digest forward-compatibility (golden fixtures untouched)
# ---------------------------------------------------------------------------

def test_new_knobs_absent_from_canonical_json_when_default():
    spec = InterconnectSpec(**SMOKE)
    canon = json.loads(spec.canonical_json())
    for name in InterconnectSpec.DIGEST_OPTIONAL:
        assert name not in canon
    # ...but serialize once set, and round-trip
    pinned = spec.replace(sa_steps=30, alphas=(1.0, 2.0), reg_penalty=2.0)
    canon = json.loads(pinned.canonical_json())
    assert canon["sa_steps"] == 30 and canon["alphas"] == [1.0, 2.0]
    assert InterconnectSpec.from_json(pinned.to_json()) == pinned
    assert pinned.digest() != spec.digest()
    assert pinned.hardware_digest() == spec.hardware_digest()


def test_folded_knobs_leave_golden_fixture_valid():
    """The acceptance gate in miniature: digests recorded before the PnR
    knobs existed still verify — growing the spec never drifted them."""
    with open(FIXTURE) as f:
        golden = json.load(f)
    assert InterconnectSpec(**SMOKE).digest() == \
        golden["stock_4x4"]["spec_digest"]


def test_spec_from_kwargs_accepts_folded_knobs():
    spec = spec_from_kwargs(width=4, height=4, num_tracks=2,
                            reg_penalty=2.0, alphas=[1.0, 4.0],
                            sa_steps=25, sa_batch=4, seed=7,
                            split_fifo_ctrl_delay=0.1)
    assert spec.reg_penalty == 2.0 and spec.alphas == (1.0, 4.0)
    assert spec.sa_steps == 25 and spec.seed == 7


def test_with_execution_defaults_fills_only_unset():
    spec = InterconnectSpec(sa_steps=10, **SMOKE)
    r = spec.with_execution_defaults(sa_steps=99, seed=3, alphas=(2.0,))
    assert r.sa_steps == 10                      # spec wins
    assert r.seed == 3 and r.alphas == (2.0,)    # unset filled
    with pytest.raises(TypeError, match="not execution knobs"):
        spec.with_execution_defaults(width=9)


def test_executor_init_knobs_deprecated_pointing_at_spec():
    with pytest.warns(DeprecationWarning, match="spec .*'sa_steps'"):
        SweepExecutor(apps={}, sa_steps=30)
    with pytest.warns(DeprecationWarning, match="'reg_penalty'"):
        SweepExecutor(apps={}, reg_penalty=2.0)


def test_sweep_functions_do_not_warn_on_sa_steps():
    """The sweep functions' per-call sa_steps is their documented
    convenience contract — routing it through the executor default must
    not trip the __init__ deprecation (empty grid: construction only)."""
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", DeprecationWarning)
        recs = sweep_num_tracks((), apps={"pw": lambda: app_pointwise(1)},
                                width=4, height=4, sa_steps=20)
    assert recs == []


# ---------------------------------------------------------------------------
# Store-backed SweepExecutor
# ---------------------------------------------------------------------------

def test_warm_sweep_recomputes_nothing(tmp_path):
    """THE acceptance criterion: a repeated sweep_num_tracks against a
    warm store performs zero PnR recomputation, asserted via the store
    hit counters, and serves identical records."""
    store = ResultStore(str(tmp_path / "s"))
    tracks = (2, 3)
    cold_ex = _executor(store, max_workers=2)
    cold = sweep_num_tracks(tracks, width=4, height=4, executor=cold_ex)
    assert cold_ex.pnr_computations == len(tracks)
    assert cold_ex.store_hits == 0

    warm_ex = _executor(ResultStore(str(tmp_path / "s")), max_workers=2)
    warm = sweep_num_tracks(tracks, width=4, height=4, executor=warm_ex)
    assert warm_ex.pnr_computations == 0         # zero PnR on warm store
    assert warm_ex.store_hits == len(tracks)
    assert warm_ex.store_misses == 0
    for c, w in zip(cold, warm):
        assert c["spec_digest"] == w["spec_digest"]
        assert c["num_tracks"] == w["num_tracks"]
        assert c["sb_area"] == w["sb_area"]
        assert c["apps"]["pw"]["emulation"]["out_checksum"] == \
            w["apps"]["pw"]["emulation"]["out_checksum"]


def test_records_of_schema_1_are_recomputed(tmp_path):
    """Schema 1 records emulated app registers and memories as wires and
    timed paths through them: a store written then serves none of them;
    the point is recomputed and stored again under the current schema."""
    assert SCHEMA_VERSION == 2
    store = ResultStore(str(tmp_path / "s"))
    sweep_num_tracks((2,), width=4, height=4, executor=_executor(store))
    records = os.path.join(store.root, "records")
    paths = [os.path.join(records, f) for f in os.listdir(records)
             if f.endswith(".json")]
    assert paths
    for path in paths:
        with open(path) as f:
            env = json.load(f)
        with open(path, "w") as f:
            json.dump(dict(env, schema=1), f)

    old = ResultStore(str(tmp_path / "s"))
    ex = _executor(old)
    sweep_num_tracks((2,), width=4, height=4, executor=ex)
    assert ex.store_hits == 0 and ex.pnr_computations == 1
    for path in paths:
        with open(path) as f:
            assert json.load(f)["schema"] == SCHEMA_VERSION


def test_store_mismatched_context_is_a_miss(tmp_path):
    """A record computed without emulation (or for different apps) must
    not satisfy an executor that needs more — it is recomputed."""
    store = ResultStore(str(tmp_path / "s"))
    spec = InterconnectSpec(**SMOKE)
    ex0 = _executor(store, emulate_cycles=0)
    ex0.run_point(spec)
    assert ex0.pnr_computations == 1

    ex1 = _executor(store)                        # wants emulation now
    rec = ex1.run_point(spec)
    assert ex1.store_misses == 1 and ex1.pnr_computations == 1
    assert "emulation" in rec["apps"]["pw"]

    ex2 = _executor(store)                        # same context: warm
    ex2.run_point(spec)
    assert ex2.store_hits == 1 and ex2.pnr_computations == 0

    ex3 = _executor(store, apps={"pw": lambda: app_pointwise(1),
                                 "pw2": lambda: app_pointwise(2)})
    ex3.run_point(spec)                           # different app set
    assert ex3.store_misses == 1 and ex3.pnr_computations == 1


def test_concurrent_same_digest_coalesces(tmp_path):
    """Two threads asking for the same digest: one computes, the other
    piggybacks on the in-flight future (no second PnR, no store race)."""
    store = ResultStore(str(tmp_path / "s"))
    gate = threading.Event()
    entered = threading.Event()

    def slow_app():
        entered.set()
        assert gate.wait(timeout=30)
        return app_pointwise(1)

    ex = _executor(store, apps={"pw": slow_app}, emulate_cycles=0)
    spec = InterconnectSpec(**SMOKE)
    recs = []

    def run():
        recs.append(ex.run_point(spec))

    t1 = threading.Thread(target=run)
    t1.start()
    assert entered.wait(timeout=30)               # leader inside PnR
    t2 = threading.Thread(target=run)
    t2.start()
    deadline = time.time() + 30                  # follower parked on the
    while not ex._inflight and time.time() < deadline:  # in-flight future
        time.sleep(0.01)
    gate.set()
    t1.join(timeout=60)
    t2.join(timeout=60)
    assert len(recs) == 2
    assert ex.pnr_computations == 1
    assert ex.coalesced + ex.store_hits == 1      # follower never computed
    assert recs[0]["spec_digest"] == recs[1]["spec_digest"]


def test_record_usable_accepts_deeper_emulation(tmp_path):
    """A stored record emulated for >= the requested cycles is a hit
    (the documented 'at least the requested emulation' contract); less
    emulation — or none recorded — stays a miss."""
    ex = _executor(ResultStore(str(tmp_path / "s")), emulate_cycles=6)
    rec = {"apps": {"pw": {}}, "emulate_cycles": 10}
    assert ex.record_usable(rec)
    assert ex.record_usable(dict(rec, emulate_cycles=6))
    assert not ex.record_usable(dict(rec, emulate_cycles=4))
    assert not ex.record_usable(dict(rec, emulate_cycles=None))
    ex0 = _executor(ResultStore(str(tmp_path / "s0")), emulate_cycles=0)
    assert ex0.record_usable({"apps": {"pw": {}}})


def test_store_deeper_emulation_serves_shallower_request(tmp_path):
    """Executors alternating emulate_cycles against one store converge on
    the deepest record instead of thrashing overwrites: a record emulated
    for 8 cycles serves a 4-cycle request with zero recomputation."""
    store = ResultStore(str(tmp_path / "s"))
    spec = InterconnectSpec(**SMOKE)
    ex8 = _executor(store, emulate_cycles=8)
    ex8.run_point(spec)
    assert ex8.pnr_computations == 1

    ex4 = _executor(store, emulate_cycles=4)
    rec = ex4.run_point(spec)
    assert ex4.store_hits == 1 and ex4.pnr_computations == 0
    assert rec["emulate_cycles"] == 8             # the stored, deeper run


def test_concurrent_run_points_own_their_pending_futures(tmp_path):
    """High-severity regression: with two run_points calls sharing one
    executor, each run joins exactly its own deferred emulation futures.
    Sweep B must return with its emulation merged while never popping
    (and awaiting, or orphaning) sweep A's still-pending future."""
    import itertools

    counter = itertools.count(1)
    count_lock = threading.Lock()
    gate = threading.Event()
    a_second_point = threading.Event()

    def mk():
        with count_lock:
            n = next(counter)
        if n == 2:                # sweep A's second point: park mid-run
            a_second_point.set()
            assert gate.wait(timeout=60)
        return app_pointwise(1)

    ex = _executor(ResultStore(str(tmp_path / "s")), apps={"pw": mk},
                   max_workers=1)
    assert ex.pipeline_emulation and ex.emulate_cycles > 0
    a_points = [(InterconnectSpec(**SMOKE), {}),
                (InterconnectSpec(**dict(SMOKE, num_tracks=4)), {})]
    b_points = [(InterconnectSpec(**dict(SMOKE, num_tracks=3)), {})]
    a_recs = []
    a_thread = threading.Thread(
        target=lambda: a_recs.extend(ex.run_points(a_points)))
    a_thread.start()
    try:
        # A has dispatched point 1's emulation and is parked inside
        # point 2's PnR; run sweep B to completion underneath it
        assert a_second_point.wait(timeout=120)
        b_recs = ex.run_points(b_points)
        assert "emulation" in b_recs[0]["apps"]["pw"]  # B joined its own
        assert a_thread.is_alive()                     # A still mid-run
        # B's join-own must have left A's point-1 future on the global
        # list (the old join-all popped it, handing A's future to B and
        # letting a sibling return records with emulation in flight)
        assert ex._pending
    finally:
        gate.set()
        a_thread.join(timeout=300)
    assert not a_thread.is_alive()
    assert len(a_recs) == 2
    for rec in a_recs:
        assert "emulation" in rec["apps"]["pw"]
    assert not ex._pending                             # A drained its own


def test_same_digest_coalesces_through_emulation_tail(tmp_path):
    """The in-flight entry survives until the deferred emulation (and
    its store write-back) lands: a same-digest request arriving in that
    tail coalesces onto the leader's record instead of missing the
    still-unwritten store and redoing PnR + emulation."""
    gate = threading.Event()
    ex = _executor(ResultStore(str(tmp_path / "s")))
    real = ex._emulate_batch

    def parked(fab, routed, device=None):
        out = real(fab, routed, device=device)
        assert gate.wait(timeout=60)
        return out

    ex._emulate_batch = parked
    spec = InterconnectSpec(**SMOKE)
    rec = ex.run_point(spec, defer_emulation=True)
    assert ex._inflight                           # alive through the tail
    follower = threading.Thread(target=lambda: ex.run_point(spec))
    follower.start()
    time.sleep(0.2)                               # let it reach the wait
    gate.set()
    follower.join(timeout=120)
    ex.join_pending()
    assert ex.pnr_computations == 1               # follower never computed
    # a late-scheduled follower may instead find the written-back store
    # record; either way the tail never triggers a recompute
    assert ex.coalesced + ex.store_hits == 1
    assert "emulation" in rec["apps"]["pw"]
    assert not ex._inflight and not ex._pending


def test_save_json_dedupes_repeated_sweeps(tmp_path):
    """Satellite fix: repeated sweep_* calls on one executor used to
    accumulate and re-persist overlapping records."""
    ex = _executor(ResultStore(str(tmp_path / "s")), emulate_cycles=0)
    tracks = (2, 3)
    sweep_num_tracks(tracks, width=4, height=4, executor=ex)
    sweep_num_tracks(tracks, width=4, height=4, executor=ex)
    assert len(ex.records) == 2 * len(tracks)     # raw accumulation
    path = ex.save_json(str(tmp_path / "out.json"))
    with open(path) as f:
        saved = json.load(f)
    assert len(saved) == len(tracks)              # deduped view
    assert [r["num_tracks"] for r in saved] == list(tracks)


def test_resolved_digest_pins_knobs_and_shares_hardware(tmp_path):
    """resolve() fills unset knobs from the executor; two executors with
    different defaults address different records for the same bare spec,
    while their artifact caches still share the hardware digest."""
    store = ResultStore(str(tmp_path / "s"))
    spec = InterconnectSpec(**SMOKE)
    ex_a = _executor(store, emulate_cycles=0)
    with pytest.warns(DeprecationWarning):
        ex_b = _executor(store, emulate_cycles=0, sa_steps=10)
    ra = ex_a.resolve(spec)
    rb = ex_b.resolve(spec)
    assert ra.digest() != rb.digest()
    assert ra.sa_steps == 60 and rb.sa_steps == 10
    assert ra.hardware_digest() == rb.hardware_digest() == spec.digest()
    ex_a.run_point(spec)
    ex_b.run_point(spec)
    assert ex_b.store_hits == 0                   # distinct addresses
    assert len(store.for_hardware(spec)) == 2     # both enumerable


# ---------------------------------------------------------------------------
# DSEService
# ---------------------------------------------------------------------------

def test_service_single_and_batch_queries(tmp_path):
    svc = canal.serve(store=str(tmp_path / "s"),
                      apps={"pw": lambda: app_pointwise(1)},
                      emulate_cycles=0, max_workers=1)
    spec = InterconnectSpec(**SMOKE)
    rec = svc.query(spec)                         # single in -> dict out
    assert rec["apps"]["pw"]["success"]
    st = svc.stats()
    assert st["misses"] == 1 and st["hits"] == 0

    out = svc.query([spec, spec.replace(num_tracks=3)])
    assert isinstance(out, list) and len(out) == 2
    st = svc.stats()
    assert st["hits"] == 1 and st["misses"] == 2  # first spec warm now
    assert st["queries"] == 2 and st["specs_served"] == 3
    assert st["latency_avg_s"] > 0
    assert st["executor"]["pnr_computations"] == 2
    svc.close()


def test_service_warm_query_hits_only(tmp_path):
    root = str(tmp_path / "s")
    apps = {"pw": lambda: app_pointwise(1)}
    specs = [InterconnectSpec(**SMOKE),
             InterconnectSpec(**dict(SMOKE, num_tracks=3))]
    svc1 = canal.serve(store=root, apps=apps, emulate_cycles=0,
                       max_workers=1)
    svc1.query(specs)
    svc1.close()

    svc2 = canal.serve(store=root, apps=apps, emulate_cycles=0,
                       max_workers=1)
    out = svc2.query(specs)                       # fresh process-alike
    st = svc2.stats()
    assert st["hits"] == 2 and st["misses"] == 0
    assert st["executor"]["pnr_computations"] == 0
    assert st["hit_rate"] == 1.0
    assert [r["spec_digest"] for r in out] == [
        svc2.executor.resolve(s).digest() for s in specs]
    svc2.close()


def test_service_duplicate_specs_in_one_query(tmp_path):
    svc = canal.serve(store=str(tmp_path / "s"),
                      apps={"pw": lambda: app_pointwise(1)},
                      emulate_cycles=0, max_workers=1)
    spec = InterconnectSpec(**SMOKE)
    out = svc.query([spec, dict(SMOKE), spec])    # legacy kwargs too
    assert len(out) == 3
    assert len({r["spec_digest"] for r in out}) == 1
    assert svc.stats()["executor"]["pnr_computations"] == 1
    svc.close()


def test_service_concurrent_queries_coalesce(tmp_path):
    """Two service queries for the same cold digest in flight at once:
    exactly one computation; the other request waits on it."""
    gate = threading.Event()
    entered = threading.Event()

    def slow_app():
        entered.set()
        assert gate.wait(timeout=30)
        return app_pointwise(1)

    svc = canal.serve(store=str(tmp_path / "s"), apps={"pw": slow_app},
                      emulate_cycles=0, max_workers=1)
    spec = InterconnectSpec(**SMOKE)
    f1 = svc.submit(spec)
    assert entered.wait(timeout=30)
    f2 = svc.submit(spec)
    deadline = time.time() + 30
    while not svc._inflight and time.time() < deadline:
        time.sleep(0.01)
    gate.set()
    r1, r2 = f1.result(timeout=60), f2.result(timeout=60)
    assert r1["spec_digest"] == r2["spec_digest"]
    st = svc.stats()
    assert st["executor"]["pnr_computations"] == 1
    # the second query either coalesced on the in-flight future or (if it
    # lost the race entirely) was served from the store
    assert st["coalesced"] + st["hits"] == 1
    svc.close()


def test_service_probe_failure_resolves_claimed_futures(tmp_path):
    """A store probe raising mid-query must not leak claimed in-flight
    futures (later queries for those digests would hang on them); the
    query surfaces the error and the service recovers."""
    root = str(tmp_path / "s")
    apps = {"pw": lambda: app_pointwise(1)}
    spec = InterconnectSpec(**SMOKE)
    specs = [spec, spec.replace(num_tracks=3)]
    warm = canal.serve(store=root, apps=apps, emulate_cycles=0,
                       max_workers=1)
    warm.query(spec)                              # a record to probe
    warm.close()

    svc = canal.serve(store=root, apps=apps, emulate_cycles=0,
                      max_workers=1)
    svc.executor.record_usable = \
        lambda rec: (_ for _ in ()).throw(TypeError("malformed record"))
    with pytest.raises(TypeError, match="malformed record"):
        svc.query(specs)
    assert not svc._inflight                      # nothing leaked
    del svc.executor.record_usable                # fault clears
    recs = svc.query(specs)
    assert all(r["apps"]["pw"]["success"] for r in recs)
    svc.close()


def test_service_cold_point_probes_store_exactly_once(tmp_path):
    """Regression (the double-probe bug): a cold query used to probe
    the store in the service AND again inside run_point — two disk
    reads and two miss increments per cold point. The probe verdict is
    now threaded through (``assume_cold``), so the counters are exact:
    one store miss per cold point, one store hit per warm one."""
    svc = canal.serve(store=str(tmp_path / "s"),
                      apps={"pw": lambda: app_pointwise(1)},
                      emulate_cycles=0, max_workers=1)
    specs = [InterconnectSpec(**SMOKE),
             InterconnectSpec(**dict(SMOKE, num_tracks=3))]
    svc.query(specs)
    assert svc.store.stats()["misses"] == len(specs)   # not 2x
    assert svc.store.stats()["hits"] == 0
    assert svc.executor.store_misses == len(specs)
    assert svc.executor.store_hits == 0
    svc.query(specs)
    assert svc.store.stats()["misses"] == len(specs)   # unchanged
    assert svc.store.stats()["hits"] == len(specs)
    assert svc.executor.store_hits == len(specs)
    assert svc.executor.pnr_computations == len(specs)
    svc.close()


def test_store_put_merges_app_records():
    """Unit contract of the ping-pong fix: put() on an existing digest
    unions app maps (newest wins per app), stamps per-app
    emulate_cycles claims, and recomputes the frontier metrics."""
    from repro.core.store import merge_records, record_metrics
    old = {"apps": {"a": {"success": True, "critical_path_ns": 2.0},
                    "b": {"success": False,
                          "critical_path_ns": float("inf")}},
           "emulate_cycles": 8, "sb_area": 10.0, "cb_area": 5.0,
           "metrics": record_metrics(
               {"apps": {}, "sb_area": 10.0, "cb_area": 5.0})}
    new = {"apps": {"b": {"success": True, "critical_path_ns": 3.0},
                    "c": {"success": True, "critical_path_ns": 1.0}},
           "emulate_cycles": 4, "sb_area": 10.0, "cb_area": 5.0}
    merged = merge_records(old, new)
    assert set(merged["apps"]) == {"a", "b", "c"}
    assert merged["apps"]["b"]["success"]              # newest wins
    assert merged["apps"]["a"]["emulate_cycles"] == 8  # old claim kept
    assert merged["apps"]["b"]["emulate_cycles"] == 4
    assert merged["emulate_cycles"] == 4               # top-level: newest
    m = merged["metrics"]
    assert m["routability"] == 1.0 and m["area"] == 15.0
    assert m["critical_path_ns"] == 3.0
    # the caller's dicts were not mutated
    assert "emulate_cycles" not in new["apps"]["b"]
    assert "c" not in old["apps"]


def test_store_alternating_app_sets_converge(tmp_path):
    """Regression (the app-set ping-pong bug): executors with different
    app sets sharing one store used to overwrite each other's records
    for the same digest forever — every lookup a miss, every miss a
    recompute. put() now merges, so after one computation per app set
    the record covers the union and both executor kinds hit."""
    store = ResultStore(str(tmp_path / "s"))
    spec = InterconnectSpec(**SMOKE)
    apps_a = {"pw": lambda: app_pointwise(1)}
    apps_b = {"pw2": lambda: app_pointwise(2)}
    ex_a = _executor(store, apps=apps_a)
    ex_b = _executor(store, apps=apps_b)
    ex_a.run_point(spec)
    ex_b.run_point(spec)
    assert ex_a.pnr_computations == 1 and ex_b.pnr_computations == 1

    # alternate fresh executors of both kinds: all hits, zero PnR —
    # the old last-writer-wins store would miss every single one
    for apps, names in ((apps_a, {"pw"}), (apps_b, {"pw2"}),
                        (apps_a, {"pw"}), (apps_b, {"pw2"})):
        ex = _executor(store, apps=apps)
        rec = ex.run_point(spec)
        assert ex.pnr_computations == 0 and ex.store_hits == 1
        assert set(rec["apps"]) == names        # filtered view
        assert "emulation" in rec["apps"][next(iter(names))]
    digest = ex_a.resolve(spec).digest()
    assert set(store.get(digest)["apps"]) == {"pw", "pw2"}

    # an executor wanting the union is also served by the merged record
    ex_ab = _executor(store, apps=dict(apps_a, **apps_b))
    ex_ab.run_point(spec)
    assert ex_ab.pnr_computations == 0 and ex_ab.store_hits == 1


def test_store_concurrent_alternating_app_sets(tmp_path):
    """The merge under concurrency: threads alternating two app sets
    against one shared store object converge to the union record with
    exactly one PnR per app set (coalescing + merge, no thrash)."""
    store = ResultStore(str(tmp_path / "s"))
    spec = InterconnectSpec(**SMOKE)
    apps_a = {"pw": lambda: app_pointwise(1)}
    apps_b = {"pw2": lambda: app_pointwise(2)}
    ex_a = _executor(store, apps=apps_a)
    ex_b = _executor(store, apps=apps_b)
    errs = []

    def run(ex):
        try:
            ex.run_point(spec)
        except BaseException as e:                # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=run, args=(ex,))
               for ex in (ex_a, ex_b, ex_a, ex_b)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errs
    # per executor: one computation total (its duplicate request hit
    # the store or coalesced), never one per alternation
    assert ex_a.pnr_computations <= 1 and ex_b.pnr_computations <= 1
    digest = ex_a.resolve(spec).digest()
    assert set(store.get(digest)["apps"]) == {"pw", "pw2"}
    # convergence: fresh executors of both kinds are pure hits
    for apps in (apps_a, apps_b):
        ex = _executor(store, apps=apps)
        ex.run_point(spec)
        assert ex.pnr_computations == 0 and ex.store_hits == 1


def test_canal_serve_is_the_front_door(tmp_path):
    from repro.serve.dse_service import DSEService
    svc = canal.serve(store=str(tmp_path / "s"), apps={},
                      emulate_cycles=0)
    assert isinstance(svc, DSEService)
    assert svc.store.root == str(tmp_path / "s")
    svc.close()
