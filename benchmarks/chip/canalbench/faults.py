"""Controls and planted faults that ``correct`` must catch.

Each entry breaks the program underneath a run of the search cell
(``dse8.search``), for as long as its context is open, in one way a
later change to the program could:

- ``foreign_routes`` (the control): every app after the first point
  reuses the placement and routes computed for the first point's
  hardware, the step a cache keyed on the app alone would take;
- ``give_up_early``: the router stops negotiating congestion after one
  iteration instead of 40, so an app that needs a second round is
  called unroutable, faster;
- ``give_up`` (the control for unroutable apps): the router gives up
  before its first iteration, so every app is called unroutable;
- ``dropped_net``: packing leaves the last net of every app out, so
  PnR routes less than the app needs;
- ``stale_record``: every point is answered with the first point's
  record;
- ``half_apps``: records carry only the first half of the apps;
- ``altered_answer``: the PnR result's wirelength is one more and its
  critical path a picosecond longer than its routes give.

The cell has one chip, so there is no exchange between chips to leave
out.
"""
from __future__ import annotations

import contextlib
import threading
from unittest import mock

SEARCH = ("foreign_routes", "give_up_early", "give_up", "dropped_net",
          "stale_record", "half_apps", "altered_answer")


def _dse(attr, make):
    import repro.core.dse as dse
    return mock.patch.object(dse, attr, make(getattr(dse, attr)))


def _first_per_app(place_and_route):
    memo, lock = {}, threading.Lock()

    def reuse(ic, app, *args, **kwargs):
        with lock:
            if app.bench_app in memo:
                return memo[app.bench_app]
        r = place_and_route(ic, app, *args, **kwargs)
        with lock:
            return memo.setdefault(app.bench_app, r)

    return reuse


def _route_cap(iterations):
    def make(place_and_route):
        def capped(ic, app, *args, **kwargs):
            return place_and_route(ic, app, *args,
                                   **dict(kwargs, route_iters=iterations))

        return capped

    return make


def _no_route():
    import repro.core.pnr.driver as driver
    from repro.core.pnr.route import RoutingError

    def gave_up(*args, **kwargs):
        raise RoutingError("gave up before the first iteration")

    return mock.patch.object(driver, "route_app", gave_up)


def _altered(place_and_route):
    def altered(ic, app, *args, **kwargs):
        r = place_and_route(ic, app, *args, **kwargs)
        if r.success:
            r.wirelength += 1
            r.timing["critical_path_ns"] += 1e-3
        return r

    return altered


def _drop_last_net():
    import repro.core.pnr.driver as driver

    pack = driver.pack

    def dropped(app):
        packed = pack(app)
        packed.nets = packed.nets[:-1]
        return packed

    return mock.patch.object(driver, "pack", dropped)


def _compute_point(edit):
    from repro.core.dse import SweepExecutor

    orig = SweepExecutor._compute_point

    def compute(self, *args, **kwargs):
        return edit(orig(self, *args, **kwargs))

    return mock.patch.object(SweepExecutor, "_compute_point", compute)


def _stale():
    first = []

    def edit(out):
        if not first:
            first.append(out)
        return first[0]

    return edit


def _half_apps(out):
    rec, emu = out
    names = list(rec["apps"])
    return dict(rec, apps={n: rec["apps"][n]
                           for n in names[:len(names) // 2]}), emu


@contextlib.contextmanager
def planted(generator: str, name: str):
    """Open fault ``name`` of the cells driven by ``generator``
    (``search``); ``none`` plants nothing."""
    if name == "none":
        patch = contextlib.nullcontext()
    elif generator == "search" and name in SEARCH:
        patch = {
            "foreign_routes": lambda: _dse("place_and_route",
                                           _first_per_app),
            "give_up_early": lambda: _dse("place_and_route", _route_cap(1)),
            "give_up": _no_route,
            "dropped_net": _drop_last_net,
            "stale_record": lambda: _compute_point(_stale()),
            "half_apps": lambda: _compute_point(_half_apps),
            "altered_answer": lambda: _dse("place_and_route", _altered),
        }[name]()
    else:
        raise KeyError(f"no fault {name!r} for generator {generator!r}")
    with patch:
        yield
