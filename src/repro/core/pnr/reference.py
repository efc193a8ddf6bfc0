"""A plain reference for what an application computes, cycle by cycle.

:func:`evaluate` interprets an :class:`~repro.core.pnr.app.AppGraph`
directly in numpy, one cycle at a time, with no fabric, no JAX and no
batching engine; the emulated outputs of a routed app are compared
against it. Its semantics:

- an ``io_in`` drives its ``io_out`` port from the stimulus; an
  ``io_out`` observes what reaches its ``io_in`` port;
- a ``const`` drives its value;
- a PE's ``res0`` is its op over ``data0..data2`` (and its ``const``),
  as ``PECore.evaluate`` defines it, in 16-bit words;
- a ``reg`` is one cycle: ``out`` at cycle t is ``in`` at t - 1;
- a ``mem`` reads one cycle after it writes: ``rdata`` at t is
  ``wdata`` at t - 1. This departs from Amber's memory tile, whose line
  buffer has a configurable depth: the fabric's ``MemCore`` model is a
  one-cycle delay and the reference states the same;
- every register and memory starts at zero, and so does every value
  from before cycle 0.

A connection (driver port to sink port) may carry extra cycles of
delay: the interconnect registers its route crosses
(:func:`route_delays`), since the router may pipeline a connection
through a track register.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Tuple

import numpy as np

from .timing import _net_segment_delays

WORD = 0xFFFF
#: (driver instance, driver port), (sink instance, sink port)
Connection = Tuple[Tuple[str, str], Tuple[str, str]]


def _alu(op: str, a, b, c, const: int):
    if op == "add":
        r = a + b
    elif op == "sub":
        r = a - b
    elif op == "mul":
        r = a * b
    elif op == "and":
        r = a & b
    elif op == "or":
        r = a | b
    elif op == "xor":
        r = a ^ b
    elif op == "shl":
        r = a << (b & 0xF)
    elif op == "shr":
        r = a >> (b & 0xF)
    elif op == "min":
        r = np.minimum(a, b)
    elif op == "max":
        r = np.maximum(a, b)
    elif op == "abs":
        r = np.abs(a - b)
    elif op == "sel":
        r = np.where(a & 1, b, c)
    elif op == "const":
        r = np.zeros_like(a) + const
    elif op == "pass":
        r = a
    else:
        raise ValueError(f"unknown PE op {op}")
    return r & WORD


def evaluate(app, stimulus: Mapping[str, np.ndarray], cycles: int,
             delays: Optional[Mapping[Connection, int]] = None
             ) -> Dict[str, np.ndarray]:
    """Run ``app`` for ``cycles`` cycles.

    ``stimulus``: ``{io_in instance: (..., T) words}``; leading axes are
    independent runs, evaluated together. ``delays``: extra cycles per
    connection. Returns ``{io_out instance: (..., cycles) words}``."""
    delays = delays or {}
    inst = app.instances
    driver: Dict[Tuple[str, str], Tuple[str, str]] = {}
    for net in app.nets:
        for sink in net.sinks:
            sink = tuple(sink)
            if sink in driver:
                raise ValueError(f"port {sink} has two drivers")
            driver[sink] = tuple(net.src)
    shape = np.broadcast_shapes(
        *(np.shape(v)[:-1] for v in stimulus.values())) if stimulus else ()
    zero = np.zeros(shape, np.int64)
    #: value history of every driven output port, (..., cycles)
    hist: Dict[Tuple[str, str], np.ndarray] = {}

    def port(name: str, p: str, t: int):
        src = driver.get((name, p))
        if src is None:
            return zero
        if src[1] != "res0" and inst[src[0]].kind == "pe":
            raise ValueError(f"PE output {src} is not modelled")
        t -= delays.get((src, (name, p)), 0)
        return hist[src][..., t] if t >= 0 else zero

    for net in app.nets:
        hist.setdefault(tuple(net.src), np.zeros(shape + (cycles,),
                                                 np.int64))
    order = _combinational_order(app, driver, delays)
    out = {n: np.zeros(shape + (cycles,), np.int64)
           for n, i in inst.items() if i.kind == "io_out"}
    for t in range(cycles):
        for (name, p), h in hist.items():
            kind = inst[name].kind
            if kind == "io_in":
                h[..., t] = np.asarray(stimulus[name])[..., t] & WORD
            elif kind == "const":
                h[..., t] = inst[name].const & WORD
            elif kind == "reg":
                h[..., t] = port(name, "in", t - 1) if t else zero
            elif kind == "mem":
                h[..., t] = port(name, "wdata", t - 1) if t else zero
        for name in order:
            a, b, c = (port(name, f"data{i}", t) for i in range(3))
            key = (name, "res0")
            if key in hist:
                hist[key][..., t] = _alu(inst[name].op, a, b, c,
                                         inst[name].const)
        for name, o in out.items():
            o[..., t] = port(name, "io_in", t)
    return out


def _combinational_order(app, driver, delays) -> List[str]:
    """PEs in an order where every PE comes after the PEs that feed it
    within the same cycle (undelayed connections)."""
    pes = [n for n, i in app.instances.items() if i.kind == "pe"]
    feeds: Dict[str, List[str]] = {n: [] for n in pes}
    for (sink, p), src in driver.items():
        if (sink in feeds and app.instances[src[0]].kind == "pe"
                and not delays.get((src, (sink, p)), 0)):
            feeds[sink].append(src[0])
    order: List[str] = []
    state: Dict[str, int] = {}

    def visit(n: str) -> None:
        if state.get(n) == 2:
            return
        if state.get(n) == 1:
            raise ValueError(f"combinational loop through PE {n}")
        state[n] = 1
        for m in feeds[n]:
            visit(m)
        state[n] = 2
        order.append(n)

    for n in pes:
        visit(n)
    return order


def route_delays(packed, result) -> Dict[Connection, int]:
    """Interconnect registers each routed connection of a placed and
    routed app crosses, keyed by the app's own connection: a connection
    into an absorbed register is the one into the register's ``in``."""
    app = packed.app
    absorbed = {}
    for name, i in app.instances.items():
        if i.kind == "reg" and name not in packed.placeable:
            net, = app.fanout_of(name)
            absorbed[tuple(net.sinks[0])] = name
    routed = {n.name: n for n in result.routing.nets}
    out: Dict[Connection, int] = {}
    for net in packed.nets:
        rnet = routed.get(net.name)
        if rnet is None:
            continue
        seg = _net_segment_delays(result.routing.resources, rnet.tree,
                                  rnet.src, rnet.sinks)
        for sink, sink_id in zip(net.sinks, rnet.sinks):
            sink = tuple(sink)
            if sink in absorbed:
                sink = (absorbed[sink], "in")
            out[(tuple(net.src), sink)] = seg[sink_id][1]
    return out
