"""Independent check of emulated application outputs.

Written against the applications' own semantics and the configuration's
netlist data, not against the program's code: it shares no code with
``repro``. It interprets each app netlist (instances ``[name, kind, op,
const]``, nets ``[name, [src, port], [[sink, port], ...]]``) cycle by
cycle:

- an ``io_in`` drives its ``io_out`` port with the stimulus; an
  ``io_out`` observes its ``io_in`` port;
- a ``const`` drives its value, a PE's ``res0`` is its op over
  ``data0..data2`` in 16-bit words;
- a ``reg`` is one cycle; a ``mem`` reads one cycle after it writes (the
  configuration's memory model); everything starts at zero.

A connection routed through interconnect registers is late by one
cycle for each: the delays are counted on the program's route trees as
``check_pnr`` walks them, with a connection into an absorbed register
standing for the one into the register itself.

``wrong_words`` counts output words of every batch that differ from
this interpretation; ``apps_missing`` counts apps of the configuration
with no routed design or no outputs in some batch. Route delays that
differ between paths that meet again make the app another function of
its inputs, which the interpretation would follow: ``unbalanced_paths``
counts them apart (:func:`unbalanced_paths`).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from .check_pnr import Graph, Packing, _path_to_root, _segment, port_name

WORD = 0xFFFF
#: (driver instance, driver port, sink instance, sink port)
Conn = Tuple[str, str, str, str]

_OPS = {
    "add": lambda a, b, c, k: a + b,
    "sub": lambda a, b, c, k: a - b,
    "mul": lambda a, b, c, k: a * b,
    "and": lambda a, b, c, k: a & b,
    "or": lambda a, b, c, k: a | b,
    "xor": lambda a, b, c, k: a ^ b,
    "shl": lambda a, b, c, k: a << (b & 15),
    "shr": lambda a, b, c, k: a >> (b & 15),
    "min": lambda a, b, c, k: np.minimum(a, b),
    "max": lambda a, b, c, k: np.maximum(a, b),
    "abs": lambda a, b, c, k: np.abs(a - b),
    "sel": lambda a, b, c, k: np.where(a & 1, b, c),
    "const": lambda a, b, c, k: a * 0 + k,
    "pass": lambda a, b, c, k: a,
}


def interpret(app: Dict, stimulus: Dict[str, np.ndarray], cycles: int,
              delays: Dict[Conn, int]) -> Dict[str, np.ndarray]:
    """``{io_out instance: (K, cycles) words}`` for ``stimulus``
    ``{io_in instance: (K, cycles) words}``: K runs at once."""
    kind = {n: k for n, k, _, _ in app["instances"]}
    op = {n: o for n, _, o, _ in app["instances"]}
    const = {n: int(c) & WORD for n, _, _, c in app["instances"]}
    into: Dict[Tuple[str, str], Tuple[str, str]] = {}
    for _, (src, sport), sinks in app["nets"]:
        for sink, port in sinks:
            into[(sink, port)] = (src, sport)
    k = len(next(iter(stimulus.values())))
    zero = np.zeros(k, np.int64)
    value: Dict[Tuple[str, str], np.ndarray] = {
        (src, sport): np.zeros((k, cycles), np.int64)
        for _, (src, sport), _ in app["nets"]}

    def at(sink: str, port: str, t: int) -> np.ndarray:
        src = into.get((sink, port))
        if src is None:
            return zero
        t -= delays.get((src[0], src[1], sink, port), 0)
        return value[src][:, t] if t >= 0 else zero

    out = {n: np.zeros((k, cycles), np.int64)
           for n, kd in kind.items() if kd == "io_out"}
    pes = [n for n, kd in kind.items() if kd == "pe"]
    for t in range(cycles):
        for (name, port), v in value.items():
            if kind[name] == "io_in":
                v[:, t] = np.asarray(stimulus[name])[:, t] & WORD
            elif kind[name] == "const":
                v[:, t] = const[name]
            elif kind[name] in ("reg", "mem"):
                inp = "in" if kind[name] == "reg" else "wdata"
                v[:, t] = at(name, inp, t - 1) if t else zero
        # PEs settle in as many passes as the longest same-cycle chain
        for _ in range(len(pes)):
            for name in pes:
                if (name, "res0") in value:
                    a, b, c = (at(name, f"data{i}", t) for i in range(3))
                    value[(name, "res0")][:, t] = _OPS[op[name]](
                        a, b, c, const[name]) & WORD
        for name, o in out.items():
            o[:, t] = at(name, "io_in", t)
    return out


def connection_delays(g: Graph, spec: Dict, app: Dict,
                      placement: Dict[str, Sequence[int]],
                      routes) -> Dict[Conn, int]:
    """Interconnect registers each app connection's route crosses."""
    pack = Packing(app, placement)
    reg_of = {v: r for r, v in pack.absorbed.items()}
    width = spec["track_width"]

    def endpoint(inst: str, port: str):
        x, y = placement[inst]
        return g.port_at.get((int(x), int(y),
                              port_name(pack.kind[inst], port), width))

    trees = {src: tree for src, _, tree in routes}
    out: Dict[Conn, int] = {}
    for src, sport, sink, port, absorbed in pack.connections(app):
        a, b = endpoint(src, sport), endpoint(sink, port)
        path = _path_to_root(trees.get(a, {}), a, b, len(g.kind) + 1)
        if path is None:
            raise ValueError(f"no route {src}.{sport} -> {sink}.{port}")
        if absorbed:
            sink, port = reg_of[(sink, port)], "in"
        out[(src, sport, sink, port)] = _segment(g, path)[1]
    return out


def unbalanced_paths(app: Dict, delays: Dict[Conn, int]) -> int:
    """Instances reached from the app inputs over paths that cross
    different numbers of interconnect registers, plus the outputs whose
    latency differs from the first output's. Zero means the routes only
    shift every output by one common latency."""
    kind = {n: k for n, k, _, _ in app["instances"]}
    edges = [(src, sink, delays.get((src, sport, sink, port), 0))
             for _, (src, sport), sinks in app["nets"]
             for sink, port in sinks]
    lag = {n: 0 for n, k in kind.items() if k == "io_in"}
    bad = set()
    for _ in range(len(kind)):
        for src, sink, d in edges:
            if src not in lag:
                continue
            if lag.setdefault(sink, lag[src] + d) != lag[src] + d:
                bad.add(sink)
    outs = {lag[n] for n, k in kind.items() if k == "io_out" and n in lag}
    return len(bad) + max(len(outs) - 1, 0)


def compare(apps: Dict[str, Dict], delays: Dict[str, Dict[Conn, int]],
            batches: List[Tuple[Dict, Dict]], cycles: int
            ) -> Dict[str, int]:
    """``wrong_words`` and ``apps_missing`` over ``batches``, each
    ``(stimulus, outputs)`` as ``{app: {instance: (cycles,) words}}``;
    ``delays`` holds the routed apps."""
    wrong = missing = 0
    for name, app in apps.items():
        if (name not in delays or not batches
                or any(name not in o for _, o in batches)):
            missing += 1
            continue
        inputs = {n for n, kd, _, _ in app["instances"] if kd == "io_in"}
        stim = {n: np.stack([s[name][n] for s, _ in batches])
                for n in inputs}
        want = interpret(app, stim, cycles, delays[name])
        for inst, words in want.items():
            got = np.stack([np.asarray(o[name][inst], np.int64)
                            for _, o in batches])
            if got.shape != words.shape:
                wrong += words.size
            else:
                wrong += int(np.count_nonzero(got != words))
    return {"wrong_words": wrong, "apps_missing": missing}
