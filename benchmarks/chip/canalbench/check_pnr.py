"""Independent check of a placed and routed design point.

Written against the Canal paper's rules (§3.3-§3.4) and the
configuration's own application netlists, not against the program's
code: it reads plain data and shares no code with ``repro``. What it
reads of the program is what the program produced for one app: the
placement, the route trees, the constants and registers it folded into
PE inputs, and the record's wirelength and critical path. Which
connections must be routed it works out itself, from the app netlist
as the configuration file holds it (instances ``[name, kind, op,
const]``, nets ``[name, [src, port], [[sink, port], ...]]``).

Packing, as §3.4 allows it: a constant that feeds exactly one PE input
is folded into that input; a register is placed on a PE tile or, where
it feeds exactly one PE input, absorbed into that input. Every other
instance is placed. The connections to route are each app net's driver
to each of its sinks, with a folded constant's nets dropped and a sink
that is an absorbed register standing for the PE input that absorbed
it; all connections from one driver port are one net (§3.3).

For every routed app it counts:

- ``bad_packing``: a folded constant whose value or port the program
  records wrongly, a register neither placed nor absorbed, an
  absorption the program does not record, or one it records that the
  netlist does not have;
- ``bad_placements``: an instance that must be placed and is not, one
  placed that the app does not have, off the array, on a tile another
  instance holds, or on a tile of the wrong class (IO on the ring
  without its corners, cores inside it; memories on memory columns, PEs
  and placed registers off them);
- ``bad_routes``: a connection with no route from its driver's port to
  its sink's port; a route from a port that drives nothing, to a port
  no connection names, over a (parent, child) pair that is no edge of
  the graph, through a port that is not its own, or in a loop;
- ``overused_nodes``: graph nodes that two routes of one app share;
- ``wirelength_gap``: |record wirelength - route nodes counted|;
- ``critical_path_gap``: |record critical path - recomputed| / recomputed.

The critical path follows the paper's static timing model (Fig. 7):
a route segment costs its nodes' intrinsic delays and its wires'
delays; a pipeline register crossed on a route ends a path and starts
the next; a PE adds 0.8 ns and an IO 0.1 ns between input and output.
Registers and memories are sequential, as the fabric emulates them (a
register or a memory's read data is one cycle late): a path ends at
their input, and their output launches a new one after their core
delay (0.8 ns for a register on a PE tile, 0.1 ns for a memory). An
absorbed register ends the path at the PE input that holds it. The
critical path is the latest arrival at any routed sink.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

PE_CORE_NS = 0.8
OTHER_CORE_NS = 0.1
#: port a net endpoint lands on, by instance kind and netlist port
_IO_PORT = {"io_in": "io_out", "io_out": "io_in"}
_PORT_ALIAS = {"out": "res0", "in": "data0"}
PLACED_KINDS = ("pe", "mem", "io_in", "io_out")

#: (driver instance, driver port, sink instance, sink port, the sink
#: port holds an absorbed register)
Connection = Tuple[str, str, str, str, bool]
#: (source node, sink nodes, {child node: parent node})
Route = Tuple[int, List[int], Dict[int, int]]


def port_name(kind: str, port: str) -> str:
    """Interconnect port that an instance's netlist port lands on."""
    return _IO_PORT.get(kind) or _PORT_ALIAS.get(port, port)


def tile_class(x: int, y: int, width: int, height: int,
               mem_columns: Sequence[int], io_ring: bool) -> str:
    """``io``, ``mem``, ``pe`` or ``none`` (a ring corner)."""
    border = x in (0, width - 1) or y in (0, height - 1)
    if io_ring and border:
        corner = x in (0, width - 1) and y in (0, height - 1)
        return "none" if corner else "io"
    if mem_columns and x in mem_columns:
        return "mem"
    return "pe"


class Packing:
    """What packing must leave of one app netlist (see the module
    docstring). ``placed``: the names of the instances the program
    placed; a register among them is placed, any other is absorbed."""

    def __init__(self, app: Dict, placed: Iterable[str]):
        self.kind = {n: k for n, k, _, _ in app["instances"]}
        self.const = {n: int(c) for n, _, _, c in app["instances"]}
        placed = set(placed)
        sinks: Dict[str, List[Tuple[str, str]]] = {}
        for _, (src, _), net_sinks in app["nets"]:
            sinks.setdefault(src, []).extend(tuple(s) for s in net_sinks)
        #: folded constant -> (PE, input port)
        self.folded: Dict[str, Tuple[str, str]] = {}
        #: absorbed register -> (PE, input port)
        self.absorbed: Dict[str, Tuple[str, str]] = {}
        #: registers that can be neither placed nor absorbed as found
        self.stray: List[str] = []
        for name, kind in self.kind.items():
            out = sinks.get(name, [])
            one_pe = (len(out) == 1 and self.kind.get(out[0][0]) == "pe")
            if kind == "const":
                if not one_pe:
                    raise ValueError(f"constant {name} feeds no single PE "
                                     "input; nothing folds it")
                self.folded[name] = out[0]
            elif kind == "reg" and name not in placed:
                if one_pe:
                    self.absorbed[name] = out[0]
                else:
                    self.stray.append(name)
        #: instances that must be placed
        self.to_place = [n for n, k in self.kind.items()
                         if k in PLACED_KINDS
                         or (k == "reg" and n not in self.absorbed
                             and n not in self.stray)]

    def placed_kind(self, name: str) -> str:
        """Tile class an instance is timed and placed as."""
        return {"io_in": "io", "io_out": "io", "mem": "mem"}.get(
            self.kind[name], "pe")

    def connections(self, app: Dict) -> List[Connection]:
        out: List[Connection] = []
        for _, (src, sport), net_sinks in app["nets"]:
            if src in self.folded or src in self.absorbed:
                continue        # folded into, or extended to, its PE
            for sink, port in net_sinks:
                if sink in self.absorbed:
                    host, hport = self.absorbed[sink]
                    out.append((src, sport, host, hport, True))
                else:
                    out.append((src, sport, sink, port, False))
        return out

    def bad(self, const_ports: Dict[str, Dict[str, int]],
            reg_ports: Dict[str, Sequence[str]]) -> int:
        """Folds and absorptions the program records wrongly, plus
        registers neither placed nor absorbable."""
        want_c = {(pe, port): self.const[c]
                  for c, (pe, port) in self.folded.items()}
        got_c = {(pe, port): int(v) for pe, ports in const_ports.items()
                 for port, v in ports.items()}
        bad = sum(1 for k in set(want_c) | set(got_c)
                  if want_c.get(k) != got_c.get(k))
        want_r = Counter(self.absorbed.values())
        got_r = Counter((pe, port) for pe, ports in reg_ports.items()
                        for port in ports)
        bad += sum(((want_r - got_r) + (got_r - want_r)).values())
        return bad + len(self.stray)


def check_placement(pack: Packing, placement: Dict[str, Tuple[int, int]],
                    spec: Dict) -> int:
    """Instances placed off their legal tiles, placed though the app
    does not have them, or not placed though they must be."""
    w, h = spec["width"], spec["height"]
    mem_cols = tuple(spec.get("mem_columns", ()))
    io_ring = spec.get("io_ring", True)
    bad = sum(1 for name in pack.to_place if name not in placement)
    seen = set()
    for name, xy in placement.items():
        if name not in pack.to_place:
            bad += 1
            continue
        x, y = int(xy[0]), int(xy[1])
        if not (0 <= x < w and 0 <= y < h) or (x, y) in seen:
            bad += 1
            continue
        seen.add((x, y))
        want = pack.placed_kind(name)
        if want == "io" and not io_ring:
            continue            # without a ring an IO may sit anywhere
        if tile_class(x, y, w, h, mem_cols, io_ring) != want:
            bad += 1
    return bad


class Graph:
    """Plain view of the interconnect: per node its kind, tile, width,
    port name (ports only), intrinsic delay, fan-in ids and the wire
    delay of each fan-in edge."""

    def __init__(self, kind: Sequence[str], x: Sequence[int],
                 y: Sequence[int], width: Sequence[int],
                 port: Sequence[Optional[str]], delay: Sequence[float],
                 fanin: Sequence[Sequence[int]],
                 wire: Sequence[Sequence[float]]):
        self.kind, self.x, self.y = kind, x, y
        self.width, self.port, self.delay = width, port, delay
        self.wire_of = [dict(zip(f, d)) for f, d in zip(fanin, wire)]
        self.port_at = {(x[i], y[i], port[i], width[i]): i
                        for i in range(len(kind)) if kind[i] == "PORT"}


def _path_to_root(tree: Dict[int, int], src: int, sink: int,
                  limit: int) -> Optional[List[int]]:
    """Node ids from ``src`` to ``sink`` along child -> parent links,
    or None when the links never reach ``src``."""
    path = [sink]
    node = sink
    while node != src:
        if node not in tree or len(path) > limit:
            return None
        node = tree[node]
        path.append(node)
    path.reverse()
    return path


def _segment(g: Graph, path: List[int]) -> Tuple[float, int]:
    """(delay after the last register crossed, registers crossed)."""
    d = g.delay[path[0]]
    regs = 0
    for a, b in zip(path, path[1:]):
        if g.kind[b] == "REGISTER":
            regs += 1
            d = 0.0
        d += g.delay[b] + g.wire_of[b][a]
    return d, regs


def check_app(g: Graph, spec: Dict, app: Dict,
              placement: Dict[str, Tuple[int, int]],
              routes: Sequence[Route],
              const_ports: Dict[str, Dict[str, int]],
              reg_ports: Dict[str, Sequence[str]],
              record: Dict) -> Dict[str, float]:
    """Counts for one routed app (see the module docstring)."""
    pack = Packing(app, placement)
    width = spec["track_width"]
    out = {"bad_packing": pack.bad(const_ports, reg_ports),
           "bad_placements": check_placement(pack, placement, spec),
           "bad_routes": 0, "overused_nodes": 0, "wirelength_gap": 0,
           "critical_path_gap": 0.0}
    n = len(g.kind)
    limit = n + 1

    def endpoint(inst: str, port: str) -> Optional[int]:
        xy = placement.get(inst)
        if xy is None or inst not in pack.kind:
            return None
        return g.port_at.get((int(xy[0]), int(xy[1]),
                              port_name(pack.kind[inst], port), width))

    by_src: Dict[int, Route] = {}
    owner: Dict[int, int] = {}
    overused = set()
    wirelength = 0
    for k, (src, sinks, tree) in enumerate(routes):
        wirelength += len(tree)
        if src in by_src or not all(0 <= i < n for pair in tree.items()
                                    for i in pair):
            out["bad_routes"] += 1
            continue
        by_src[src] = (src, list(sinks), tree)
        for nid in set(tree) | {src}:
            if nid in owner and owner[nid] != k:
                overused.add(nid)
            owner[nid] = k
    out["overused_nodes"] = len(overused)
    out["wirelength_gap"] = abs(int(record["wirelength"]) - wirelength)

    # connections grouped into nets by driver port node
    nets: Dict[int, List[Tuple[Connection, Optional[int]]]] = {}
    for conn in pack.connections(app):
        src = endpoint(conn[0], conn[1])
        if src is None:
            out["bad_routes"] += 1
            continue
        nets.setdefault(src, []).append((conn, endpoint(conn[2], conn[3])))
    segments: Dict[Connection, Tuple[float, int]] = {}
    for src, conns in nets.items():
        route = by_src.pop(src, None)
        want = {s for _, s in conns}
        if route is None or None in want:
            out["bad_routes"] += len(conns)
            continue
        _, sinks, tree = route
        ok = set(sinks) == want
        for child, parent in tree.items():
            if parent not in g.wire_of[child]:
                ok = False          # no such edge in the graph
            if g.kind[child] == "PORT" and child not in want:
                ok = False          # runs through another core's port
        for conn, sink in conns:
            path = _path_to_root(tree, src, sink, limit)
            if path is None:
                ok = False
            else:
                segments[conn] = _segment(g, path)
        out["bad_routes"] += 0 if ok else len(conns)
    out["bad_routes"] += len(by_src)    # routes from ports driving nothing
    if all(out[k] == 0 for k in ("bad_routes", "bad_placements",
                                 "bad_packing")):
        crit = critical_path(pack, list(segments), segments)
        rec = float(record["critical_path_ns"])
        out["critical_path_gap"] = abs(rec - crit) / max(crit, 1e-12)
    return out


def critical_path(pack: Packing, conns: Sequence[Connection],
                  segments: Dict[Connection, Tuple[float, int]]) -> float:
    """Latest arrival at any routed sink (see the module docstring)."""
    launch = {"mem": OTHER_CORE_NS, "reg": PE_CORE_NS}
    out_arrival: Dict[str, float] = {
        name: launch[kind] for name, kind in pack.kind.items()
        if kind in launch}
    sink_arrival: Dict[Connection, float] = {}
    # relax to the fixpoint: sequential instances cut every cycle, so
    # what is left is acyclic and longest paths settle within
    # len(instances) + 1 rounds
    for _ in range(len(pack.kind) + 1):
        changed = False
        for conn in conns:
            src, _, sink, _, absorbed = conn
            d, regs = segments[conn]
            arr = (out_arrival.get(src, 0.0) if regs == 0 else 0.0) + d
            sink_arrival[conn] = arr
            kind = pack.kind[sink]
            if kind in launch:
                continue        # the path ends at a register or memory
            core = PE_CORE_NS if kind == "pe" else OTHER_CORE_NS
            # an absorbed register ends the path at the PE input and
            # launches the PE's operand at the clock
            start = 0.0 if absorbed else arr
            if start + core > out_arrival.get(sink, 0.0) + 1e-12:
                out_arrival[sink] = start + core
                changed = True
        if not changed:
            break
    return max(sink_arrival.values(), default=0.0)


COUNTS = ("bad_packing", "bad_placements", "bad_routes", "overused_nodes")
GAPS = ("wirelength_gap", "critical_path_gap")


def worst(rows: Iterable[Dict[str, float]]) -> Dict[str, float]:
    """Counts summed and gaps maximised over apps and points."""
    total = {k: 0 for k in COUNTS + GAPS}
    total["critical_path_gap"] = 0.0
    for row in rows:
        for k in COUNTS:
            total[k] += row[k]
        for k in GAPS:
            total[k] = max(total[k], row[k])
    return total
