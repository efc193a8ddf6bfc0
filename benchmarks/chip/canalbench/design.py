"""The bridge from the program's objects to the plain data the checks
read: the interconnect graph (:func:`graph`) and what the program
produced for one app (:func:`app_result`). Everything the checks see
passes through here as lists, dicts and numbers.
"""
from __future__ import annotations

from typing import Dict

from .check_pnr import Graph


def graph(ic) -> Graph:
    """Plain view of an interconnect IR (``ic.nodes()`` order, the ids
    the router uses)."""
    nodes = list(ic.nodes())
    ids = {n: i for i, n in enumerate(nodes)}
    return Graph(kind=[n.kind.name for n in nodes],
                 x=[n.x for n in nodes], y=[n.y for n in nodes],
                 width=[n.width for n in nodes],
                 port=[getattr(n, "port_name", None) for n in nodes],
                 delay=[float(n.delay) for n in nodes],
                 fanin=[[ids[s] for s in n.fan_in] for n in nodes],
                 wire=[[float(d) for d in n.edge_delay_in]
                       for n in nodes])


def app_result(result) -> Dict:
    """What the program produced for one placed and routed app: the
    placement, the route trees (``[src, sinks, [[child, parent],
    ...]]``, by node id), and the constants and registers packing
    folded into PE inputs. The app's netlist is not read from here:
    the checks take it from the configuration."""
    packed = result.packed
    return {
        "placement": {n: [int(x), int(y)]
                      for n, (x, y) in result.placement.items()},
        "routes": [[int(rn.src), [int(s) for s in rn.sinks],
                    [[int(c), int(p)] for c, p in rn.tree.items()]]
                   for rn in result.routing.nets],
        "const_ports": {n: {p: int(v) for p, v in ports.items()}
                        for n, ports in packed.const_ports.items()},
        "reg_ports": {n: list(ports)
                      for n, ports in packed.reg_ports.items()},
    }


def routes_of(result: Dict):
    """Route trees of :func:`app_result` as ``(src, sinks, {child:
    parent})``."""
    return [(src, sinks, {c: p for c, p in tree})
            for src, sinks, tree in result["routes"]]
