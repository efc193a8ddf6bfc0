"""Applications of a configuration file, built with the program's own
``AppGraph`` API from the netlist data the file holds (instances as
``[name, kind, op, const]``, nets as ``[name, [src, port], [[sink,
port], ...]]``). The data is the yardstick's copy: editing the
program's app generators does not change it."""
from __future__ import annotations

from typing import Callable, Dict


def build(name: str, data: Dict):
    from repro.core.pnr.app import AppGraph

    g = AppGraph()
    for inst, kind, op, const in data["instances"]:
        g.add(inst, kind, op=op, const=int(const))
    for net, (src, port), sinks in data["nets"]:
        g.connect(src, port, *[tuple(s) for s in sinks], name=net)
    g.validate()
    g.bench_app = name
    return g


def builders(config: Dict) -> Dict[str, Callable]:
    """``{app name: () -> AppGraph}``, the form the executor takes."""
    return {name: (lambda n=name, d=data: build(n, d))
            for name, data in config["apps"].items()}

