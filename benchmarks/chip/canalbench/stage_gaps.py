"""Device-idle gaps of a traced window, named by the program's own spans.

:func:`tracing.reduce` names each gap by the innermost harness span
(``bench:<name>``); this names it by the innermost program span
(``canal:<name>``, written by ``repro.core.trace`` while a recording
is on) open at the gap's midpoint, on any host thread, and ``none``
where no program span is open. Busy intervals, gaps and the averaging
over the cell's chips are those of :mod:`.tracing`. The result is
diagnostic: it goes into no metric.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

from . import tracing

PROGRAM_PREFIX = "canal:"


def read_program_spans(path: str) -> List[Tuple[str, float, float]]:
    """``[(name, start_ns, end_ns)]`` of the ``canal:`` spans in a
    trace file."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(PROGRAM_PREFIX):
                        spans.append((ev.name[len(PROGRAM_PREFIX):],
                                      ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return spans


def stage_gaps(devices: Dict[str, List[Tuple[str, float, float]]],
               harness_spans: Sequence[Tuple[str, float, float]],
               program_spans: Sequence[Tuple[str, float, float]]
               ) -> List[List]:
    """``[[stage, idle seconds]]``, largest first, over the harness's
    window, averaged over the device planes given."""
    windows = [(a, b) for name, a, b in harness_spans
               if name == tracing.WINDOW_SPAN]
    if not windows or not devices:
        raise ValueError("trace has no window span or no device plane")
    lo = min(a for a, _ in windows)
    hi = max(b for _, b in windows)
    index = tracing._SpanIndex(program_spans)
    by_stage: Dict[str, float] = {}
    for events in devices.values():
        busy = tracing.union([(a, b) for _, a, b in events], lo, hi)
        for a, b in tracing.gaps(busy, lo, hi):
            name = index.at(0.5 * (a + b))
            by_stage[name] = by_stage.get(name, 0.0) + (b - a)
    n = len(devices)
    return [[k, v / n * 1e-9] for k, v in
            sorted(by_stage.items(), key=lambda kv: -kv[1])]


def reduce_dir(trace_dir: str, chips: int) -> List[List]:
    path = tracing.find_xplane(trace_dir)
    devices, harness_spans = tracing.read_xplane(path)
    return stage_gaps(tracing.used_planes(devices, chips), harness_spans,
                      read_program_spans(path))


def table(rows: Sequence[Sequence]) -> str:
    """The rows as text, one stage a line, with its share of the idle
    time."""
    total = sum(s for _, s in rows) or 1.0
    return "\n".join(f"idle {name:<18} {s:10.3f} s {100 * s / total:6.1f}%"
                     for name, s in rows)
