"""Reduce a JAX profiler trace to device busy time, idle gaps and the
device operations that took the most time.

Input is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes,
read with ``jax.profiler.ProfileData`` (nothing but JAX). Device planes
are those named ``/device:TPU:<n>``; on each, the operations are the
events of its ``XLA Ops`` line, each named ``<program>/<op>`` after the
``XLA Modules`` event it starts in (the plane's other lines when it
has no ``XLA Ops``). Host spans are the ``bench:<name>`` annotations of
:mod:`.spans`, on the same clock. The traced window is the
``bench:window`` span.

- busy: the union of operation intervals inside the window, per chip,
  averaged over the chips the cell uses (``/device:TPU:0`` up to its
  chip count; a chip the cell leaves alone does not dilute it);
- idle gaps: the rest of the window, each gap named by the innermost
  harness span open at its midpoint ("none" when no span was open),
  summed by name;
- top ops: operation time inside the window summed by event name.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Sequence, Tuple

from .spans import SPAN_PREFIX

WINDOW_SPAN = "window"
Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str) -> Tuple[Dict[str, List[Tuple[str, float, float]]],
                                    List[Tuple[str, float, float]]]:
    """``({device plane: [(op, start_ns, end_ns)]}, [(span, start_ns,
    end_ns)])`` from a trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get("XLA Ops")
            modules = lines.get("XLA Modules")
            if ops is None:
                devices[plane.name] = [
                    (short_name(ev.name), ev.start_ns,
                     ev.start_ns + ev.duration_ns)
                    for ln in lines.values() for ev in ln.events]
            else:
                devices[plane.name] = named_ops(
                    [(ev.name, ev.start_ns, ev.duration_ns)
                     for ev in ops.events],
                    [] if modules is None else
                    [(ev.name, ev.start_ns, ev.duration_ns)
                     for ev in modules.events])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return devices, spans


def short_name(name: str) -> str:
    """``jit_f(123)`` -> ``jit_f``; ``%fusion.3 = f32[...] ...`` ->
    ``fusion.3``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return name.split("(", 1)[0] if name.endswith(")") else name


def named_ops(ops: Sequence[Tuple[str, float, float]],
              modules: Sequence[Tuple[str, float, float]]
              ) -> List[Tuple[str, float, float]]:
    """``(module/op, start_ns, end_ns)`` for ``(name, start_ns,
    duration_ns)`` op events, each named with the program (module)
    running when it started."""
    mods = sorted((a, a + d, short_name(n)) for n, a, d in modules)
    out, i = [], 0
    for name, a, d in sorted(ops, key=lambda e: e[1]):
        while i < len(mods) and mods[i][1] < a:
            i += 1
        owner = mods[i][2] if i < len(mods) and mods[i][0] <= a else "?"
        out.append((f"{owner}/{short_name(name)}", a, a + d))
    return out


def union(intervals: Sequence[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged intervals clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


class _SpanIndex:
    """Innermost (latest-starting) non-window span open at a time."""

    def __init__(self, spans: Sequence[Tuple[str, float, float]]):
        # equal starts: the shorter (inner) span sorts last
        self.spans = sorted(((a, b, name) for name, a, b in spans
                             if name != WINDOW_SPAN),
                            key=lambda s: (s[0], -s[1]))
        self.starts = [a for a, _, _ in self.spans]

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t)
        while i > 0:
            i -= 1
            a, b, name = self.spans[i]
            if b >= t:
                return name
        return "none"


def reduce(devices: Dict[str, List[Tuple[str, float, float]]],
           spans: Sequence[Tuple[str, float, float]], top: int = 10
           ) -> Dict:
    """Busy and window seconds, idle gaps by host span, top device ops.

    Raises when the trace has no window span or no device plane: a
    traced run that cannot be reduced has no per-layer numbers."""
    windows = [(a, b) for name, a, b in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError("trace has no bench:window span")
    if not devices:
        raise ValueError("trace has no TPU device plane")
    lo = min(a for a, _ in windows)
    hi = max(b for _, b in windows)
    index = _SpanIndex(spans)
    busy_ns, gap_by_name, op_ns = [], {}, {}
    for events in devices.values():
        merged = union([(a, b) for _, a, b in events], lo, hi)
        busy_ns.append(sum(b - a for a, b in merged))
        for a, b in gaps(merged, lo, hi):
            name = index.at(0.5 * (a + b))
            gap_by_name[name] = gap_by_name.get(name, 0.0) + (b - a)
        for name, a, b in events:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                op_ns[name] = op_ns.get(name, 0.0) + (b - a)
    n = len(devices)
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy_ns) / n * 1e-9

    def ranked(d: Dict[str, float]) -> List[List]:
        rows = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / n * 1e-9] for k, v in rows]

    return {"busy_s": busy_s, "window_s": window_s,
            "device_ops": ranked(op_ns), "idle_gaps": ranked(gap_by_name)}


def used_planes(devices: Dict, chips: int) -> Dict:
    """The device planes of the first ``chips`` TPUs."""
    return {name: events for name, events in devices.items()
            if int(name.rsplit(":", 1)[1]) < chips}


def reduce_dir(trace_dir: str, chips: int) -> Dict:
    devices, spans = read_xplane(find_xplane(trace_dir))
    return reduce(used_planes(devices, chips), spans)
