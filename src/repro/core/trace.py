"""Spans and a compile counter inside the DSE path.

Off by default. :func:`span` then checks one module-level variable and
returns a shared no-op context: no clock read, no span object, no JAX
import. Inside :func:`recording` each span records

- its ``name``: layers separated by dots (``place.detailed``,
  ``route.app``, ``device.wait``);
- its ``parent``: the ``id`` of the innermost span open on the same
  thread when it started;
- its ``tag``: the design point it works for (the spec digest, set by
  the executor's ``point`` span and inherited by the spans under it);
- its ``thread`` (name), ``t0`` and ``t1`` on ``time.perf_counter``;
- ``cpu_s``: the thread CPU seconds it took (``time.thread_time``);
- ``jit_n`` and ``jit_s``: the programs JAX compiled or loaded from its
  persistent cache, and the seconds JAX spent tracing, lowering and
  compiling or loading, while it was the innermost open span of its
  thread (JAX reports these on the thread that dispatches);
- its ``attrs``.

Each span is also a ``jax.profiler.TraceAnnotation`` named
``canal:<name>``, so a profiler trace shows it on the device's clock.
Compile time with no span open goes to the recording's ``(none)`` row.
While a recording is on, ``DSEService.stats()["spans"]`` gives its
:meth:`Recording.summary`.
"""
from __future__ import annotations

import itertools
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Iterator, List, Optional

SPAN_PREFIX = "canal:"
#: JAX's duration events for tracing to a jaxpr, lowering to MLIR, and
#: compiling (or loading from the persistent cache) one program
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
JIT_EVENTS = frozenset((TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT))
UNATTRIBUTED = "(none)"

#: the recording in progress; None is the off state :func:`span` checks
_active: Optional["Recording"] = None
_switch = threading.Lock()
_local = threading.local()
_ids = itertools.count()


class _Off:
    """The shared context :func:`span` returns while nothing records."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


_OFF = _Off()


def _stack() -> List["Span"]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class Span:
    """One recorded span; a context manager while it is open."""

    __slots__ = ("id", "name", "parent", "tag", "thread", "t0", "t1",
                 "cpu_s", "jit_n", "jit_s", "attrs", "_rec", "_cpu0",
                 "_ann")

    def __init__(self, rec: "Recording", name: str, tag: Any,
                 attrs: Dict[str, Any]):
        self.id = next(_ids)
        self.name = name
        self.tag = tag
        self.attrs = attrs
        self.parent: Optional[int] = None
        self.thread = threading.current_thread().name
        self.t0 = self.t1 = 0.0
        self.cpu_s = 0.0
        self.jit_n = 0
        self.jit_s = 0.0
        self._rec = rec
        self._ann = None

    def set(self, **attrs) -> None:
        """Add attributes known only inside the span (a round count)."""
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        stack = _stack()
        if stack:
            self.parent = stack[-1].id
            if self.tag is None:
                self.tag = stack[-1].tag
        self._ann = self._rec._annotation(SPAN_PREFIX + self.name)
        self._ann.__enter__()
        stack.append(self)
        self._cpu0 = time.thread_time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        self.cpu_s = time.thread_time() - self._cpu0
        _stack().pop()
        self._ann.__exit__(None, None, None)
        self._rec._add(self)
        return False


def span(name: str, tag: Any = None, **attrs):
    """A span ``name`` around a ``with`` block; ``tag`` names the design
    point (default: the enclosing span's). A shared no-op while nothing
    records."""
    rec = _active
    if rec is None:
        return _OFF
    return Span(rec, name, tag, attrs)


def handoff(name: str, fn: Callable) -> Callable:
    """``fn`` for another thread to run, with a span ``name`` from this
    call until ``fn`` starts there (a queue wait; it has no parent, no
    CPU time and no profiler annotation). ``fn`` itself while nothing
    records."""
    rec = _active
    if rec is None:
        return fn
    t0 = time.perf_counter()

    def started(*args, **kwargs):
        s = Span(rec, name, None, {})
        s.t0, s.t1 = t0, time.perf_counter()
        rec._add(s)
        return fn(*args, **kwargs)

    return started


def active() -> Optional["Recording"]:
    """The recording in progress, or None."""
    return _active


def _row() -> Dict[str, float]:
    return {"n": 0, "wall_s": 0.0, "self_s": 0.0, "cpu_s": 0.0,
            "jit_n": 0, "jit_s": 0.0}


class Recording:
    """Spans kept in memory while :func:`recording` is on."""

    def __init__(self, annotation: Callable[[str], Any]):
        self.spans: List[Span] = []
        #: compile events with no span open on their thread
        self.unattributed = {"jit_n": 0, "jit_s": 0.0}
        self._annotation = annotation
        self._lock = threading.Lock()

    def _add(self, s: Span) -> None:
        with self._lock:
            self.spans.append(s)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event not in JIT_EVENTS:
            return
        # nested events (a jit traced while tracing its caller) end
        # first; the enclosing one then counts only the time beyond them
        end = time.perf_counter()
        start = end - duration
        done = getattr(_local, "jit", None)
        if done is None:
            done = _local.jit = []
        covered = 0.0
        while done and done[-1][0] >= start:
            a, b = done.pop()
            covered += b - a
        done.append((start, end))
        del done[:-16]
        seconds = max(duration - covered, 0.0)
        n = int(event == COMPILE_EVENT)
        stack = getattr(_local, "stack", None)
        if stack:
            # only this thread touches its innermost span
            stack[-1].jit_n += n
            stack[-1].jit_s += seconds
        else:
            with self._lock:
                self.unattributed["jit_n"] += n
                self.unattributed["jit_s"] += seconds

    # ------------------------------------------------------------ reading
    def per_tag(self, tags=None) -> Dict[Any, Dict[str, Dict[str, float]]]:
        """:meth:`summary` for each tag (design point) apart."""
        with self._lock:
            spans = list(self.spans)
            none = dict(self.unattributed)
        children: Dict[int, float] = {}
        for s in spans:
            if s.parent is not None:
                children[s.parent] = (children.get(s.parent, 0.0)
                                      + s.t1 - s.t0)
        out: Dict[Any, Dict[str, Dict[str, float]]] = {}
        for s in spans:
            if tags is not None and s.tag not in tags:
                continue
            row = out.setdefault(s.tag, {}).setdefault(s.name, _row())
            wall = s.t1 - s.t0
            row["n"] += 1
            row["wall_s"] += wall
            row["self_s"] += wall - children.get(s.id, 0.0)
            row["cpu_s"] += s.cpu_s
            row["jit_n"] += s.jit_n
            row["jit_s"] += s.jit_s
        if none["jit_n"] or none["jit_s"]:
            if tags is None or None in tags:
                out.setdefault(None, {})[UNATTRIBUTED] = dict(_row(), **none)
        return out

    def summary(self, tags=None) -> Dict[str, Dict[str, float]]:
        """Per span name: ``n``, ``wall_s``, ``self_s`` (wall minus the
        time its child spans cover), ``cpu_s``, ``jit_n`` and ``jit_s``,
        over the spans whose tag is in ``tags`` (all when None)."""
        out: Dict[str, Dict[str, float]] = {}
        for rows in self.per_tag(tags).values():
            for name, row in rows.items():
                acc = out.setdefault(name, _row())
                for k, v in row.items():
                    acc[k] += v
        return out


@contextmanager
def recording() -> Iterator[Recording]:
    """Record spans and compile events until the block ends. One
    recording at a time per process."""
    global _active
    import jax.monitoring
    import jax.profiler

    rec = Recording(jax.profiler.TraceAnnotation)
    listener = rec._on_event
    with _switch:
        if _active is not None:
            raise RuntimeError("a trace recording is already on")
        jax.monitoring.register_event_duration_secs_listener(listener)
        _active = rec
    try:
        yield rec
    finally:
        with _switch:
            _active = None
            jax.monitoring.unregister_event_duration_listener(listener)
