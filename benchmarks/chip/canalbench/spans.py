"""Host spans around calls into the program, and the compile counter.

The benchmark puts its own spans around the program's layer entry
points (it wraps them for the length of a run and restores them after),
so the per-layer metrics need nothing inside the program. Each span is
kept in memory as ``(name, tag, start, end)`` on ``time.perf_counter``;
``tag`` is the design point the calling thread is working on. With
tracing on, every span is also a ``jax.profiler.TraceAnnotation`` named
``bench:<name>``, which puts it on the profiler's clock so idle gaps on
the device can be named by what the host was doing.

The compile counter listens to JAX's backend-compile event, which JAX
records for every program it compiles or loads from the persistent
cache: a count inside the window is a program that was not in memory.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SPAN_PREFIX = "bench:"


class Recorder:
    def __init__(self, trace: bool = False):
        self.trace = trace
        self.spans: List[Tuple[str, Any, float, float]] = []
        self.compiles: List[float] = []
        self._local = threading.local()
        self._lock = threading.Lock()
        self._patched: List[Tuple[Any, str, Any, bool]] = []
        self._listener = None

    # ---------------------------------------------------------------- tags
    @property
    def tag(self) -> Any:
        return getattr(self._local, "tag", None)

    @tag.setter
    def tag(self, value: Any) -> None:
        self._local.tag = value

    # --------------------------------------------------------------- spans
    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.trace:
            import jax
            ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            with self._lock:
                self.spans.append((name, self.tag, t0, t1))

    def wrap(self, owner: Any, attr: str, name: str) -> None:
        """Replace ``owner.attr`` with a spanned call until
        :meth:`restore`."""
        had = attr in vars(owner) if hasattr(owner, "__dict__") else True
        fn = getattr(owner, attr)

        @functools.wraps(fn)
        def spanned(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)

        self._patched.append((owner, attr, fn, had))
        setattr(owner, attr, spanned)

    def patch(self, owner: Any, attr: str, new: Any) -> None:
        """Replace ``owner.attr`` with ``new`` until :meth:`restore`."""
        had = attr in vars(owner) if hasattr(owner, "__dict__") else True
        self._patched.append((owner, attr, getattr(owner, attr), had))
        setattr(owner, attr, new)

    def restore(self) -> None:
        while self._patched:
            owner, attr, fn, had = self._patched.pop()
            if had:
                setattr(owner, attr, fn)
            else:
                delattr(owner, attr)

    # ------------------------------------------------------------ compiles
    def count_compiles(self) -> None:
        import jax.monitoring

        def listener(event: str, duration: float, **kwargs) -> None:
            if event == COMPILE_EVENT:
                with self._lock:
                    self.compiles.append(time.perf_counter())

        self._listener = listener
        jax.monitoring.register_event_duration_secs_listener(listener)

    def close(self) -> None:
        self.restore()
        if self._listener is not None:
            import jax.monitoring
            jax.monitoring.unregister_event_duration_listener(self._listener)
            self._listener = None

    # ------------------------------------------------------------- reading
    def compiles_between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.compiles if t0 <= t <= t1)

    def per_tag(self, name: str, tags: Optional[set] = None
                ) -> Dict[Any, float]:
        """Seconds in spans ``name`` summed per tag."""
        out: Dict[Any, float] = {}
        for n, tag, t0, t1 in self.spans:
            if n == name and (tags is None or tag in tags):
                out[tag] = out.get(tag, 0.0) + (t1 - t0)
        return out
