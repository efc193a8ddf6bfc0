"""What a traffic generator gets (:class:`Ctx`) and the pieces every
generator uses: the traced window and the checks with their limits."""
from __future__ import annotations

import contextlib
import shutil
import tempfile
from dataclasses import dataclass, field
from typing import Dict, Optional

from .spans import Recorder


@dataclass
class Ctx:
    cell: Dict
    config: Dict
    traffic: Dict
    seed: int
    seconds: float
    trace: bool
    bench_dir: str
    repo_root: str
    rec: Recorder = field(default_factory=Recorder)
    #: the reduced profiler trace of the window (``tracing.reduce``)
    trace_summary: Optional[Dict] = None

    def __post_init__(self):
        # spans go into the profiler's trace only in a traced run
        self.rec.trace = self.trace

    @contextlib.contextmanager
    def window(self):
        """The measured window: a ``window`` span, profiled when the run
        traces. The trace is reduced once the window has closed."""
        trace_dir = None
        if self.trace:
            import jax
            trace_dir = tempfile.mkdtemp(prefix="canalbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with self.rec.span("window"):
                yield
        finally:
            if trace_dir is not None:
                import jax
                from . import tracing
                jax.profiler.stop_trace()
                try:
                    self.trace_summary = tracing.reduce_dir(
                        trace_dir, int(self.cell["chips"]))
                finally:
                    shutil.rmtree(trace_dir, ignore_errors=True)


def checks(values: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """``{name: {"value", "limit"}}`` for every number compared."""
    return {k: {"value": values[k], "limit": limits[k]} for k in limits}


def passed(table: Dict) -> bool:
    return all(row["value"] <= row["limit"] for row in table.values())

