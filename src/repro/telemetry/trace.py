"""Host-side tracing & profiling hooks (telemetry layer 2).

Wall-clock here always means ``time.perf_counter`` around a call that
BLOCKS on its (pytree) result — timing async dispatch instead of
execution is the classic JAX benchmarking bug (``jax.block_until_ready``
walks a pytree and ignores non-array leaves, so any result shape works).

``span`` is the program's one host-span primitive: a
``jax.profiler.TraceAnnotation`` (a no-op unless a profiler trace is
active, and on the device trace's clock when one is), plus a logger
``span`` event when the logger has handlers. The driver path opens its
spans always, so any ``jax.profiler`` trace shows where the host spent
each chunk:

    repro.loop.chunk                 train/loop.py, one loop iteration
      repro.streams.next_chunk       data/pipeline.py, the chunk's batches
      repro.engine.run_chunk         DecentralizedLearner.run_chunk
        repro.engine.dispatch        the chunk and fold programs' calls
        repro.engine.fetch           the fold's one blocking device_get
        repro.engine.accumulate      host counters (and the recorder)
      repro.loop.trajectory          the per-round trajectory records

Every span of one chunk carries ``round=<the chunk's first round>``.
The device side is named by ``jax.named_scope`` inside the programs
(``repro.local_update``; ``repro.sync``, the sync gate and its cond,
with the stages ``repro.sync.condition/cohort/aggregate/commit``): the
names land in each HLO op's ``op_name`` metadata, which a TPU profile
shows as ``tf_op_name``. An op XLA creates without metadata (a layout
copy, a multi-output fusion) carries no name.

``trace_counts`` counts JAX's own trace and compile events per program
(one ``jax.monitoring`` listener, registered at import); each event is
also marked in an active profiler trace as a ``repro.compile`` host
event. ``ChunkProfiler`` reads the counts for the engine's
compile-vs-execute accounting.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Dict, Optional

import jax
from jax import monitoring

from repro.telemetry.sink import TelemetryLogger, get_logger

__all__ = ["timed", "span", "trace_counts", "COMPILE_MARK",
           "ChunkProfiler"]

# the host event each counted trace or compile leaves in a profiler trace
COMPILE_MARK = "repro.compile"
# jax.monitoring duration events -> the trace_counts() field they count
_COUNTED = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/backend_compile_duration": "compiles",
}
_counts: Dict[str, Dict[str, int]] = {}
_counts_lock = threading.Lock()


def timed(fn: Callable, *args, **kw):
    """``(result, seconds)`` of one call, blocking on the result so the
    wall-clock covers execution, not async dispatch."""
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


@contextlib.contextmanager
def span(name: str, logger: Optional[TelemetryLogger] = None, **fields):
    """A named host span around the with-body: a profiler
    ``TraceAnnotation`` carrying ``fields`` as its stats, and a ``span``
    event with the body's seconds when the logger has handlers. The body
    is responsible for blocking on device work it wants included."""
    log = logger or get_logger()
    t0 = time.perf_counter() if log.enabled else None
    with jax.profiler.TraceAnnotation(name, **fields):
        yield
    if t0 is not None:
        log.event("span", name=name, seconds=time.perf_counter() - t0,
                  **fields)


def _program(fun_name: str) -> str:
    """JAX names a traced function ``chunk`` and its compile
    ``jit(chunk)``: both count under ``chunk``."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _on_duration(event: str, duration: float, **kw) -> None:
    kind = _COUNTED.get(event)
    if kind is None:
        return
    program = _program(str(kw.get("fun_name", "")))
    with _counts_lock:
        c = _counts.setdefault(program, {"traces": 0, "compiles": 0})
        c[kind] += 1
    with jax.profiler.TraceAnnotation(COMPILE_MARK, program=program,
                                      kind=kind):
        pass


def trace_counts() -> Dict[str, Dict[str, int]]:
    """``{program: {"traces": n, "compiles": n}}`` since import: how many
    times JAX traced each function to a jaxpr and compiled (or loaded
    from the persistent cache) each program, in this process. A call
    served from jit's in-memory cache counts nothing."""
    with _counts_lock:
        return {p: dict(c) for p, c in _counts.items()}


monitoring.register_event_duration_secs_listener(_on_duration)


class ChunkProfiler:
    """Compile accounting for the engine's chunk (or step) program, read
    from ``trace_counts``: ``begin(program)`` before a call,
    ``end()`` after it says whether the call compiled ``program`` (a
    new chunk length, or the first call) and counts it in
    ``recompiles``."""

    def __init__(self):
        self.recompiles = 0
        self._program = None
        self._before = 0

    @staticmethod
    def _compiles(program: str) -> int:
        return trace_counts().get(program, {}).get("compiles", 0)

    def begin(self, program: str) -> None:
        self._program = program
        self._before = self._compiles(program)

    def end(self) -> bool:
        compiled = self._compiles(self._program) > self._before
        self.recompiles += compiled
        return compiled
