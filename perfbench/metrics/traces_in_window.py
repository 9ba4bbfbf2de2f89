"""Traces and compiles inside the traced window, from the program's own
counter (``repro.telemetry.trace.trace_counts``), which marks each one in
the trace as a host event named ``COMPILE_MARK``. A steady window has
none. A program without the counter reads nothing."""


def read(run):
    if run.trace is None:
        return None
    try:
        from repro.telemetry.trace import COMPILE_MARK
    except ImportError:
        return None
    return sum(1 for h in run.trace.host if h.name == COMPILE_MARK
               and run.trace.lo <= h.start and h.end <= run.trace.hi)
