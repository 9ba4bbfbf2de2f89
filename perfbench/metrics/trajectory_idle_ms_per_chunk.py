"""Device-idle milliseconds per chunk inside the driver's trajectory span
(``repro.loop.trajectory``: from ``run_chunk``'s return to the end of the
chunk's per-round records in ``train/loop.py``), mean over the spans that
lie wholly in the window and over chips. It is the part of
``driver_gap_ms`` spent pulling the stacked per-round metrics to the host.
A program without the span reads nothing."""

from perfbench.scopes import idle_within

SPAN = "repro.loop.trajectory"


def read(run):
    if run.trace is None:
        return None
    idle, spans = idle_within(run.trace, SPAN)
    if not spans or not run.trace.devices:
        return None
    return 1e3 * idle / spans
