"""repro.telemetry — the fleet telemetry plane.

Three layers (ROADMAP: the observability substrate every subsystem logs
into):

1. **Round records** (``record``/``sink``/``recorder``): a versioned,
   schema'd record per executed round, materialized host-side from the
   per-chunk fold ``DecentralizedLearner`` already fetches — zero extra
   device work — streamed to JSONL + a bounded in-memory ring. Attach
   via ``TelemetryConfig`` (``repro.config``) through
   ``DecentralizedLearner(telemetry=...)`` /
   ``run_protocol_training(telemetry=...)`` /
   ``benchmarks/run.py --telemetry``.
2. **Tracing & profiling** (``trace``/``costs``): named host spans on
   the driver path and named device scopes in the programs (both show
   up in any ``jax.profiler`` trace), per-program trace/compile counts,
   blocked wall-clock timing, and static per-stage cost attribution
   (jaxpr FLOPs × observed trigger fires).
3. **Observatory** (``observatory``, ``python -m repro.telemetry``):
   summarize/tail a recorded stream — comm-vs-loss frontier, sync
   efficiency, per-link-class bytes, Prometheus text exposition — from
   the file alone.
"""
from repro.telemetry.record import (  # noqa: F401
    SCHEMA_VERSION, RoundRecord, chunk_record, meta_record,
    validate_record,
)
from repro.telemetry.recorder import RoundRecorder  # noqa: F401
from repro.telemetry.sink import (  # noqa: F401
    TelemetryLogger, TelemetrySink, console_handler, get_logger,
    jsonl_handler,
)
from repro.telemetry.trace import (  # noqa: F401
    ChunkProfiler, span, timed, trace_counts,
)

__all__ = [
    "SCHEMA_VERSION", "RoundRecord", "chunk_record", "meta_record",
    "validate_record", "RoundRecorder", "TelemetrySink", "TelemetryLogger",
    "get_logger", "console_handler", "jsonl_handler", "timed", "span",
    "trace_counts", "ChunkProfiler",
]
