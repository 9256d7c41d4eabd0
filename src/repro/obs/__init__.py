"""repro.obs — unified telemetry: stage tracing, metrics stream, reporting.

See :mod:`repro.obs.tracing` for the span/scope layer and
:mod:`repro.obs.metrics` for the JSONL event stream. The reporting layer
lives in ``experiments/make_report.py`` (overhead accounting) and
``benchmarks/kernels_bench.py`` (``obs.enabled_over_disabled`` gate).
"""

from repro.obs.tracing import (
    STAGE_CAPTURE,
    STAGE_CHUNK,
    STAGE_DRAIN,
    STAGE_FLIP,
    STAGE_FWD_BWD,
    STAGE_GATHER,
    STAGE_HISTORY,
    STAGE_INVERSE,
    STAGE_PRECOND,
    STAGE_PRECOND_ROWS,
    STAGE_REDUCE,
    STAGE_STATS,
    STAGE_UPDATE,
    ProfileCapture,
    Span,
    SpanRecord,
    kernel_scope,
)
from repro.obs.metrics import SCHEMA_VERSION, MetricsLogger, inverse_tally

__all__ = [
    "STAGE_CAPTURE",
    "STAGE_CHUNK",
    "STAGE_DRAIN",
    "STAGE_FLIP",
    "STAGE_FWD_BWD",
    "STAGE_GATHER",
    "STAGE_HISTORY",
    "STAGE_INVERSE",
    "STAGE_PRECOND",
    "STAGE_PRECOND_ROWS",
    "STAGE_REDUCE",
    "STAGE_STATS",
    "STAGE_UPDATE",
    "ProfileCapture",
    "Span",
    "SpanRecord",
    "kernel_scope",
    "SCHEMA_VERSION",
    "MetricsLogger",
    "inverse_tally",
]
