"""repro.obs — structured round telemetry.

registry        metric catalog + the generic scalar-metrics extraction
                behind History.extra and the trace writer
trace           schema-versioned per-round JSONL traces: writer, record
                builders, validator, reader
timers          host-side compile/steady wall-time attribution: per-stage
                instrumentation (unjitted rounds), whole-round clock,
                compile log, named_scope/TraceAnnotation helpers
selection_probe opt-in dense Eq. 9 score decomposition, fused-kernel
                parity checks, cumulative selection-graph export

Layering: obs sits above core (the selection probe reuses the scoring
definitions) and below comms/fl — the engine, simulator, benchmarks,
and launch drivers all import it; it never imports them.
"""
from repro.obs.registry import (
    DEFAULT_REGISTRY,
    MetricRegistry,
    MetricSpec,
    scalar_metrics,
)
from repro.obs.selection_probe import (
    SelectionGraph,
    check_fused_parity,
    components_of_selected,
    decompose_scores,
    probe_topk,
)
from repro.obs.timers import (
    CompileLog,
    RoundClock,
    StageTimes,
    annotate,
    instrument_stages,
    part,
    stage_name,
)
from repro.obs.trace import (
    SCHEMA_VERSION,
    TraceWriter,
    header_record,
    read_trace,
    round_record,
    score_block,
    stage_profile_record,
    summary_record,
    validate_record,
    validate_trace,
)

__all__ = [
    "DEFAULT_REGISTRY",
    "MetricRegistry",
    "MetricSpec",
    "scalar_metrics",
    "SelectionGraph",
    "check_fused_parity",
    "components_of_selected",
    "decompose_scores",
    "probe_topk",
    "CompileLog",
    "RoundClock",
    "StageTimes",
    "annotate",
    "instrument_stages",
    "part",
    "stage_name",
    "SCHEMA_VERSION",
    "TraceWriter",
    "header_record",
    "read_trace",
    "round_record",
    "score_block",
    "stage_profile_record",
    "summary_record",
    "validate_record",
    "validate_trace",
]
