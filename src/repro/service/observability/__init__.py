"""Per-job lifecycle tracing, windowed stats, event log, replay, live view.

See ``docs/OBSERVABILITY.md``.  Spans (``repro.core.spans``, re-exported
here) are always recorded; lifecycle hops are opt-in.  Enable hops with
``StratumConfig.make(..., trace=True)`` (in-memory traces on every
``JobReport``) or ``trace_dir="/path"`` (plus a durable JSONL event log
replayable via ``python -m repro.service.observability.replay``).
"""

from ...core.spans import (compile_totals, record, scope, self_times,
                           span)
from .events import (TraceLog, TraceSink, hop_record, record_hop,
                     record_span, span_record)
from .trace import (ADMITTED, ANALYZED, CANCELLED, COALESCED, COMPLETED,
                    DISPATCHED, EVENTS, FAILED, FAILOVER, PREEMPTED, QUEUED,
                    REQUEUED, RETUNED, ROUTED, SHED, SUBMITTED, TERMINAL,
                    JobTrace, make_hop)
from .windows import (MAX_SAMPLES, ThroughputCollector,
                      merge_window_snapshots, percentile)

__all__ = [
    "JobTrace", "make_hop", "EVENTS", "TERMINAL",
    "span", "scope", "record", "self_times", "compile_totals",
    "SUBMITTED", "ANALYZED", "ADMITTED", "QUEUED", "COALESCED", "DISPATCHED",
    "PREEMPTED", "REQUEUED", "ROUTED", "FAILOVER", "RETUNED", "COMPLETED",
    "FAILED", "SHED", "CANCELLED",
    "TraceSink", "TraceLog", "hop_record", "record_hop", "span_record",
    "record_span",
    "ThroughputCollector", "merge_window_snapshots", "percentile",
    "MAX_SAMPLES",
]
