"""Device-resident telemetry for the DIAL reproduction.

Opt-in tracing threaded through both execution paths — decision
provenance and per-OST timelines accumulated as scan outputs inside the
fused loop (no host callbacks), mirrored record-for-record by the host
agent path — plus host-side sinks (JSONL, Chrome ``trace_event``,
markdown), host spans with compile accounting (``obs/spans.py``), and
bench provenance.  See
``docs/OBSERVABILITY.md``.
"""

from repro.obs.diagnose import (ARMS, CAUSES, DIAGNOSIS_SCHEMA,
                                DiagnoseConfig, cause_counts, diagnose,
                                render_diagnosis_markdown,
                                write_diagnosis_report)
from repro.obs.host import HostTracer
from repro.obs.schema import (DECISION_FIELDS, TIMELINE_FIELDS,
                              TRACE_SCHEMA, RunTrace, TraceConfig,
                              timeline_tap)
from repro.obs.sinks import (chrome_trace, read_jsonl,
                             read_jsonl_diagnosis, render_summary,
                             write_chrome, write_jsonl)
from repro.obs.provenance import collect_provenance
from repro.obs.spans import since, span, totals

__all__ = [
    "TRACE_SCHEMA", "DECISION_FIELDS", "TIMELINE_FIELDS",
    "TraceConfig", "RunTrace", "timeline_tap", "HostTracer",
    "write_jsonl", "read_jsonl", "read_jsonl_diagnosis", "chrome_trace",
    "write_chrome", "render_summary",
    "DIAGNOSIS_SCHEMA", "CAUSES", "ARMS", "DiagnoseConfig", "diagnose",
    "cause_counts", "write_diagnosis_report", "render_diagnosis_markdown",
    "span", "totals", "since", "collect_provenance",
]
