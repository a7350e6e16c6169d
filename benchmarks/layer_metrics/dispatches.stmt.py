"""Fused-pipeline executions (`pipeline_span` events) per window
statement. A count: it repeats exactly for one seed and one number of
statements, and says nothing about time."""

from benchmarks.lib import events_between

LAYER = "executor + fused pipelines"
UNIT = "dispatches/stmt"
MOVES = "stmt_p50_ms"
SOURCE = "program_span"


def read(run):
    if not run["statements"] or not run.get("events"):
        return None
    spans = events_between(run, "pipeline_span", "window_open", "window_close")
    return len(spans) / len(run["statements"])
