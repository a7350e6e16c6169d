"""Milliseconds of cardinality-feedback store I/O a statement of the window
paid: the `feedback_flush` spans that ended inside the window, over the
window's statements. The program writes its store where a session's work
ends, never inside a statement, so this reads 0.0; a write that finds its
way back onto the statement's path shows here first. Nothing where the
program emits no such span at all (a program from before it)."""

from benchmarks.layer_metrics._spans import WINDOW, between

LAYER = "executor + fused pipelines"
UNIT = "ms"
MOVES = "stmt_p50_ms"
SOURCE = "program_span"


def read(run):
    statements = run.get("statements")
    if not statements or not any(
            e.get("kind") == "feedback_flush" for e in run.get("events", ())):
        return None
    flushes = between(run, "feedback_flush", WINDOW)
    return sum(e["dur_ms"] for e in flushes) / len(statements)
