"""Milliseconds a statement of the traced slice spent inside set
operations themselves: the own (exclusive) time of the `op_span`s of SetOp
nodes, the time of their inputs taken out: for INTERSECT / EXCEPT the
DISTINCT of the left side, the candidate join over whole rows and its
verification; for UNION the concatenation and the DISTINCT; for a UNION ALL
that was not blocked the concatenation alone. Host time of the operator's
thread, device work awaited inside it included. Reads the spans that say
their `op`; nothing where none does (a program from before the field)."""

from benchmarks.layer_metrics._xchan import (
    per_statement, setop_spans, wrote)

LAYER = "executor + fused pipelines"
UNIT = "ms"
MOVES = "stmt_p50_ms"
SOURCE = "program_span"


def read(run):
    if not wrote(run, "op_span", "op", node="SetOp"):
        return None
    return per_statement(run, sum(e["own_ms"] for e in setop_spans(run)))
