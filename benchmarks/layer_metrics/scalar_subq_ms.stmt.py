"""Milliseconds a statement of the traced slice spent evaluating scalar
subqueries whose plan ran: the `dur_ms` of the `scalar_subquery` spans with
`source` executed, each inclusive of its plan's operators and of the one
blocking read that fetches the value. query9 holds fifteen over
store_sales, each scanning all 23 columns (`cols_read`) until
`prune_columns` walks subquery plans. Nothing where the program writes no
such event."""

from benchmarks.layer_metrics._xchan import per_statement, subqueries

LAYER = "executor + fused pipelines"
UNIT = "ms"
MOVES = "replay_qps"
SOURCE = "program_span"


def read(run):
    ran = subqueries(run)
    if ran is None:
        return None
    return per_statement(run, sum(e["dur_ms"] for e in ran))
