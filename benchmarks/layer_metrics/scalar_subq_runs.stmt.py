"""Scalar subqueries whose plan ran, per statement of the traced slice:
the count of `scalar_subquery` spans with `source` executed. A query9 in
full runs fifteen, so a slice of the mix's five statements reads 3.0; one
the session's cache answered (`source` session-cache) is not counted, so a
fall says answers were served and not recomputed. Nothing where the program
writes no such event."""

from benchmarks.layer_metrics._xchan import per_statement, subqueries

LAYER = "executor + fused pipelines"
UNIT = "runs/stmt"
MOVES = "replay_qps"
SOURCE = "program_span"


def read(run):
    ran = subqueries(run)
    if ran is None:
        return None
    return per_statement(run, len(ran))
