"""Windows of blocked union-aggregation per statement of the traced slice:
the `windows` of the `blocked_union` spans that ended there. 0.0 is a
reading: every UNION ALL under an aggregate ran unblocked, as a SetOp span
with `op` union_all (one concatenation, inside `setop_ms.stmt`). Nothing
where the program says neither: no `blocked_union` with a duration and no
SetOp span with its `op` (a program from before both)."""

from benchmarks.layer_metrics._spans import SLICE, between
from benchmarks.layer_metrics._xchan import per_statement, wrote

LAYER = "executor + fused pipelines"
UNIT = "windows/stmt"
MOVES = "stmt_p50_ms"
SOURCE = "program_span"


def read(run):
    if not (wrote(run, "blocked_union", "dur_ms")
            or wrote(run, "op_span", "op", node="SetOp")):
        return None
    blocked = [e for e in between(run, "blocked_union", SLICE)
               if "dur_ms" in e]
    return per_statement(run, sum(e["windows"] for e in blocked))
