"""Kernel launches per window statement as the program's launch seam counts
them: the `launches` of every `op_span` and `result_span` (one per kernel
entry point of `ops/kernels.py` and per fused-pipeline call, each at least
one program launch, and one per buffer for `take_columns`, the row gather,
which runs one jitted program a buffer). A count: it repeats exactly for one
seed and one number of statements."""

from benchmarks.layer_metrics._spans import WINDOW, between

LAYER = "kernels"
UNIT = "launches/stmt"
MOVES = "stmt_p50_ms"
SOURCE = "program_counter"


def read(run):
    if not run.get("statements"):
        return None
    spans = [e for kind in ("op_span", "result_span")
             for e in between(run, kind, WINDOW) if "launches" in e]
    if not spans:
        return None
    return sum(sum(e["launches"].values()) for e in spans) / len(
        run["statements"])
