"""Blocking device-to-host reads per window statement: the program's
`host_read` events (row counts, join sizes, group counts, the collect).
Each stops the host until the device has caught up. A count: it repeats
exactly for one seed and one number of statements."""

from benchmarks.layer_metrics._spans import WINDOW, between

LAYER = "executor + fused pipelines"
UNIT = "reads/stmt"
MOVES = "stmt_p50_ms"
SOURCE = "program_counter"


def read(run):
    reads = between(run, "host_read", WINDOW)
    if not reads or not run.get("statements"):
        return None
    return len(reads) / len(run["statements"])
