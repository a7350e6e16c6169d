"""Host milliseconds inside a statement's execution with nothing awaited:
the `result_span` (what the benchmark's `execute` annotation times from
outside) minus the `host_read` waits of the same execution, per statement
of the traced slice. What the trace shows as idle under `execute`."""

from benchmarks.layer_metrics._spans import reads_of, slice_results

LAYER = "executor + fused pipelines"
UNIT = "ms"
MOVES = "stmt_p50_ms"
SOURCE = "program_span"


def read(run):
    results = slice_results(run)
    if not results:
        return None
    waited = sum(e["dur_ms"] for e in reads_of(run, results))
    return (sum(e["dur_ms"] for e in results) - waited) / len(results)
