"""Milliseconds a statement of the traced slice spent blocked in
device-to-host reads (`host_read.dur_ms` of the slice's executions): device
work the host waited for, plus the copies. Beside `device_busy_ms.stmt` of
the same slice; with `exec_host_ms.stmt` it adds up to the `result_span`."""

from benchmarks.layer_metrics._spans import reads_of, slice_results

LAYER = "executor + fused pipelines"
UNIT = "ms"
MOVES = "stmt_p50_ms"
SOURCE = "program_span"


def read(run):
    results = slice_results(run)
    if not results:
        return None
    return sum(e["dur_ms"] for e in reads_of(run, results)) / len(results)
