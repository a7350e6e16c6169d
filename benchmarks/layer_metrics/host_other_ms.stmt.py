"""Host milliseconds a statement of the traced slice spent under no name:
`result_span.dur_ms` minus the `host_read` waits, minus `launch_ms.stmt`,
`retrace_ms.stmt` and `host_phase_ms.stmt` of the same executions. The
remainder no seam, compile stage or phase covers: the tracing's own score,
never spread over the other three. The four add up to `exec_host_ms.stmt`."""

from benchmarks.layer_metrics._hostsplit import part

LAYER = "executor + fused pipelines"
UNIT = "ms"
MOVES = "stmt_p50_ms"
SOURCE = "program_span"


def read(run):
    return part(run, "other")
