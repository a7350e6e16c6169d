"""Host milliseconds a statement of the traced slice spent launching: the sum
of `launch_ms_by` over the `op_span`s and the `result_span` of the slice's
executions: time inside outermost seamed kernel calls, fused-pipeline calls
and `eager:<site>` seams (eager `jnp` work outside every kernel entry), less
the reads and compile stages inside them. By name in the spans themselves;
one of the four parts of `exec_host_ms.stmt`."""

from benchmarks.layer_metrics._hostsplit import part

LAYER = "executor + fused pipelines"
UNIT = "ms"
MOVES = "stmt_p50_ms"
SOURCE = "program_span"


def read(run):
    return part(run, "launch")
