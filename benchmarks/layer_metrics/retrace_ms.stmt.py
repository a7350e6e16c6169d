"""Milliseconds a statement of the traced slice spent tracing, lowering,
loading and compiling: the sum of `compile_ms` (`trace`, `lower`, `load`,
`compile`) over the spans of the slice's executions. What `compiles.window`
cannot see: a pipeline built anew at every execution re-traces and re-loads
its executable from the caches without one fresh backend compile. One of the
four parts of `exec_host_ms.stmt`."""

from benchmarks.layer_metrics._hostsplit import part

LAYER = "executor + fused pipelines"
UNIT = "ms"
MOVES = "stmt_p50_ms"
SOURCE = "program_span"


def read(run):
    return part(run, "retrace")
