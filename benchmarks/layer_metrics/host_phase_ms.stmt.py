"""Host milliseconds a statement of the traced slice spent in named phases
that launch nothing: the sum of `host_ms` over the spans of the slice's
executions (the vocabulary is `nds_tpu/obs/tally.py PHASES`: plan-cache,
exec-lookup, pipeline-build, scan, join-plan, dict-merge, feedback, to-arrow,
span-emit). One of the four parts of `exec_host_ms.stmt`."""

from benchmarks.layer_metrics._hostsplit import part

LAYER = "executor + fused pipelines"
UNIT = "ms"
MOVES = "stmt_p50_ms"
SOURCE = "program_span"


def read(run):
    return part(run, "phase")
