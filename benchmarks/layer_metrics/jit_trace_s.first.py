"""Seconds of the first pass inside jax's trace and lower stages
(`xla_compile` stage `trace` or `lower`): Python turned into programs, which
no disk cache saves. The union of the spans, since a function traced inside
another's trace reports both."""

from benchmarks.layer_metrics._spans import FIRST, compile_stages, union_s

LAYER = "compile caches"
UNIT = "s"
MOVES = "first_pass_s"
SOURCE = "program_span"


def read(run):
    events = compile_stages(run, FIRST, ("trace", "lower"))
    return None if events is None else union_s(events)
