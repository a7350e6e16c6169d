"""Seconds of the measured child's first pass inside XLA compiles that jax's
persistent cache did not serve (`xla_compile` stage `compile`, `cached`
false; the union of their intervals). Beside `fresh_compiles.first`: the
same count at more seconds is the host, more programs is a cold cache."""

from benchmarks.layer_metrics._spans import FIRST, compile_stages, union_s

LAYER = "compile caches"
UNIT = "s"
MOVES = "first_pass_s"
SOURCE = "program_span"


def read(run):
    events = compile_stages(run, FIRST, ("compile",))
    if events is None:
        return None
    return union_s([e for e in events if not e["cached"]])
