"""Seconds of the first pass inside jax's backend-compile stage
(`xla_compile` stage `compile`), whether XLA compiled the program or jax's
persistent cache served it: with warm disk caches, executable load."""

from benchmarks.layer_metrics._spans import FIRST, compile_stages, union_s

LAYER = "compile caches"
UNIT = "s"
MOVES = "first_pass_s"
SOURCE = "program_span"


def read(run):
    events = compile_stages(run, FIRST, ("compile",))
    return None if events is None else union_s(events)
