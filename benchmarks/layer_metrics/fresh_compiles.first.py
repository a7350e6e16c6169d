"""Programs XLA compiled anew inside the measured child's first pass:
`xla_compile` stage `compile` that jax's persistent cache did not serve.
Says whether the pass found its disk caches warm: the count repeats from
run to run of one seed, and a run that reads more met shapes no earlier
process had left on disk."""

from benchmarks.layer_metrics._spans import FIRST, compile_stages

LAYER = "compile caches"
UNIT = "count"
MOVES = "first_pass_s"
SOURCE = "program_counter"


def read(run):
    events = compile_stages(run, FIRST, ("compile",))
    if events is None:
        return None
    return sum(1 for e in events if not e["cached"])
