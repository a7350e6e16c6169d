"""Programs XLA compiled anew per rehearsal statement: `xla_compile` stage
`compile` that jax's persistent cache did not serve. The programs no cache
keeps, met again in every process; their names are on the events (`fun`)."""

from benchmarks.layer_metrics._spans import REHEARSAL, compile_stages

LAYER = "compile caches"
UNIT = "compiles/stmt"
MOVES = "new_stmt_ms"
SOURCE = "program_counter"


def read(run):
    events = compile_stages(run, REHEARSAL, ("compile",))
    if events is None or not run.get("rehearsal"):
        return None
    return sum(1 for e in events if not e["cached"]) / len(run["rehearsal"])
