"""Programs XLA compiled inside the window: jax's own count of backend
compile requests minus those its persistent cache served. The guard that
nothing compiles in the measured window: every statement was rehearsed, so
this reads 0, and a change that makes the window build programs again
shows here before it shows in `replay_qps`."""

from benchmarks import lib

LAYER = "compile caches"
UNIT = "count"
MOVES = "replay_qps"
SOURCE = "program_counter"


def read(run):
    through, served = lib.compiles_of(run["counters"]["window_close"]["jax"])
    through0, served0 = lib.compiles_of(run["counters"]["rehearsal_end"]["jax"])
    return (through - through0) - (served - served0)
